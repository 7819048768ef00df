"""Fault-tolerant execution tier: taxonomy, fault injection, supervised
runner, cooperative deadlines, graceful degradation, torn-grid resume.

Covers the robustness contract end to end:

* the typed error taxonomy (``repro.compiler.errors``) — dual inheritance,
  distinct exit codes, JSON failure payloads;
* the fault-injection harness (``repro.compiler.faultinject``) — spec
  parsing, site/label/attempt scoping, the ``inject`` test helper;
* :class:`repro.core.runner.SupervisedRunner` — crash isolation, hard
  per-cell timeouts, bounded deterministic retry, fail-fast on
  deterministic errors;
* cooperative wall-clock deadlines (``compile(..., deadline_s=)``) —
  bounded overshoot, partial per-pass stats, bit-identity when the
  deadline does not fire;
* graceful degradation (``fallback_mapper=``) — timeout and infeasibility
  legs, the ``degraded`` provenance block, the never-cache-degraded rule;
* store fault tolerance — injected I/O errors are survived, torn entries
  are quarantined as misses;
* collect chaos — a crashed worker and a hung cell become structured
  failure records, the sweep completes, and a clean re-run heals exactly
  the failed cells back to the golden IIs (under ``spawn`` too);
* the bounded bench lock — a dead lock-holder strands the entry into a
  sidecar instead of hanging the run.
"""
import glob
import json
import os
import subprocess
import sys
import time

import pytest

from repro.compiler import faultinject
from repro.compiler.errors import (
    RETRYABLE_ERRORS,
    VERIFY_FAILURES,
    ArtifactError,
    CompileError,
    CompileTimeout,
    LockTimeout,
    MappingInfeasible,
    StoreIOError,
    WorkerCrashed,
    classify,
    exit_code_for,
)
from repro.compiler.faultinject import FaultSpecError
from repro.compiler.fsio import locked
from repro.compiler.pipeline import compile_key, compile_workload
from repro.compiler.registry import MAPPERS, register_mapper
from repro.compiler.store import ArtifactStore
from repro.core.runner import SupervisedRunner, run_supervised

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden_ii_quick.json")

with open(GOLDEN) as _f:
    _GOLDEN_II = json.load(_f)


# -- error taxonomy -----------------------------------------------------------


def test_taxonomy_exit_codes_distinct():
    classes = (CompileError, MappingInfeasible, CompileTimeout,
               WorkerCrashed, StoreIOError, ArtifactError, LockTimeout)
    codes = [c.exit_code for c in classes]
    assert len(set(codes)) == len(codes)
    assert all(c >= 10 for c in codes)  # 0/1/2 keep conventional meanings
    for c in classes:
        assert exit_code_for(c("x")) == c.exit_code
    assert exit_code_for(ValueError("x")) == 1
    assert exit_code_for(KeyboardInterrupt()) == 1


def test_taxonomy_dual_inheritance_preserves_old_handlers():
    # pre-taxonomy call sites caught these bases; they must keep working
    assert isinstance(MappingInfeasible("x"), ValueError)
    assert isinstance(ArtifactError("x"), ValueError)
    assert isinstance(StoreIOError("x"), OSError)
    assert isinstance(CompileTimeout("x"), TimeoutError)
    assert isinstance(LockTimeout("x"), TimeoutError)
    for c in (MappingInfeasible, CompileTimeout, WorkerCrashed,
              StoreIOError, ArtifactError, LockTimeout):
        assert issubclass(c, CompileError)


def test_taxonomy_to_json_payloads():
    e = CompileError("boom", cell="atax_u2/plaid")
    assert e.to_json() == {"error": "CompileError", "message": "boom",
                           "details": {"cell": "atax_u2/plaid"}}
    t = CompileTimeout("late", deadline_s=1.0, elapsed_s=1.23456,
                       where="negotiate round 7",
                       pass_stats=[{"name": "place", "wall_s": 1.0}])
    j = t.to_json()
    assert j["deadline_s"] == 1.0
    assert j["elapsed_s"] == 1.235
    assert j["where"] == "negotiate round 7"
    assert j["pass_stats"][0]["name"] == "place"
    w = WorkerCrashed("died", exitcode=-9)
    assert w.to_json()["exitcode"] == -9


def test_classify_labels():
    assert classify(CompileTimeout("x")) == "CompileTimeout"
    assert classify(OSError("x")) == "OSError"
    assert "OSError" in RETRYABLE_ERRORS
    assert "WorkerCrashed" in RETRYABLE_ERRORS
    assert AssertionError in VERIFY_FAILURES


# -- fault-injection harness --------------------------------------------------


def test_faultinject_rejects_bad_specs(monkeypatch):
    monkeypatch.setenv(faultinject.ENV_VAR, "not json")
    with pytest.raises(FaultSpecError):
        faultinject.active_faults()
    monkeypatch.setenv(faultinject.ENV_VAR, '{"mode": "crash"}')  # not a list
    with pytest.raises(FaultSpecError):
        faultinject.active_faults()
    monkeypatch.setenv(faultinject.ENV_VAR, '[{"mode": "meltdown"}]')
    with pytest.raises(FaultSpecError):
        faultinject.active_faults()
    monkeypatch.setenv(faultinject.ENV_VAR,
                       '[{"mode": "crash", "attempts": "0"}]')
    with pytest.raises(FaultSpecError):
        faultinject.active_faults()


def test_faultinject_inject_scopes_and_restores_env():
    assert faultinject.active_faults() == []
    with faultinject.inject({"mode": "oserror", "site": "store.get"}):
        assert faultinject.active_faults() == [
            {"mode": "oserror", "site": "store.get"}]
        with pytest.raises(OSError):
            faultinject.check("store.get", "anything")
        faultinject.check("store.put", "anything")  # other site: no-op
    assert faultinject.active_faults() == []
    faultinject.check("store.get", "anything")  # plan gone: no-op


def test_faultinject_match_attempts_and_times(monkeypatch):
    spec = {"mode": "oserror", "site": "worker", "match": "atax_u2/*",
            "attempts": [1], "times": 1}
    with faultinject.inject(spec):
        faultinject.check("worker", "atax_u2/plaid")  # attempt 0: no fire
        monkeypatch.setenv(faultinject.ATTEMPT_VAR, "1")
        faultinject.check("worker", "gemm_u2/plaid")  # label mismatch
        with pytest.raises(OSError):
            faultinject.check("worker", "atax_u2/plaid")
        faultinject.check("worker", "atax_u2/plaid")  # times=1: spent


def test_faultinject_maybe_corrupt_tears_file(tmp_path):
    p = tmp_path / "artifact.json"
    p.write_text(json.dumps({"k": list(range(100))}))
    before = p.read_bytes()
    assert not faultinject.maybe_corrupt(str(p), "store.put", "x")  # no plan
    with faultinject.inject({"mode": "corrupt", "site": "store.put"}):
        assert faultinject.maybe_corrupt(str(p), "store.put", "x")
    after = p.read_bytes()
    assert after != before and len(after) < len(before)
    with pytest.raises(ValueError):
        json.loads(after)


# -- supervised runner --------------------------------------------------------
# task functions must be top-level (picklable under spawn)


def _task_ok(task):
    return task * 2


def _task_crash(task):
    os._exit(137)


def _task_hang(task):
    time.sleep(60)
    return task


def _task_flaky(task):
    # transient: fails on the first attempt, heals on retry
    if int(os.environ.get(faultinject.ATTEMPT_VAR, "0")) == 0:
        raise OSError("transient I/O blip")
    return task


def _task_boom(task):
    raise ValueError("deterministic bug")


def _task_sim_backend(task):
    from repro.sim.batch import ENV_BACKEND, select_backend

    return os.environ.get(ENV_BACKEND), select_backend("auto")


def _drain(stream):
    oks, fails = {}, {}
    for task, status, payload in stream:
        assert task not in oks and task not in fails  # exactly-once
        (oks if status == "ok" else fails)[task] = payload
    return oks, fails


def test_runner_all_ok_streams_every_task():
    oks, fails = _drain(run_supervised(_task_ok, [1, 2, 3, 4, 5], jobs=3))
    assert oks == {i: i * 2 for i in (1, 2, 3, 4, 5)}
    assert fails == {}


def test_runner_detects_dead_worker_and_retries():
    oks, fails = _drain(
        run_supervised(_task_crash, ["c"], retries=1, backoff_s=0.01))
    assert oks == {}
    f = fails["c"]
    assert f.error == "WorkerCrashed"
    assert f.attempts == 2  # crash is retryable: first try + one retry
    assert f.exitcode == 137
    assert "137" in f.message
    assert f.to_json()["exitcode"] == 137


def test_runner_transient_error_heals_on_retry():
    oks, fails = _drain(
        run_supervised(_task_flaky, ["t"], retries=1, backoff_s=0.01))
    assert fails == {}
    assert oks == {"t": "t"}


def test_runner_deterministic_error_fails_fast():
    oks, fails = _drain(
        run_supervised(_task_boom, ["b"], retries=3, backoff_s=0.01))
    f = fails["b"]
    assert f.error == "ValueError"
    assert f.attempts == 1  # not retryable: retries must not be burned
    assert "deterministic bug" in f.message
    assert "deterministic bug" in f.traceback


def test_runner_hard_timeout_reclaims_hung_worker():
    t0 = time.monotonic()
    oks, fails = _drain(
        run_supervised(_task_hang, ["h"], timeout_s=1.0))
    assert time.monotonic() - t0 < 10.0  # not the 60s the task sleeps
    f = fails["h"]
    assert f.error == "CompileTimeout"
    assert f.attempts == 1  # timeouts are not retried by default
    assert "1.0" in f.message


def test_runner_mixed_grid_completes():
    def label(t):
        return f"cell/{t}"

    runner = SupervisedRunner(_task_ok, jobs=2, retries=0, label=label)
    oks, fails = _drain(runner.run(list(range(7))))
    assert len(oks) == 7 and not fails


def test_runner_workers_pin_host_sim_backend(monkeypatch):
    """A chip belongs to one process: a worker verifies on the host numpy
    backend even when the parent asked for a device backend."""
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jnp")
    oks, fails = _drain(run_supervised(_task_sim_backend, ["w"]))
    assert not fails
    assert oks["w"] == ("numpy", "numpy")
    assert os.environ["REPRO_SIM_BACKEND"] == "jnp"  # parent untouched


# -- cooperative deadlines ----------------------------------------------------


def test_compile_deadline_raises_within_bound():
    deadline = 0.05
    t0 = time.perf_counter()
    with pytest.raises(CompileTimeout) as ei:
        compile_workload("jacobi", unroll=4, deadline_s=deadline)
    elapsed = time.perf_counter() - t0
    # the cooperative checks must fire well inside 2x the deadline (plus a
    # constant frontend allowance: the DFG build is not under the deadline)
    assert elapsed < max(2 * deadline, deadline + 1.0)
    e = ei.value
    assert isinstance(e, TimeoutError)
    assert e.deadline_s == pytest.approx(deadline, abs=0.01)
    assert e.elapsed_s is not None and e.elapsed_s >= deadline
    assert e.where  # the checkpoint that fired is attributable
    # the partial per-pass stats collected so far ride along
    assert isinstance(e.pass_stats, list)
    assert all("name" in row for row in e.pass_stats)


def test_compile_generous_deadline_is_bit_identical():
    a = compile_workload("atax", unroll=2)
    b = compile_workload("atax", unroll=2, deadline_s=600.0)
    assert b.degraded is None
    assert (a.ii, a.cycles, a.makespan) == (b.ii, b.cycles, b.makespan)
    assert a.mappings == b.mappings  # pure clock reads: no RNG perturbation
    assert b.ii == _GOLDEN_II["atax_u2"]["plaid"]


# -- graceful degradation -----------------------------------------------------


@pytest.fixture
def never_maps(monkeypatch):
    """A test mapper that always exhausts its II range, registered for one
    test only: left registered, it would reach every later test of the
    process that walks the registry.  No ``jobs`` metadata: it must NOT
    extend the collect grid."""
    monkeypatch.setattr(MAPPERS, "_items", dict(MAPPERS._items))
    monkeypatch.setattr(MAPPERS, "_meta", dict(MAPPERS._meta))

    @register_mapper("_rt_never_maps",
                     description="test-only: always infeasible")
    class _NeverMaps:
        def __init__(self, arch, seed=0, time_budget=None):
            pass

        def map(self, dfg):
            return None
    return "_rt_never_maps"


def test_fallback_on_timeout_degrades_instead_of_raising():
    res = compile_workload("jacobi", unroll=4, deadline_s=0.05,
                           fallback_mapper="node_greedy")
    d = res.degraded
    assert d is not None
    assert d["requested_mapper"] == "hierarchical"
    assert d["fallback"] == "node_greedy"
    assert d["reason"] == "timeout"
    assert d["deadline_s"] == 0.05
    assert d["elapsed_s"] >= 0.05
    assert res.mapper == "node_greedy"  # artifact records what actually ran
    assert res.ii is not None  # the cheap fallback produced a mapping


def test_fallback_on_infeasibility(never_maps):
    name = never_maps
    bare = compile_workload("atax", unroll=2, mapper=name)
    assert bare.ii is None and bare.degraded is None  # no fallback: unmapped
    with pytest.raises(MappingInfeasible):
        bare.simulate()  # nothing to replay
    res = compile_workload("atax", unroll=2, mapper=name,
                           fallback_mapper="node_greedy")
    d = res.degraded
    assert d == {"requested_mapper": name, "fallback": "node_greedy",
                 "reason": "infeasible"}
    assert res.mapper == "node_greedy"
    # the fallback leg is the same deterministic compile a direct request
    # for the fallback mapper would have run
    direct = compile_workload("atax", unroll=2, mapper="node_greedy")
    assert (res.ii, res.cycles) == (direct.ii, direct.cycles)


def test_degraded_artifact_roundtrips_schema_v5(tmp_path):
    from repro.compiler.artifact import ARTIFACT_SCHEMA, CompileResult

    assert ARTIFACT_SCHEMA == "repro.compiler/artifact@5"
    res = compile_workload("jacobi", unroll=4, deadline_s=0.05,
                           fallback_mapper="node_greedy")
    path = str(tmp_path / "degraded.json")
    res.save(path)
    loaded = CompileResult.load(path)
    assert loaded.degraded == res.degraded
    assert loaded.summary()["degraded"] == res.degraded
    # non-degraded artifacts carry an explicit null (schema invariant) and
    # keep their summary free of degradation noise
    clean = compile_workload("atax", unroll=2, mapper="node_greedy")
    assert clean.to_json()["degraded"] is None
    assert "degraded" not in clean.summary()


def test_degraded_results_are_never_stored(tmp_path, never_maps):
    name = never_maps
    store = ArtifactStore(str(tmp_path / "store"))
    res = compile_workload("atax", unroll=2, mapper=name,
                           fallback_mapper="node_greedy", store=store)
    assert res.degraded is not None and res.store_hit is False
    # neither under the requested mapper's key (it would serve the wrong
    # mapper's output) nor under the fallback's (never ran standalone)
    assert store.get(compile_key("atax", unroll=2, mapper=name)) is None
    assert store.get(
        compile_key("atax", unroll=2, mapper="node_greedy")) is None


# -- store fault tolerance ----------------------------------------------------


def test_store_read_fault_falls_back_to_compile(tmp_path):
    store_path = str(tmp_path / "store")
    a = compile_workload("atax", unroll=2, mapper="node_greedy",
                         store=store_path)
    assert a.store_hit is False  # cold
    with faultinject.inject({"mode": "oserror", "site": "store.get"}):
        b = compile_workload("atax", unroll=2, mapper="node_greedy",
                             store=store_path)
    assert b.store_hit is False  # read failed: compiled fresh, not crashed
    assert (b.ii, b.cycles) == (a.ii, a.cycles)
    c = compile_workload("atax", unroll=2, mapper="node_greedy",
                         store=store_path)
    assert c.store_hit is True  # the store itself is intact


def test_store_write_fault_leaves_result_uncached(tmp_path):
    store_path = str(tmp_path / "store")
    with faultinject.inject({"mode": "oserror", "site": "store.put"}):
        a = compile_workload("atax", unroll=2, mapper="node_greedy",
                             store=store_path)
    assert a.ii is not None and a.store_hit is False
    b = compile_workload("atax", unroll=2, mapper="node_greedy",
                         store=store_path)
    assert b.store_hit is False  # the faulted write cached nothing
    assert (b.ii, b.cycles) == (a.ii, a.cycles)


def test_store_io_errors_are_typed(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    res = compile_workload("atax", unroll=2, mapper="node_greedy")
    key = compile_key("atax", unroll=2, mapper="node_greedy")
    with faultinject.inject({"mode": "oserror", "site": "store.put"}):
        with pytest.raises(StoreIOError):
            store.put(res, key=key)
    store.put(res, key=key)
    with faultinject.inject({"mode": "oserror", "site": "store.get"}):
        with pytest.raises(StoreIOError):
            store.get(key)


def test_store_torn_entry_quarantined_as_miss(tmp_path):
    store = ArtifactStore(str(tmp_path / "store"))
    res = compile_workload("atax", unroll=2, mapper="node_greedy")
    key = compile_key("atax", unroll=2, mapper="node_greedy")
    with faultinject.inject({"mode": "corrupt", "site": "store.put"}):
        store.put(res, key=key)  # committed, then torn on disk
    assert store.get(key) is None  # integrity check: miss, not bad data
    assert store.counters.rejected == 1
    # the torn file was quarantined, so a re-put works cleanly
    store.put(res, key=key)
    again = store.get(key)
    assert again is not None and again.ii == res.ii


# -- bounded locks ------------------------------------------------------------


def test_locked_timeout_raises_lock_timeout(tmp_path):
    target = str(tmp_path / "data.json")
    t0 = time.monotonic()
    with locked(target):  # a second open fd conflicts under flock
        with pytest.raises(LockTimeout):
            with locked(target, timeout_s=0.2):
                pass
    assert time.monotonic() - t0 < 5.0
    with locked(target, timeout_s=0.2):  # released: reacquirable
        pass


def test_append_bench_strands_entry_on_dead_lock_holder(tmp_path):
    from repro.core.collect import _append_bench

    bench = str(tmp_path / "bench.json")
    with locked(bench):  # simulate a dead/hung lock-holder
        _append_bench(bench, {"note": "stranded run"}, lock_timeout_s=0.2)
        sidecars = glob.glob(bench + ".stranded-*.json")
        assert len(sidecars) == 1  # entry preserved, run not hung
        with open(sidecars[0]) as f:
            assert json.load(f)["runs"] == [{"note": "stranded run"}]
        assert not os.path.exists(bench)
    # the next successful locked append reclaims the sidecar: its runs
    # merge back into the trajectory and the sidecar file is removed
    _append_bench(bench, {"note": "healthy"}, lock_timeout_s=5.0)
    with open(bench) as f:
        assert json.load(f)["runs"] == [{"note": "stranded run"},
                                        {"note": "healthy"}]
    assert glob.glob(bench + ".stranded-*.json") == []


# -- collect chaos: torn grids heal -------------------------------------------


def _assert_golden(rec, key):
    # REPRO_QUICK (pytest --quick) clamps SA budgets, which legitimately
    # drifts the budget-sensitive grid cells; the headline mappers are
    # budget-insensitive on this slice (the same contract
    # test_routing_equivalence gates).  The full-grid golden diff belongs
    # to scripts/ci.sh, which runs collect with REPRO_QUICK unset.
    jobs = (("plaid", "st") if os.environ.get("REPRO_QUICK")
            else tuple(_GOLDEN_II[key]))
    for job in jobs:
        assert rec["ii"][job] == _GOLDEN_II[key][job], (job, rec["ii"])


def test_collect_survives_crash_and_hang_then_heals(tmp_path):
    """The chaos contract end to end: a worker crash and a hung cell are
    recorded as structured failures (the sweep completes), and a clean
    re-run re-attempts exactly the failed cells, healing the record back
    to the golden IIs bit-identically."""
    from repro.core.collect import collect

    out = str(tmp_path / "results.json")
    bench = str(tmp_path / "bench.json")
    with faultinject.inject(
        {"mode": "crash", "site": "worker", "match": "atax_u2/plaid",
         "attempts": [0, 1]},
        {"mode": "hang", "site": "worker", "match": "atax_u2/st",
         "seconds": 120},
    ):
        r1 = collect(out, quick=True, jobs=2, bench_path=bench,
                     workloads=["atax_u2"], cell_timeout_s=15.0, retries=1)
    rec = r1["atax_u2"]
    crash = rec["failures"]["plaid"]
    assert crash["error"] == "WorkerCrashed"
    assert crash["attempts"] == 2  # crashes are retried; both were injected
    assert crash["exitcode"] == 137
    hang = rec["failures"]["st"]
    assert hang["error"] == "CompileTimeout"
    assert hang["attempts"] == 1  # timeouts are not retried by default
    assert rec["ii"]["plaid"] is None and rec["ii"]["st"] is None
    assert rec["ii"]["node_on_plaid"] is not None  # rest of the row landed
    # the successful parts ride along for the resume
    assert "st" not in rec["partial_parts"]
    assert "node_on_plaid" in rec["partial_parts"]
    with open(bench) as f:
        assert json.load(f)["runs"][-1]["failed_cells"] == 2

    # clean re-run: only the two failed cells are re-attempted, and the
    # healed record is indistinguishable from a never-failed run
    r2 = collect(out, quick=True, jobs=2, bench_path=bench,
                 workloads=["atax_u2"])
    rec2 = r2["atax_u2"]
    assert "failures" not in rec2 and "partial_parts" not in rec2
    _assert_golden(rec2, "atax_u2")
    assert rec2["verified"] == {"plaid": True, "st": True}
    # the ride-along parts were merged, not recompiled: bit-identical
    assert rec2["ii"]["node_on_plaid"] == rec["ii"]["node_on_plaid"]
    assert rec2["cycles"]["node_on_plaid"] == rec["cycles"]["node_on_plaid"]
    # a third run has nothing left to do (the record is complete)
    r3 = collect(out, quick=True, jobs=2, bench_path=bench,
                 workloads=["atax_u2"])
    assert r3["atax_u2"] == rec2


def test_collect_spawn_matches_golden_with_plugins(tmp_path):
    """Registrations must survive the ``spawn`` start method (workers do
    not inherit interpreter state): built-ins re-register when the worker
    imports the pipeline, runtime plug-ins travel via ``REPRO_PLUGINS``."""
    from repro.core.collect import PLUGINS_VAR, collect

    sentinel = str(tmp_path / "plugin_imports.txt")
    (tmp_path / "rt_plugmod.py").write_text(
        "import os\n"
        "with open(os.environ['RT_PLUG_SENTINEL'], 'a') as f:\n"
        "    f.write(str(os.getpid()) + '\\n')\n"
    )
    sys.path.insert(0, str(tmp_path))
    os.environ["RT_PLUG_SENTINEL"] = sentinel
    try:
        res = collect(str(tmp_path / "results.json"), quick=True, jobs=2,
                      bench_path=str(tmp_path / "bench.json"),
                      workloads=["atax_u2"], start_method="spawn",
                      plugins=["rt_plugmod"])
        rec = res["atax_u2"]
        assert "failures" not in rec
        _assert_golden(rec, "atax_u2")  # spawn is bit-identical to fork
        with open(sentinel) as f:
            pids = {int(line) for line in f if line.strip()}
        # every spawn worker imported the plugin module, not just the parent
        assert pids - {os.getpid()}, "no spawn worker imported the plugin"
    finally:
        sys.path.remove(str(tmp_path))
        os.environ.pop("RT_PLUG_SENTINEL", None)
        os.environ.pop(PLUGINS_VAR, None)
        sys.modules.pop("rt_plugmod", None)


# -- CLI exit codes -----------------------------------------------------------


def _run_cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", "")).rstrip(
                             os.pathsep)
    return subprocess.run(
        [sys.executable, "-m", "repro.compiler", *argv],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300,
    )


def test_cli_timeout_maps_to_distinct_exit_code():
    r = _run_cli("compile", "jacobi", "-u", "4", "--deadline-s", "0.05")
    assert r.returncode == CompileTimeout.exit_code, r.stderr
    assert "CompileTimeout" in r.stderr
    assert "Traceback" not in r.stderr  # rendered, not dumped


def test_cli_fallback_degrades_to_success():
    r = _run_cli("compile", "jacobi", "-u", "4", "--deadline-s", "0.05",
                 "--fallback-mapper", "node_greedy")
    assert r.returncode == 0, r.stderr
    assert "DEGRADED(timeout -> node_greedy)" in r.stdout


def test_cli_unknown_mapper_is_usage_error_and_debug_reraises():
    r = _run_cli("compile", "atax", "-u", "2", "--mapper", "nope")
    assert r.returncode == 2
    assert "unknown mapper" in r.stderr
    assert "Traceback" not in r.stderr
    r = _run_cli("--debug", "compile", "atax", "-u", "2", "--mapper", "nope")
    assert r.returncode == 1
    assert "Traceback" in r.stderr  # --debug preserves the full traceback
