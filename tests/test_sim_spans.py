"""``repro.sim``'s spans, counters and named scopes.

Every ``simulate_batch`` call records its phases under one root span, in
call order, with the byte and build counters; ``wall_s`` is the root's
length; the jitted cycle loop names each op's phase in its metadata; and
the spans share the profiler's clock, so a record lands where its
``TraceAnnotation`` does in a profile.
"""
import copy
import glob
import os
import re

import pytest

from repro.core.mapper import HierarchicalMapper
from repro.sim import prepare_batch, simulate_batch, spans, step
from repro.sim.batch import COUNTERS, ROOT_SPAN

KERNELS = [("atax", 2), ("jacobi", 1)]

#: the phases each kind of call runs, in order
PHASES = {
    ("numpy", "cold"): ["sim.prepare", "sim.cycle_loop", "sim.check"],
    ("numpy", "prepared"): ["sim.cycle_loop", "sim.check"],
    ("jnp", "cold"): ["sim.prepare", "sim.upload", "sim.cycle_loop",
                      "sim.pullback", "sim.check"],
    ("jnp", "prepared"): ["sim.upload", "sim.cycle_loop", "sim.pullback",
                          "sim.check"],
}


@pytest.fixture(scope="module")
def mappings(workload_dfg, arch):
    out = []
    for name, unroll in KERNELS:
        m = HierarchicalMapper(arch("plaid2x2"), seed=0).map(
            workload_dfg(name, unroll))
        assert m is not None, f"{name}_u{unroll} failed to map"
        out.append(m)
    return out


def _call(mappings, backend, kind):
    prepared = (prepare_batch(mappings, iterations=3)
                if kind == "prepared" else None)
    res = simulate_batch(mappings, iterations=3, backend=backend,
                         prepared=prepared)
    assert all(v.ok for v in res)
    return res, prepared or prepare_batch(mappings, iterations=3)


@pytest.mark.parametrize("backend,kind", sorted(PHASES))
def test_spans_nest_under_the_root_in_call_order(mappings, backend, kind):
    res, _ = _call(mappings, backend, kind)
    root, *phases = res.spans
    assert root.name == ROOT_SPAN and root.parent is None
    assert [sp.name for sp in phases] == PHASES[backend, kind]
    assert all(sp.parent == ROOT_SPAN for sp in phases)
    edges = [root.start_ns] + [t for sp in phases
                               for t in (sp.start_ns, sp.end_ns)] + [
        root.end_ns]
    assert edges == sorted(edges)                 # inside the root, in turn
    assert res.wall_s == (root.end_ns - root.start_ns) / 1e9 > 0
    assert list(res.phases_ms()) == PHASES[backend, kind]
    assert spans._CURRENT.get() is None           # nothing left bound


@pytest.mark.parametrize("kind", ["cold", "prepared"])
def test_counters_count_what_crossed(mappings, kind):
    res, prepared = _call(mappings, "jnp", kind)
    pb = prepared.packed
    B, N, _, _, _ = pb.shape
    assert list(res.counters) == list(COUNTERS)
    assert res.counters["upload_bytes"] == sum(
        a.nbytes for a in step.device_args(pb))
    # val float32 and done bool per (mapping, node, iteration), fail bool
    assert res.counters["pullback_bytes"] == B * N * 3 * 5 + B
    numpy_res, _ = _call(mappings, "numpy", kind)
    assert numpy_res.counters == dict.fromkeys(COUNTERS, 0)


def test_runner_builds_once_per_shape(mappings):
    step._jit_runner.cache_clear()
    first = simulate_batch(mappings, iterations=3, backend="jnp")
    again = simulate_batch(mappings, iterations=3, backend="jnp")
    assert first.counters["runner_builds"] == 1
    assert again.counters["runner_builds"] == 0
    assert "runner_builds=0" in again.describe()


def test_scalar_fallback_span_exactly_when_a_mapping_falls_back(mappings):
    bad = copy.deepcopy(mappings[0])
    bad.dfg.edges[next(iter(bad.routes))].distance = -1
    for batch, fallbacks in (([mappings[0], bad], 1), (mappings, 0)):
        res = simulate_batch(batch, iterations=3, backend="numpy")
        names = [sp.name for sp in res.spans]
        assert res.n_scalar_fallback == fallbacks
        assert ("sim.scalar_fallback" in names) == (fallbacks > 0)


def _computations(hlo: str):
    """``name -> instruction lines`` of every computation in ``hlo``."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _while_body(hlo: str):
    """The instructions of the cycle loop's ``while`` body computation and
    of every computation it calls (fusions, scatter combiners)."""
    comps = _computations(hlo)
    loops = [line for body in comps.values() for line in body
             if " while(" in line]
    assert len(loops) == 1, loops
    todo = [re.search(r"body=%?([\w.\-]+)", loops[0]).group(1)]
    seen, lines = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        lines += comps[name]
        for line in comps[name]:
            todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)
    return lines


def _op_name(line: str):
    m = re.search(r'op_name="([^"]*)"', line)
    return m and m.group(1)


def test_cycle_loop_ops_carry_their_phase_scope(mappings):
    """The ``while`` body propagates values only: its ops sit under
    ``execute`` (``operand_read``, ``alu``, ``value_write``), none under
    ``presence`` or ``commit``; the static availability predicates run
    once per call before the loop, under ``sim_cycle_loop/commit`` and
    ``sim_cycle_loop/execute/presence``."""
    pb = prepare_batch(mappings, iterations=3).packed
    runner = step._jit_runner(pb.hmax, pb.iterations, pb.shape, False)
    hlo = runner.lower(*step.device_args(pb)).compile().as_text()
    body = _while_body(hlo)
    scoped, counter = [], []
    for line in body:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? (gather|scatter|fusion)"
                     r"\(", line)
        if not m:
            continue
        op_name = _op_name(line)
        assert "/sim_cycle_loop/" in op_name, line
        if op_name.endswith("/while/body/add"):   # the loop's own counter
            counter.append(m.group(1))
        else:
            scoped.append(op_name)
            assert re.search(r"/sim_cycle_loop/.*/execute/", op_name), line
            if m.group(2) == "gather":
                assert "/execute/operand_read/" in op_name, line
    assert len(counter) <= 1 and scoped
    names = {_op_name(line) for line in body} - {None}
    assert not [n for n in names if "/presence/" in n or "/commit/" in n]
    for step_name in ("operand_read", "alu", "value_write"):
        assert any(f"/execute/{step_name}/" in n for n in names), step_name
    hoisted = {_op_name(line) for line in hlo.splitlines()} - names - {None}
    for scope in ("sim_cycle_loop/commit/",
                  "sim_cycle_loop/execute/presence/"):
        assert any(scope in n and "/while/" not in n for n in hoisted), scope


def _profile_start_and_annotations(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    start, events = None, {}
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sim."):
                    events.setdefault(e.name, []).append(int(e.start_ns))
    return start, events


def test_spans_share_the_profilers_clock(mappings, tmp_path):
    import jax

    simulate_batch(mappings, iterations=3, backend="jnp")      # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = simulate_batch(mappings, iterations=3, backend="jnp")
    finally:
        jax.profiler.stop_trace()
    start, events = _profile_start_and_annotations(str(tmp_path))
    assert start is not None
    for sp in res.spans:
        (ann,) = events[sp.name]
        assert abs(start + ann - sp.start_ns) < 1_000_000, sp.name
