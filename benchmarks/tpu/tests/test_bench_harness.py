"""The harness end to end on the CPU at tiny sizes: cells, configurations
and metrics found by name, the result line, and ``correct`` coming out
false when the timed path is broken underneath."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import harness

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_by_name(cell):
    c = harness.find_cell(cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert hasattr(c.traffic, "Traffic") and c.traffic.SPANS
    assert c.config["name"] == c.entry["config"]
    for m in c.per_layer:                     # each reports what it moves
        assert m["moves"] in {e["name"] for e in c.end_to_end}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_layer_metric_has_its_reader(metric):
    assert callable(harness.reader(metric).read)


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
    with pytest.raises(KeyError):
        harness.find_cell("no_such.cell")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_limit_not_yet_set_never_passes():
    assert not harness.Check("served_logit_gap", 0.0, None).ok
    assert not harness.Check("value_gap", float("nan"), 1.0).ok
    assert harness.Check("value_gap", 0.0, 0.0).ok


def test_a_new_cell_is_new_files_and_entries(tiny_bench):
    """The tiny cells of the fixture are files and BENCHMARK.json entries
    added beside the real ones: no file the benchmark has is edited."""
    here, root = tiny_bench
    for path in HERE.rglob("*"):
        rel = path.relative_to(HERE)
        if path.is_file() and "tests" not in rel.parts \
                and "__pycache__" not in rel.parts:
            assert (here / rel).read_bytes() == path.read_bytes(), rel
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert bench["workloads"][:len(BENCH["workloads"])] == BENCH["workloads"]
    cell = harness.find_cell("serve_decode.tiny", here=here, root=root)
    assert cell.config["name"] == "qwen3_tiny"
    assert {m["name"] for m in cell.per_layer} == {
        "prefill_mfu", "decode_mfu", "decode_roofline", "device_idle.serve"}


def _run(tiny_bench, cpu, monkeypatch, capsys, cell, seed=2**33 + 1,
         seconds="0.2"):
    import run

    here, root = tiny_bench
    monkeypatch.setattr(harness, "peaks",
                        lambda kind, here=None: {"hbm_bytes_per_s": 819e9,
                                                 "bf16_flops_per_s": 197e12})
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     seconds, "--trace", "0"], devices=cpu, here=here,
                    root=root) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    return result


@pytest.mark.parametrize("cell,metrics", [
    ("verify_sweep.tiny", {"verify_mappings_per_s", "setup_s"}),
    ("serve_decode.tiny", {"output_tokens_per_s", "setup_s"}),
])
def test_tiny_run_is_correct(tiny_bench, cpu, monkeypatch, capsys, cell,
                             metrics):
    r = _run(tiny_bench, cpu, monkeypatch, capsys, cell)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == metrics
    here, root = tiny_bench
    traffic = harness.find_cell(cell, here=here, root=root).traffic
    assert set(traffic.END_TO_END) == metrics - {"setup_s"}
    assert set(r["checks"]) == set(traffic.CHECKS)
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1


def _alter_one_value(monkeypatch):
    import repro.sim.step as step

    real = step.run_bucket_jnp

    def broken(pb, use_pallas=False):
        val, done, fail = real(pb, use_pallas)
        val = val.copy()
        val[tuple(np.argwhere(done)[0])] += 1.0   # an answer altered
        return val, done, fail

    monkeypatch.setattr(step, "run_bucket_jnp", broken)


def _drop_half_the_bucket(monkeypatch):
    import repro.sim.step as step

    real = step.run_bucket_jnp

    def broken(pb, use_pallas=False):
        val, done, fail = real(pb, use_pallas)
        done = done.copy()
        done[len(done) // 2:] = False         # half of the batch left out
        return val, done, fail

    monkeypatch.setattr(step, "run_bucket_jnp", broken)


def _alter_one_token(monkeypatch):
    import repro.train.steps as steps

    real = steps.make_serve_step

    def make(cfg):
        step = real(cfg)

        def broken(params, cache, tokens):
            cache, nxt, logits = step(params, cache, tokens)
            return cache, nxt.at[0, 0].add(1) % cfg.vocab_size, logits
        return broken

    monkeypatch.setattr(steps, "make_serve_step", make)


def _drop_half_the_batch(monkeypatch):
    import repro.serve.loop as loop

    real = loop.generate

    def broken(cfg, params, prompts, max_new_tokens=16, **kw):
        tokens, info = real(cfg, params, prompts, max_new_tokens, **kw)
        return tokens[: tokens.shape[0] // 2], info

    monkeypatch.setattr(loop, "generate", broken)


@pytest.mark.parametrize("cell,fault", [
    ("verify_sweep.tiny", _alter_one_value),
    ("verify_sweep.tiny", _drop_half_the_bucket),
    ("serve_decode.tiny", _alter_one_token),
    ("serve_decode.tiny", _drop_half_the_batch),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(tiny_bench, cpu, monkeypatch,
                                          capsys, cell, fault):
    fault(monkeypatch)
    r = _run(tiny_bench, cpu, monkeypatch, capsys, cell)
    assert r["correct"] is False


@pytest.mark.parametrize("cell,seeds", [
    ("verify_sweep.tiny", [11, 12, 13]),
    ("serve_decode.tiny", [1, 2, 3]),
])
def test_controls_fail_and_the_program_passes(tiny_bench, cpu, cell, seeds,
                                             monkeypatch):
    """The precision control in the program's place fails the cell's
    limits at a size a test can hold; the program on the same seeds
    passes them."""
    import readings

    here, root = tiny_bench
    monkeypatch.setattr(harness, "peaks", lambda kind, here=None: {})
    recs = list(readings.readings(cell, seeds, len(seeds), devices=cpu,
                                  here=here, root=root))
    limits = harness.find_cell(cell, here=here, root=root).params["limits"]
    for r in recs:
        over = [k for k, v in r["checks"].items() if v > limits[k]]
        assert bool(over) == (r["side"] == "control"), r


def test_no_chip_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "verify_sweep.table2", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_draws_give_every_seed_the_same_bucket_shape(cpu):
    """Every seed sends every pool mapping once, in its own order, in one
    padded shape."""
    cell = harness.find_cell("verify_sweep.table2")
    shapes, orders = set(), set()
    for seed in (1, 2**33 + 7):
        ctx = harness.Context(cell=cell, seed=seed, devices=cpu, peaks={})
        t = cell.traffic.Traffic(ctx)
        t.setup()
        pb = t.prepared.packed
        shapes.add((pb.op_steps.shape, pb.hmax))
        orders.add(tuple(t.order.tolist()))
        assert sorted(t.order.tolist()) == list(range(len(t.pool)))
    assert len(shapes) == 1 and len(orders) == 2



@pytest.mark.parametrize("k,unanswered", [(8, ()), (3, ()), (8, (5,))])
def test_served_sample_takes_each_request_from_a_slot_of_its_own(
        cpu, k, unanswered):
    """A serve cell's sample draws each request from another row of the
    batch, the same from the same seed; a slot that never answered gives
    none."""
    import dataclasses

    cell = harness.find_cell("serve_decode.qwen3_14b_d10")
    cell = dataclasses.replace(cell, params={
        **cell.params, "clients": 8, "prompt_len": 4, "new_tokens": 2,
        "check_requests": k})
    ctx = harness.Context(cell=cell, seed=2**33 + 5, devices=cpu, peaks={})
    picks = []
    for _ in range(2):
        t = cell.traffic.Traffic(ctx)
        for _ in range(3):
            tokens = np.ones((8, 2), np.int32)
            tokens[list(unanswered)] = -1
            t.batches.append({"prompts": None, "tokens": tokens})
        picks.append(t._sample())
    slots = [r for _, r in picks[0]]
    assert len(set(slots)) == len(slots) == min(k, 8 - len(unanswered))
    assert not set(slots) & set(unanswered)
    assert all(0 <= i < 3 for i, _ in picks[0]) and picks[0] == picks[1]
