"""The plain references: independent of the program, and agreeing with
it where both are exact."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import bench_fixtures
import harness
import weights
from reference import cgra, qwen3

HERE = Path(__file__).resolve().parents[1]
POOL = sorted((HERE / "data" / "table2").glob("*.json"))


def test_pool_holds_distinct_mappings_of_every_job():
    """One file per (kernel, job) of the sweep that stored mappings: all 30
    TABLE2 kernels and every job of the configuration.  The sweep stores
    one artifact per job, so two jobs that found the same mapping
    (dwconv_u1 on plaid and plaid_ml) are both sent."""
    from repro.core.workloads import TABLE2

    cfg = json.loads((HERE / "configs" / "table2.json").read_text())
    jobs = {f"{w.name}_u{w.unroll}__{f['job']}"
            for w in TABLE2 for f in cfg["fabrics"]}
    names = {p.stem for p in POOL}
    assert names <= jobs
    modulo = {n for n in jobs if not n.endswith("__spatial")}
    assert len(names & modulo) >= 0.9 * len(modulo)
    assert {n.split("__")[0] for n in names} == {n.split("__")[0]
                                                 for n in jobs}
    assert {n.split("__")[1] for n in names} == {f["job"]
                                                 for f in cfg["fabrics"]}
    records = [json.dumps(m, sort_keys=True) for p in POOL
               for m in json.loads(p.read_text())["mappings"]]
    assert len(set(records)) >= len(records) - 2
    assert sum(p.stat().st_size for p in POOL) < 2_000_000


@pytest.mark.parametrize("path", POOL, ids=lambda p: p.stem)
def test_pool_mapping_is_accepted_by_the_oracle_and_the_reference(path):
    from repro.compiler.artifact import CompileResult
    from repro.core.simulate import simulate

    data = json.loads(path.read_text())
    mappings = CompileResult.from_json(data).rebuild_mappings()
    assert len(mappings) == len(data["mappings"]) >= 1
    for mapping, record in zip(mappings, data["mappings"]):
        want = simulate(mapping, iterations=3)    # raises if it rejects
        ok, got, reason = cgra.simulate(record, 3)
        assert ok, reason
        assert cgra.value_gap(got, want) == (0.0, 0)


def test_cgra_reference_rejects_a_mistimed_mapping():
    rec = json.loads(POOL[0].read_text())["mappings"][0]
    n = next(k for k, t in rec["time"].items() if t > 0
             and any(e[0] == int(k) for e in rec["dfg"]["edges"]))
    bad = dict(rec, time=dict(rec["time"], **{n: rec["time"][n] + 1}))
    ok, _, reason = cgra.simulate(bad, 3)
    assert not ok and reason


def test_bfloat16_control_fails_the_cell_value_limit():
    cell = json.loads((HERE / "cells" /
                       "verify_sweep.table2.json").read_text())
    worst = 0.0
    for rec in (m for p in POOL for m in json.loads(p.read_text())[
            "mappings"]):
        _, exact, _ = cgra.simulate(rec, 3)
        _, low, _ = cgra.simulate(rec, 3, round_to="bfloat16",
                                  tol=(float("inf"), 0.0))
        worst = max(worst, cgra.value_gap(low, exact)[0])
    assert worst > cell["limits"]["value_gap"]


def test_reference_weights_are_the_served_weights():
    c = bench_fixtures.TINY_QWEN
    params = weights.make_params(c, 2**33 + 5)
    for i in range(c["num_hidden_layers"]):
        lay = weights.layer_params(c, 2**33 + 5, i)
        np.testing.assert_array_equal(
            np.asarray(params["layers"]["mlp"]["w2"][i], np.float32),
            np.asarray(lay["mlp/w2"]))
        np.testing.assert_array_equal(
            np.asarray(params["layers"]["attn"]["q_norm"][i], np.float32),
            np.asarray(lay["attn/q_norm"]))
    top = weights.top_params(c, 2**33 + 5)
    np.testing.assert_array_equal(np.asarray(params["emb"], np.float32),
                                  np.asarray(top["emb"]))


def test_qwen3_reference_agrees_with_the_program_in_float32():
    import jax
    import jax.numpy as jnp

    from repro.models import zoo

    c = bench_fixtures.TINY_QWEN
    cm = harness.load_module(HERE / "configs" / "qwen3_14b_d10.py")
    cfg = cm.model_config(c)
    seed = 77
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          cm.make_params(c, cfg, seed))
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 24))
    with jax.default_matmul_precision("highest"):
        h = zoo.forward(cfg, params, {"tokens": jnp.asarray(toks)})
        got = np.asarray(h @ params["emb"].T)
    want = qwen3.logits(c, seed, list(toks), list(range(24)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and the float8 control does not
    low = qwen3.logits(c, seed, list(toks), list(range(24)),
                       quant="float8_e4m3fn")
    assert np.abs(low - want).max() > 1e-2


def test_served_gaps():
    ref = np.array([[[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]]])
    assert qwen3.served_gaps(ref, np.array([[1, 2]])).tolist() == [[0.0,
                                                                     0.5]]
