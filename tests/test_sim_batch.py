"""``repro.sim`` — batched cycle-accurate verification vs the scalar oracle.

The batched subsystem's contract is *parity*: for every mapping — valid or
deliberately corrupted — ``simulate_batch`` must reach the same accept /
reject decision as the frozen scalar simulator, and on accept the same
per-``(node, iter)`` values.  These tests pin that contract:

* lowering round-trips through JSON bit-identically;
* packing pads to power-of-two shapes with the documented sentinels;
* all three backends (numpy / jnp / pallas) pass the differential harness
  on real kernel mappings, including a recurrence (distance > 0) workload;
* random DAGs fuzz the same property through the hypothesis shim;
* corrupted mappings (dropped route, foreign place key, shifted issue)
  fail — or survive — identically on both sides;
* ``prepare_batch`` warm reruns reproduce the cold verdicts, and a stale
  ``PreparedBatch`` is rejected loudly;
* an injected backend fault (``sim.batch`` site) degrades
  ``CompileResult.simulate`` to the scalar oracle instead of serving an
  unverified artifact, while a failing Pallas kernel raises;
* the row-gridded Pallas ALU kernel equals the jnp where-ladder at shapes
  that are not multiples of its tile.
"""
import copy
import json

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, strategies as st

from repro.compiler import compile, faultinject
from repro.core.arch import make_arch
from repro.core.dfg import random_dag
from repro.core.mapper import HierarchicalMapper, NodeGreedyMapper
from repro.core.simulate import simulate
from repro.sim import (
    CompiledSim,
    LoweringUnsupported,
    lower_mapping,
    pack_bucket,
    prepare_batch,
    simulate_batch,
    verify_mappings,
)
from repro.sim import step
from repro.sim.check import DEFAULT_TOL, close, assert_differential
from repro.sim.lower import OPS
from repro.sim.step import NEVER, apply_ops_jnp

# (workload, unroll): atax_u2 is the quick-grid staple, dwconv_u1 a deep
# mul/mac chain, jacobi_u1 carries a distance>0 recurrence edge
KERNELS = [("atax", 2), ("dwconv", 1), ("jacobi", 1)]


@pytest.fixture(scope="module")
def mappings(workload_dfg, arch):
    out = []
    for name, unroll in KERNELS:
        m = HierarchicalMapper(arch("plaid2x2"), seed=0).map(
            workload_dfg(name, unroll))
        assert m is not None, f"{name}_u{unroll} failed to map"
        m.validate()
        out.append(m)
    return out


# -- lowering ----------------------------------------------------------------


def test_lowering_json_roundtrip(mappings):
    for m in mappings:
        cs = lower_mapping(m, iterations=3)
        # through real JSON text, not just the dict view
        back = CompiledSim.from_json(json.loads(json.dumps(cs.to_json())))
        assert back.ii == cs.ii and back.horizon == cs.horizon
        assert back.iterations == cs.iterations
        assert back.node_ids == cs.node_ids
        assert back.fail_static == cs.fail_static
        for f in (CompiledSim._INT_FIELDS + CompiledSim._BOOL_FIELDS
                  + CompiledSim._F64_FIELDS + ("op_kind",)):
            got, want = getattr(back, f), getattr(cs, f)
            assert got.shape == want.shape, f
            assert (got == want).all(), f
    # a non-record payload is rejected by schema, not mis-parsed
    with pytest.raises(ValueError, match="compiled@1"):
        CompiledSim.from_json({"schema": "something/else"})


def test_lowering_covers_recurrence(mappings):
    # the batch genuinely exercises distance > 0 (loop-carried) operands
    assert any(e.distance > 0 for m in mappings for e in m.dfg.edges)
    for m in mappings:
        if not any(e.distance > 0 for e in m.dfg.edges):
            continue
        cs = lower_mapping(m, iterations=3)
        assert (cs.op_dist > 0).any()


def test_lowering_rejects_negative_distance(mappings):
    # the static-availability derivation assumes dist >= 0; a corrupted
    # edge must route to the scalar oracle, not silently mis-verify
    mm = copy.deepcopy(mappings[0])
    idx = next(iter(mm.routes))
    mm.dfg.edges[idx].distance = -1
    with pytest.raises(LoweringUnsupported, match="negative distance"):
        lower_mapping(mm, iterations=3)
    res = simulate_batch([mm], iterations=3)
    assert res.n_scalar_fallback == 1
    assert res[0].backend == "scalar"


# -- packing -----------------------------------------------------------------


def test_pack_bucket_pow2_padding_and_sentinels(mappings):
    forms = [lower_mapping(m, iterations=3) for m in mappings]
    pb = pack_bucket(forms)
    B, N = pb.opcode.shape
    S = pb.step_src.shape[1]
    assert B == len(forms)
    # power-of-two with floors 8/16, covering the largest member
    assert N >= max(8, max(cs.n_nodes for cs in forms))
    assert S >= max(16, max(cs.n_steps for cs in forms))
    assert N & (N - 1) == 0 and S & (S - 1) == 0
    for b, cs in enumerate(forms):
        n, s = cs.n_nodes, cs.n_steps
        # padded node rows never execute, never compare, read as 0.0
        assert not pb.exec_mask[b, n:].any()
        assert not pb.compare[b, n:].any()
        # absent operand sources point at sentinel row N
        assert (pb.op_src[b, n:] == N).all()
        # padded step slots never become available
        assert (pb.step_src[b, s:] == N).all()
        assert (pb.step_abs[b, s:] == NEVER).all()
    # sanity: padding changed shapes but not verdicts
    for v in simulate_batch(mappings, iterations=3):
        assert v.ok, v.reason


def test_pack_single_tiny_mapping():
    # a minimal DAG still pads up to the 8/16 floors and verifies
    g = random_dag(3, seed=7)
    m = NodeGreedyMapper(make_arch("plaid2x2"), seed=0).map(g)
    if m is None:
        pytest.skip("tiny DAG did not map")
    pb = pack_bucket([lower_mapping(m, iterations=3)])
    assert pb.opcode.shape[1] >= 8 and pb.step_src.shape[1] >= 16
    assert_differential([m], iterations=3)


# -- differential parity -----------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
def test_differential_all_backends(mappings, backend):
    assert assert_differential(mappings, iterations=3,
                               backend=backend) == len(mappings)


def test_values_match_oracle_and_materialize_lazily(mappings):
    res = simulate_batch(mappings, iterations=3, backend="numpy")
    for m, v in zip(mappings, res):
        assert v.ok
        assert v._values is None          # throughput paths never pay this
        want = simulate(m, iterations=3)
        got = v.values                    # first access builds the dict
        assert v._values is got
        assert set(got) == set(want)
        for key, w in want.items():
            assert close(got[key], w, DEFAULT_TOL), (key, got[key], w)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(6, 14))
def test_fuzz_random_dag_parity(seed, n):
    g = random_dag(n, seed=seed)
    m = NodeGreedyMapper(make_arch("plaid2x2"), seed=0).map(g)
    if m is None:
        return
    assert_differential([m], iterations=3)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_corrupted_mappings_fail_identically(mappings, backend):
    good = mappings[0]

    dropped = copy.deepcopy(good)
    dropped.routes.pop(next(iter(dropped.routes)))

    foreign = copy.deepcopy(good)
    foreign.place[99999] = 0

    shifted = copy.deepcopy(good)
    nid = next(iter(shifted.time))
    shifted.time[nid] += 1

    # parity is the assertion: each corrupted form must get the SAME
    # verdict from both engines (assert_differential raises on divergence)
    batch = [good, dropped, foreign, shifted]
    assert_differential(batch, iterations=3, backend=backend)
    res = simulate_batch(batch, iterations=3, backend=backend)
    assert res.backend == backend
    assert res[0].ok
    assert not res[1].ok and "not present at read time" in res[1].reason
    assert not res[2].ok and "unknown node 99999" in res[2].reason


# One producer (row 0, issue ``src``) feeds operand 0 of one consumer (row
# 1, issue ``dst``) through one route step (``step_abs``) at distance
# ``dist``; ii 2, horizon ``hor``, 3 iterations.  Written by hand: the
# read of iteration ``it`` at cycle dst + it*ii wants the value that
# arrives at step_abs + (it - dist)*ii, which exists iff src < step_abs.
EDGES = {
    # name: (src, step_abs, dst, dist, kind, hor, fail, consumer done)
    "arrival_at_read_cycle": (0, 3, 3, 0, "routed", 12, False, [1, 1, 1]),
    "arrival_one_cycle_late": (0, 4, 3, 0, "routed", 12, True, [1, 1, 1]),
    "producer_issued_at_step_abs": (3, 3, 5, 0, "routed", 12, True,
                                    [1, 1, 1]),
    "first_needy_read_at_horizon": (0, 13, 10, 1, "routed", 12, False,
                                    [1, 0, 0]),
    "first_needy_read_before_horizon": (0, 13, 10, 1, "routed", 13, True,
                                        [1, 1, 0]),
    "dist_at_least_iterations": (0, 10, 3, 3, "routed", 12, False,
                                 [1, 1, 1]),
    "broken_column": (0, 3, 3, 0, "broken", 12, True, [1, 1, 1]),
}


def _edge_bucket(src, step_abs, dst, dist, kind, hor):
    from repro.sim.lower import K_ABSENT, K_BROKEN, K_ROUTED, OP_INDEX
    from repro.sim.step import PackedBucket

    N, K, M, S, I = 2, 3, 1, 1, 3
    op_kind = np.full((1, N, K), K_ABSENT, dtype=np.int8)
    op_kind[0, 1, 0] = K_ROUTED if kind == "routed" else K_BROKEN
    op_src = np.full((1, N, K), N, dtype=np.int32)
    op_src[0, 1, 0] = 0
    op_dist = np.zeros((1, N, K), dtype=np.int32)
    op_dist[0, 1, 0] = dist
    op_steps = np.full((1, N, K, M), S, dtype=np.int32)
    op_steps[0, 1, 0, 0] = 0
    return PackedBucket(
        iterations=I, hmax=hor, ii=np.array([2], dtype=np.int32),
        horizon=np.array([hor], dtype=np.int32),
        opcode=np.array([[OP_INDEX["const"], OP_INDEX["store"]]],
                        dtype=np.int32),
        exec_mask=np.ones((1, N), dtype=bool),
        issue=np.array([[src, dst]], dtype=np.int32),
        compare=np.zeros((1, N), dtype=bool), leaf=np.zeros((1, N)),
        ref=np.zeros((1, N, I)), op_kind=op_kind, op_src=op_src,
        op_dist=op_dist, op_feed=np.zeros((1, N, K)), op_steps=op_steps,
        step_src=np.zeros((1, S), dtype=np.int32),
        step_abs=np.array([[step_abs]], dtype=np.int32))


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_static_availability_edges(edge, backend):
    """``fail`` and ``done`` of hand-packed buckets at the edges of the
    static availability derivation, on the host and the device backend."""
    src, step_abs, dst, dist, kind, hor, fail, consumer = EDGES[edge]
    pb = _edge_bucket(src, step_abs, dst, dist, kind, hor)
    _, done, got_fail = step.run_bucket(pb, backend)
    assert got_fail.tolist() == [fail]
    producer = [src + it * 2 < hor for it in range(3)]
    assert done.tolist() == [[producer, [bool(d) for d in consumer]]]


def test_verify_mappings_raises_on_disproof(mappings):
    bad = copy.deepcopy(mappings[0])
    bad.routes.pop(next(iter(bad.routes)))
    values = verify_mappings(mappings, iterations=3)
    assert len(values) == len(mappings) and all(values)
    with pytest.raises(AssertionError, match=r"mapping\[1\]"):
        verify_mappings([mappings[0], bad], iterations=3)


# -- prepared reruns ---------------------------------------------------------


def test_prepared_batch_warm_rerun_matches_cold(mappings):
    cold = simulate_batch(mappings, iterations=3)
    pb = prepare_batch(mappings, iterations=3)
    warm1 = simulate_batch(mappings, iterations=3, prepared=pb)
    warm2 = simulate_batch(mappings, iterations=3, prepared=pb)
    for c, w1, w2 in zip(cold, warm1, warm2):
        assert c.ok == w1.ok == w2.ok
        assert c.reason == w1.reason == w2.reason
        # warm runs reuse the backend's buffers; values must not alias
        assert w1.values == w2.values == c.values


def test_prepared_batch_mismatch_rejected(mappings):
    pb = prepare_batch(mappings, iterations=3)
    with pytest.raises(ValueError, match="prepared batch"):
        simulate_batch(mappings[:-1], iterations=3, prepared=pb)
    with pytest.raises(ValueError, match="prepared batch"):
        simulate_batch(mappings, iterations=4, prepared=pb)


# -- fault injection / degradation -------------------------------------------


def test_sim_batch_fault_site_fires(mappings):
    with faultinject.inject({"mode": "oserror", "site": "sim.batch"}):
        with pytest.raises(OSError):
            simulate_batch(mappings, iterations=3)
    # the context manager cleans up: the very next call is healthy
    assert all(v.ok for v in simulate_batch(mappings, iterations=3))


def test_compile_result_degrades_to_scalar_on_backend_fault(capsys):
    res = compile("atax", unroll=2)
    assert res.mappings
    # a multi-segment artifact routes through the batched backend
    res.mappings = res.mappings + [copy.deepcopy(res.mappings[0])]
    want = res.simulate(iterations=3)
    assert len(want) == 2
    with faultinject.inject({"mode": "oserror", "site": "sim.batch"}):
        got = res.simulate(iterations=3)
    err = capsys.readouterr()
    assert "degrading to the scalar" in err.out
    # degraded result is still fully verified: same values, scalar engine
    assert len(got) == 2
    for g, w in zip(got, want):
        assert set(g) == set(w)
        assert all(close(g[k], w[k], DEFAULT_TOL) for k in w)


def test_pallas_failure_raises_instead_of_falling_back(mappings, monkeypatch):
    """A failing Pallas kernel fails the verify: ``simulate_batch`` raises
    rather than answering on plain jnp, and ``CompileResult.simulate``
    does not hide it behind the scalar oracle."""
    import repro.kernels.sim_alu as sim_alu_mod

    def broken(*args, **kwargs):
        raise RuntimeError("pallas kernel failed")

    monkeypatch.setattr(sim_alu_mod, "sim_alu", broken)
    step._jit_runner.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="pallas kernel failed"):
            simulate_batch(mappings, iterations=3, backend="pallas")
        res = compile("atax", unroll=2)
        res.mappings = res.mappings + [copy.deepcopy(res.mappings[0])]
        monkeypatch.setenv("REPRO_SIM_BACKEND", "pallas")
        with pytest.raises(RuntimeError, match="pallas kernel failed"):
            res.simulate(iterations=3)
    finally:
        step._jit_runner.cache_clear()
    monkeypatch.undo()
    assert all(v.backend == "pallas" and v.ok for v in simulate_batch(
        mappings, iterations=3, backend="pallas"))


@pytest.mark.parametrize("shape", [(1, 1), (3, 5), (9, 130), (77, 64),
                                   (1030, 70)])
def test_sim_alu_grid_matches_jnp(shape):
    """Shapes off the (8, 128) tile and, at (1030, 70), more than one
    512-row block of the lane-dense view."""
    from repro.kernels.sim_alu import sim_alu

    rng = np.random.default_rng(sum(shape))
    code = jnp.asarray(rng.integers(0, len(OPS), shape), jnp.int32)
    a, b, c, leaf = (jnp.asarray(rng.integers(-50, 50, shape), jnp.float32)
                     for _ in range(4))
    got = sim_alu(code, a, b, c, leaf, interpret=True)
    assert got.shape == shape
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(apply_ops_jnp(code, a, b, c, leaf)))
