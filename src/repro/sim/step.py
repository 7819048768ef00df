"""Vectorized whole-grid step function over packed ``CompiledSim`` buckets.

One call executes every cycle of every mapping in a bucket; the scalar
oracle's dicts become dense tensors:

* ``val[b, node, iter]`` — produced values (``+2`` sentinel rows: a read
  sentinel that stays 0.0 for absent operands, and a write dump that soaks
  up masked-out scatters on backends without boolean scatter).
* ``done[b, node, iter]`` — which (node, iteration) values exist.
* ``fail[b]`` — per-mapping read failure (missing operand / unrouted-edge
  read), exactly where the scalar oracle asserts.

Batched execution never *gates* an FU on operand presence: a missing read
sets ``fail`` and the node computes with a 0.0 operand, exactly mirroring
where the scalar oracle would assert.  Node ``n`` therefore produces
iteration ``k`` iff ``issue + k*ii < horizon`` — ``done`` is a pure timing
function — and the oracle's route-step availability (its commit phase:
a step becomes readable one cycle after its write, if the producer's value
exists) unrolls to a *static* predicate::

    avail(step, k) ⇔ exec(src) ∧ issue_src < step_abs          (producer
                     committed before the write cycle step_abs + k·ii − 1)

    present(read)  ⇔ ∃ matched step: step_abs ≤ issue_dst + dist·ii
                     ∧ avail(step)          (iteration-independent: both
                     read and arrival cycles shift by the same k·ii)

so every read-failure check hoists out of the cycle loop.  Both backends
compute these predicates with the same two functions over the array
module, :func:`step_arrival` and :func:`read_checks` (``numpy`` on the
host, ``jax.numpy`` inside the jitted program, on every call).  The cycle
loop that remains only propagates *values*: the data recurrence still
needs ordered evaluation, and each cycle's operand reads see the values as
of the start of the cycle.

Backends:

* ``numpy``  — float64 reference; the loop runs as a precomputed event
  schedule, fastest on CPU-only hosts and verdict/value-identical to the
  scalar oracle under ``DEFAULT_TOL``.
* ``jnp``    — float32, a value-only ``lax.fori_loop`` under ``jit``, one
  traced program per bucket shape, for accelerator execution.
* ``pallas`` — the jnp backend with the ALU apply stage running as a
  Pallas kernel (``repro.kernels.sim_alu``); a kernel failure raises.

Names on the device: the jitted program runs under ``jax.named_scope``
``sim_cycle_loop``.  Before the loop, the static predicates run under
``commit`` (:func:`step_arrival`, the static form of the oracle's commit
phase) and ``execute/presence`` (:func:`read_checks`); each simulated
cycle runs under ``execute``, with ``operand_read``, ``alu`` and
``value_write`` inside it, so every op of the compiled program names its
phase in its ``op_name`` metadata.  The Pallas ALU kernel is ``sim_alu``.

Final comparison against the ``ref`` oracle lives in ``repro.sim.batch``
(it is tolerance-policy dependent; see ``repro.sim.check``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Tuple

import numpy as np

from repro.sim.lower import K_BROKEN, K_FEED, K_ROUTED, OPS
from repro.sim.spans import count, span

#: step_abs padding: far enough out that no in-horizon cycle matches
NEVER = 1 << 30

# -- numpy ALU ---------------------------------------------------------------


def _np_alu(code: int, a, b, c, leaf):
    op = OPS[code]
    if op in ("const", "input", "load"):
        return leaf
    if op in ("store", "output"):
        return a
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "mac":
        return a * b + c
    if op == "shl":
        return a * 2.0
    if op == "shr":
        return a / 2.0
    if op == "and":
        return (a.astype(np.int64) & b.astype(np.int64)).astype(np.float64)
    if op == "or":
        return (a.astype(np.int64) | b.astype(np.int64)).astype(np.float64)
    if op == "xor":
        return (a.astype(np.int64) ^ b.astype(np.int64)).astype(np.float64)
    if op == "not":
        return (~a.astype(np.int64) & 0xFFFF).astype(np.float64)
    if op == "min":
        return np.minimum(a, b)
    if op == "max":
        return np.maximum(a, b)
    if op == "abs":
        return np.abs(a)
    if op == "cmp":
        return (a > b).astype(np.float64)
    if op == "select":
        return np.where(a != 0.0, b, c)
    raise ValueError(op)


def apply_ops_numpy(opcode, a, b, c, leaf):
    """Vectorized ``repro.core.dfg._apply`` over an opcode array."""
    out = np.zeros_like(a)
    for code in np.unique(opcode):
        m = opcode == code
        out[m] = _np_alu(int(code), a[m], b[m], c[m], leaf[m])
    return out


# -- packed bucket -----------------------------------------------------------


@dataclass
class PackedBucket:
    """A batch of same-shape-padded ``CompiledSim`` forms (see
    ``repro.sim.batch.pack_bucket``).  Sentinel conventions: ``op_src`` /
    ``step_src`` use row ``N`` (never written, reads 0.0 / not-done),
    ``op_steps`` uses step row ``S`` (never available), padded steps carry
    ``step_abs = NEVER``."""

    iterations: int
    hmax: int
    ii: np.ndarray         # (B,)   int32
    horizon: np.ndarray    # (B,)   int32
    opcode: np.ndarray     # (B,N)  int32
    exec_mask: np.ndarray  # (B,N)  bool
    issue: np.ndarray      # (B,N)  int32
    compare: np.ndarray    # (B,N)  bool
    leaf: np.ndarray       # (B,N)  f64
    ref: np.ndarray        # (B,N,I) f64
    op_kind: np.ndarray    # (B,N,K) int8
    op_src: np.ndarray     # (B,N,K) int32 (sentinel N)
    op_dist: np.ndarray    # (B,N,K) int32
    op_feed: np.ndarray    # (B,N,K) f64
    op_steps: np.ndarray   # (B,N,K,M) int32 (sentinel S)
    step_src: np.ndarray   # (B,S)  int32 (sentinel N)
    step_abs: np.ndarray   # (B,S)  int32 (pad NEVER)
    #: per-backend derived-data memo (static predicates, event schedule);
    #: lives with the bucket so warm reruns skip every precomputation
    cache: Dict[str, object] = field(
        default_factory=dict, repr=False, compare=False)

    @property
    def shape(self) -> Tuple[int, int, int, int, int]:
        b, n, k, m = self.op_steps.shape
        return b, n, k, m, self.step_src.shape[1]


# -- static availability (both backends) ------------------------------------


def step_arrival(xp, exec_mask, issue, step_src, step_abs):
    """(B,S+1) cycle from which each route step holds a readable value
    (iteration k's from that cycle + k·ii), or ``NEVER``: a step holds its
    values iff its producer committed before the write cycle, ``exec(src)
    ∧ issue_src < step_abs`` (the static form of the oracle's commit
    phase).  Sentinel row N is never
    exec, padded steps carry ``NEVER``, and the appended column S is the
    never-available sentinel step.  ``xp`` is ``numpy`` or ``jax.numpy``."""
    B = exec_mask.shape[0]
    never = xp.full((B, 1), NEVER, dtype=xp.int32)
    src_issue = xp.take_along_axis(
        xp.concatenate([xp.where(exec_mask, issue, never), never], axis=1),
        step_src, axis=1)                                        # (B,S)
    return xp.concatenate(
        [xp.where(src_issue < step_abs, step_abs, never), never], axis=1)


def read_checks(xp, iterations, ii, horizon, exec_mask, issue, op_kind,
                op_dist, op_steps, arrival):
    """``done`` (B,N,I) — a pure timing function — and ``fail`` (B,) —
    every read-failure check of the cycle loop, hoisted out of it
    (derivation in the module docstring); ``arrival`` is
    :func:`step_arrival`'s table."""
    B, N, K, M = op_steps.shape
    ii3 = ii[:, None, None]
    hor3 = horizon[:, None, None]
    it_r = xp.arange(iterations, dtype=xp.int32)
    done = exec_mask[:, :, None] & (issue[:, :, None] + it_r * ii3 < hor3)
    arr = xp.take_along_axis(arrival, op_steps.reshape(B, N * K * M),
                             axis=1).reshape(B, N, K, M)
    # presence is iteration-independent: arrival step_abs + (it-dist)*ii
    # <= read cycle issue_dst + it*ii  ⇔  step_abs <= issue_dst + dist*ii
    deadline = issue[:, :, None] + op_dist * ii3                 # (B,N,K)
    ok_col = (arr <= deadline[:, :, :, None]).any(axis=3)
    # the first needy read is iteration `dist`; it happens iff that
    # execution lands inside the horizon (deadline is exactly its cycle)
    reads = exec_mask[:, :, None] & (op_dist < iterations) & (deadline < hor3)
    bad = (op_kind == K_BROKEN) | ((op_kind == K_ROUTED) & ~ok_col)
    return done, (reads & bad).any(axis=(1, 2))


# -- numpy backend -----------------------------------------------------------


def _np_static(pb: PackedBucket):
    """The static predicates of one bucket on the host (memoized on
    ``pb.cache`` by :func:`run_bucket_numpy`)."""
    arrival = step_arrival(np, pb.exec_mask, pb.issue, pb.step_src,
                           pb.step_abs)
    return read_checks(np, pb.iterations, pb.ii, pb.horizon, pb.exec_mask,
                       pb.issue, pb.op_kind, pb.op_dist, pb.op_steps,
                       arrival)


def _np_schedule(pb: PackedBucket):
    """One-time event schedule for the value recurrence: every (mapping,
    node, iteration) execution becomes an event with prebuilt gather /
    scatter indices into one flat buffer, sorted by (cycle, opcode) and
    grouped into per-cycle opcode segments.

    Buffer layout: ``[0, V)`` node values (b, node-row incl. the 0.0
    sentinel row N, iter; reset each run), ``[V, V+P)`` the static feed
    pool (const/input operand values per (b, n, k, it)), ``[V+P]`` a 0.0
    slot for absent / pre-loop operands."""
    B, N, K, M, S = pb.shape
    I = pb.iterations
    ii3 = pb.ii[:, None, None]
    hor3 = pb.horizon[:, None, None]
    routed = pb.op_kind == K_ROUTED
    feed = pb.op_kind == K_FEED
    it_r = np.arange(I, dtype=np.int32)
    V = B * (N + 1) * I
    P = B * N * K * I

    t_ev = pb.issue[:, :, None] + it_r * ii3                     # (B,N,I)
    valid = pb.exec_mask[:, :, None] & (t_ev < hor3)
    node_flat = ((np.arange(B)[:, None] * (N + 1)
                  + np.arange(N)[None, :])[:, :, None] * I + it_r)

    src_base = (np.arange(B)[:, None, None] * (N + 1)
                + pb.op_src) * I                                 # (B,N,K)
    want = it_r[None, None, None, :] - pb.op_dist[:, :, :, None]  # (B,N,K,I)
    rd = src_base[:, :, :, None] + want
    feed_idx = V + np.arange(P, dtype=np.int64).reshape(B, N, K, I)
    idx_full = np.where(routed[..., None] & (want >= 0), rd,
                        np.where(feed[..., None], feed_idx, V + P))
    feedpool = (pb.op_feed[:, :, :, None] + it_r).ravel()

    mask = valid.ravel()
    t_flat = t_ev.ravel()[mask]
    code_flat = np.broadcast_to(
        pb.opcode[:, :, None], (B, N, I)).ravel()[mask]
    gidx = idx_full.transpose(0, 1, 3, 2).reshape(B * N * I, K)[:, :3][mask]
    widx = node_flat.ravel()[mask]
    leafv = (pb.leaf[:, :, None] + it_r).ravel()[mask]

    order = np.lexsort((code_flat, t_flat))
    t_s = t_flat[order]
    code_s = code_flat[order]
    gidx = np.ascontiguousarray(gidx[order])
    widx = np.ascontiguousarray(widx[order])
    leafv = np.ascontiguousarray(leafv[order])

    # cycles: [(clo, chi, [(opcode, lo, hi), ...]), ...] in cycle order
    cycles = []
    E = len(t_s)
    if E:
        seg_key = t_s.astype(np.int64) * len(OPS) + code_s
        starts = np.concatenate(
            ([0], np.flatnonzero(np.diff(seg_key) != 0) + 1, [E]))
        cur_t = None
        for a0, a1 in zip(starts[:-1], starts[1:]):
            t = int(t_s[a0])
            if t != cur_t:
                cycles.append((int(a0), [a1], []))
                cur_t = t
            cycles[-1][1][0] = int(a1)
            cycles[-1][2].append((int(code_s[a0]), int(a0), int(a1)))
        cycles = [(lo, hi[0], segs) for lo, hi, segs in cycles]

    buf = np.zeros(V + P + 1, dtype=np.float64)
    buf[V:V + P] = feedpool
    return {"V": V, "buf": buf, "gidx": gidx, "widx": widx,
            "leaf": leafv, "cycles": cycles}


def run_bucket_numpy(pb: PackedBucket):
    """Returns ``(val (B,N,I) f64, done (B,N,I) bool, fail (B,) bool)``;
    ``fail`` marks read failures only (final ref comparison is the
    caller's, under its tolerance policy).

    Static-availability fast path: ``done``/``fail`` and the event
    schedule are computed once per bucket (memoized on ``pb.cache``); a
    run is one operand gather plus a few opcode-segment ALU calls per
    cycle — reads still see start-of-cycle state because each cycle's
    gather happens before any of its writes."""
    B, N, K, M, S = pb.shape
    I = pb.iterations
    static = pb.cache.get("np_static")
    if static is None:
        static = pb.cache["np_static"] = _np_static(pb)
    done, fail = static
    sched = pb.cache.get("np_sched")
    if sched is None:
        sched = pb.cache["np_sched"] = _np_schedule(pb)

    buf = sched["buf"]
    V = sched["V"]
    buf[:V] = 0.0
    gidx, widx, leafv = sched["gidx"], sched["widx"], sched["leaf"]
    for clo, chi, segs in sched["cycles"]:
        vals = buf[gidx[clo:chi]]                                # (E,3)
        a, b, c = vals[:, 0], vals[:, 1], vals[:, 2]
        for code, lo, hi in segs:
            buf[widx[lo:hi]] = _np_alu(
                code, a[lo - clo:hi - clo], b[lo - clo:hi - clo],
                c[lo - clo:hi - clo], leafv[lo:hi])
    val = buf[:V].reshape(B, N + 1, I)[:, :N, :].copy()
    return val, done, fail


# -- jnp backend (optional Pallas ALU stage) ---------------------------------


def _jnp_alu(jnp, code: int, a, b, c, leaf):
    op = OPS[code]
    if op in ("const", "input", "load"):
        return leaf
    if op in ("store", "output"):
        return a
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "mac":
        return a * b + c
    if op == "shl":
        return a * 2.0
    if op == "shr":
        return a / 2.0
    ai = a.astype(jnp.int32)
    bi = b.astype(jnp.int32)
    if op == "and":
        return (ai & bi).astype(a.dtype)
    if op == "or":
        return (ai | bi).astype(a.dtype)
    if op == "xor":
        return (ai ^ bi).astype(a.dtype)
    if op == "not":
        return (~ai & 0xFFFF).astype(a.dtype)
    if op == "min":
        return jnp.minimum(a, b)
    if op == "max":
        return jnp.maximum(a, b)
    if op == "abs":
        return jnp.abs(a)
    if op == "cmp":
        return (a > b).astype(a.dtype)
    if op == "select":
        return jnp.where(a != 0.0, b, c)
    raise ValueError(op)


def apply_ops_jnp(opcode, a, b, c, leaf):
    import jax.numpy as jnp

    out = jnp.zeros_like(a)
    for code in range(len(OPS)):
        out = jnp.where(opcode == code,
                        _jnp_alu(jnp, code, a, b, c, leaf), out)
    return out


@functools.lru_cache(maxsize=None)
def _jit_runner(hmax: int, iterations: int, shape: Tuple[int, ...],
                use_pallas: bool):
    """Build (and cache) the jitted cycle loop for one bucket shape."""
    import jax
    import jax.numpy as jnp

    B, N = shape[:2]
    I = iterations

    if use_pallas:
        from repro.kernels.sim_alu import sim_alu

        def alu(opcode, a, b, c, leaf):
            return sim_alu(opcode, a, b, c, leaf)
    else:
        alu = apply_ops_jnp

    def run(ii, horizon, opcode, exec_mask, issue, leaf,
            op_kind, op_src, op_dist, op_feed, op_steps,
            step_src, step_abs):
        iiB = ii[:, None]
        horB = horizon[:, None]
        node_base = (jnp.arange(B)[:, None] * (N + 2)
                     + jnp.arange(N)[None, :]) * I
        dump = jnp.int32((B * (N + 2) - 1) * I)  # last dump row, iter 0
        src_base = (jnp.arange(B)[:, None, None] * (N + 2) + op_src) * I
        routed = op_kind == K_ROUTED
        feed = op_kind == K_FEED

        def body(t, val):
            with jax.named_scope("execute"):
                act = exec_mask & (issue <= t) & (t < horB)
                d = t - issue
                q = d // iiB
                act = act & (d - q * iiB == 0) & (q < I)
                itq = jnp.where(act, q, 0)
                want = itq[:, :, None] - op_dist
                in_range = (want >= 0) & (want < I)
                wc = jnp.clip(want, 0, I - 1)
                with jax.named_scope("operand_read"):
                    vr = jnp.take(val, src_base + wc)
                    opv = jnp.where(routed & in_range, vr, 0.0)
                    opv = jnp.where(
                        feed, op_feed + itq[:, :, None].astype(leaf.dtype),
                        opv)
                with jax.named_scope("alu"):
                    newv = alu(opcode, opv[:, :, 0], opv[:, :, 1],
                               opv[:, :, 2], leaf + itq.astype(leaf.dtype))
                with jax.named_scope("value_write"):
                    idx = jnp.where(act, node_base + itq, dump)
                    return val.at[idx.ravel()].set(newv.ravel())

        with jax.named_scope("sim_cycle_loop"):
            with jax.named_scope("commit"):
                arrival = step_arrival(jnp, exec_mask, issue, step_src,
                                       step_abs)
            with jax.named_scope("execute"), jax.named_scope("presence"):
                done, fail = read_checks(
                    jnp, I, ii, horizon, exec_mask, issue, op_kind,
                    op_dist, op_steps, arrival)
            val = jax.lax.fori_loop(
                0, hmax, body, jnp.zeros(B * (N + 2) * I, dtype=jnp.float32))
        return val.reshape(B, N + 2, I)[:, :N, :], done, fail

    return jax.jit(run)


def device_args(pb: PackedBucket):
    """The bucket's arrays as the jitted cycle loop takes them."""
    import jax.numpy as jnp

    return (
        jnp.asarray(pb.ii), jnp.asarray(pb.horizon),
        jnp.asarray(pb.opcode), jnp.asarray(pb.exec_mask),
        jnp.asarray(pb.issue), jnp.asarray(pb.leaf, dtype=jnp.float32),
        jnp.asarray(pb.op_kind), jnp.asarray(pb.op_src),
        jnp.asarray(pb.op_dist),
        jnp.asarray(pb.op_feed, dtype=jnp.float32),
        jnp.asarray(pb.op_steps), jnp.asarray(pb.step_src),
        jnp.asarray(pb.step_abs),
    )


def run_bucket_jnp(pb: PackedBucket, use_pallas: bool = False):
    """jnp backend: same contract as :func:`run_bucket_numpy` (values are
    float32 upcast to float64 — compare under ``F32_TOL``).  With
    ``use_pallas`` the ALU apply stage runs as a Pallas kernel; a failure
    there raises like any other backend fault.

    Records spans ``sim.upload`` (until the arguments are on the device),
    ``sim.cycle_loop`` (until the loop's outputs are ready) and
    ``sim.pullback``, and counters ``upload_bytes``, ``pullback_bytes``
    and ``runner_builds`` (see ``repro.sim.spans``)."""
    import jax

    misses = _jit_runner.cache_info().misses
    runner = _jit_runner(pb.hmax, pb.iterations, pb.shape, use_pallas)
    count("runner_builds", _jit_runner.cache_info().misses - misses)
    with span("sim.upload"):
        args = jax.block_until_ready(device_args(pb))
    count("upload_bytes", sum(a.nbytes for a in args))
    with span("sim.cycle_loop"):
        out = jax.block_until_ready(runner(*args))
    count("pullback_bytes", sum(a.nbytes for a in out))
    with span("sim.pullback"):
        val, done, fail = out
        return (np.asarray(val, dtype=np.float64), np.asarray(done),
                np.asarray(fail))


def run_bucket(pb: PackedBucket, backend: str):
    if backend == "numpy":
        with span("sim.cycle_loop"):
            return run_bucket_numpy(pb)
    if backend == "jnp":
        return run_bucket_jnp(pb, use_pallas=False)
    if backend == "pallas":
        return run_bucket_jnp(pb, use_pallas=True)
    raise ValueError(f"unknown sim backend {backend!r}")
