"""``simulate_batch`` — verify many mappings per vectorized call.

The scalar oracle costs ~1 ms per mapping per verification; serving-tier
policies like ``verify="always"`` and post-sweep re-verification multiply
that by every artifact served.  This module buckets lowered mappings by
padded shape, packs each bucket into dense tensors, and runs the whole
bucket through one vectorized backend call (``repro.sim.step``), returning
a per-mapping :class:`SimVerdict` with the same accept/reject decision —
and, on accept, the same ``(node, iter) -> value`` map — as the scalar
simulator.

Parity is a hard guarantee, not an aspiration:

* mappings the lowering cannot express (:class:`LoweringUnsupported`)
  run through the scalar oracle itself, inside the same batch call;
* ``backend="auto"`` resolves via ``REPRO_SIM_BACKEND`` (default
  ``numpy``: float64, verdict/value-identical under ``DEFAULT_TOL``; the
  jnp/Pallas backends compare under ``F32_TOL``);
* the CI gate (``plaid-compile verify --parity``) diffs batched verdicts
  against the scalar oracle over the full quick grid on every run.

Packing: one bucket per call — per-cycle fixed overhead dominates batched
cost on the numpy fast path, so splitting by shape only multiplies it.
Mappings pad to the batch max in every dimension (node/step counts round
up to a power of two so the jnp backend retraces rarely); the per-mapping
``horizon`` masks the tail cycles of shorter members.

Lowering is the expensive half of a cold call (it includes one
``dfg.eval`` per mapping — comparable to a scalar simulation), so it is
exposed separately: :func:`prepare_batch` lowers + packs once, and
``simulate_batch(..., prepared=...)`` reruns the vectorized backend on the
cached :class:`PreparedBatch` — the serving-tier shape for "verify the
same artifacts again under a different backend / on every load".

Spans and counters: every call records its phases — ``sim.simulate_batch``
(the root, whole call), ``sim.prepare`` (cold calls), ``sim.scalar_fallback``,
``sim.upload``, ``sim.cycle_loop``, ``sim.pullback`` and ``sim.check`` — and
its :data:`COUNTERS` on the :class:`BatchResult` it returns, each span also
a profiler ``TraceAnnotation`` of the same name (``repro.sim.spans``).
``BatchResult.wall_s`` is the root span's length.

Fault injection: the ``sim.batch`` site fires at entry
(``REPRO_FAULTS``), so chaos tests can crash/hang/OSError the batched
verify path; ``CompileResult.simulate`` degrades to the scalar oracle on
that ``OSError`` rather than serving unverified artifacts, and lets every
other backend fault (a jax runtime error on the device) raise.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.compiler import faultinject
from repro.sim.check import Tolerance, close_array, tolerance_for
from repro.sim.lower import CompiledSim, LoweringUnsupported, lower_mapping
from repro.sim.spans import Span, record, span
from repro.sim.step import NEVER, PackedBucket, run_bucket

ENV_BACKEND = "REPRO_SIM_BACKEND"
BACKENDS = ("numpy", "jnp", "pallas")
#: the root span of every call; its children are the call's phases
ROOT_SPAN = "sim.simulate_batch"
#: counters of every call: bytes sent to and pulled back from the
#: device, and cycle-loop runners built (``step._jit_runner`` misses)
COUNTERS = ("upload_bytes", "pullback_bytes", "runner_builds")


def select_backend(backend: str = "auto") -> str:
    """Resolve ``auto`` via ``REPRO_SIM_BACKEND`` (default ``numpy`` —
    float64 and fastest on CPU-only hosts; set ``jnp``/``pallas`` where an
    accelerator makes the device call win)."""
    if backend == "auto":
        backend = os.environ.get(ENV_BACKEND, "") or "numpy"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown sim backend {backend!r} (choose from "
            f"{', '.join(BACKENDS)} or 'auto')")
    return backend


class SimVerdict:
    """One mapping's batched-verification outcome.

    ``values`` materializes lazily: the ``(node, iter) -> value`` dict is
    built from the backend's dense result on first access, so throughput
    paths that only consume verdicts never pay for dict construction."""

    __slots__ = ("ok", "reason", "backend", "_values", "_thunk")

    def __init__(self, ok: bool, reason: Optional[str] = None,
                 values: Optional[Dict[Tuple[int, int], float]] = None,
                 backend: str = "numpy", values_thunk=None):
        self.ok = ok
        self.reason = reason                  # None iff ok
        self.backend = backend                # what actually ran this one
        self._values = values
        self._thunk = values_thunk

    @property
    def values(self) -> Optional[Dict[Tuple[int, int], float]]:
        if self._values is None and self._thunk is not None:
            self._values = self._thunk()
            self._thunk = None
        return self._values

    def __repr__(self) -> str:
        return (f"SimVerdict(ok={self.ok!r}, reason={self.reason!r}, "
                f"backend={self.backend!r})")


class BatchResult(list):
    """``list[SimVerdict]`` plus run metadata: backend, bucket count,
    scalar fallbacks, and the call's ``spans`` (in start order, the root
    ``sim.simulate_batch`` first) and ``counters`` (:data:`COUNTERS`; see
    ``repro.sim.spans``)."""

    def __init__(self, verdicts=()):
        super().__init__(verdicts)
        self.backend = "numpy"
        self.n_buckets = 0
        self.n_scalar_fallback = 0
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = dict.fromkeys(COUNTERS, 0)

    @property
    def wall_s(self) -> float:
        """Seconds of the root span: the whole call."""
        root = next((sp for sp in self.spans if sp.parent is None), None)
        return (root.end_ns - root.start_ns) / 1e9 if root else 0.0

    @property
    def mappings_per_s(self) -> float:
        return len(self) / self.wall_s if self.wall_s > 0 else 0.0

    def phases_ms(self) -> Dict[str, float]:
        """Milliseconds of each phase of the call (the root's children,
        summed by name, in the order they first ran)."""
        out: Dict[str, float] = {}
        for sp in self.spans:
            if sp.parent == ROOT_SPAN:
                out[sp.name] = out.get(sp.name, 0.0) + sp.ms
        return out

    def describe(self) -> str:
        """One line of phases and counters, for operators."""
        phases = ", ".join(f"{name[len('sim.'):]} {ms:.1f}"
                           for name, ms in self.phases_ms().items())
        counters = ", ".join(f"{k}={v}" for k, v in self.counters.items())
        return f"phases (ms): {phases or 'none'}; {counters}"


def _pow2(x: int) -> int:
    n = 1
    while n < x:
        n <<= 1
    return n


def pack_bucket(forms: List[CompiledSim]) -> PackedBucket:
    """Pad a batch's ``CompiledSim`` forms to common shape and stack.

    Node and step counts round up to a power of two (floors 8 / 16) so
    the jnp backend's shape-keyed trace cache stays warm across batches.

    Sentinels (see ``repro.sim.step``): absent operand sources and padded
    step producers point at node row ``N`` (reads 0.0, never done);
    unmatched/padded step slots point at step row ``S`` (never available);
    padded steps get ``step_abs = NEVER`` so no cycle fires them."""
    B = len(forms)
    I = forms[0].iterations
    N = _pow2(max(max(cs.n_nodes for cs in forms), 8))
    S = _pow2(max(max(cs.n_steps for cs in forms), 16))
    K = max(cs.n_operands for cs in forms)
    M = max(cs.n_matches for cs in forms)
    hmax = max(cs.horizon for cs in forms)

    ii = np.ones(B, dtype=np.int32)
    horizon = np.zeros(B, dtype=np.int32)
    opcode = np.zeros((B, N), dtype=np.int32)
    exec_mask = np.zeros((B, N), dtype=bool)
    issue = np.zeros((B, N), dtype=np.int32)
    compare = np.zeros((B, N), dtype=bool)
    leaf = np.zeros((B, N), dtype=np.float64)
    ref = np.zeros((B, N, I), dtype=np.float64)
    op_kind = np.zeros((B, N, K), dtype=np.int8)
    op_src = np.full((B, N, K), N, dtype=np.int32)
    op_dist = np.zeros((B, N, K), dtype=np.int32)
    op_feed = np.zeros((B, N, K), dtype=np.float64)
    op_steps = np.full((B, N, K, M), S, dtype=np.int32)
    step_src = np.full((B, S), N, dtype=np.int32)
    step_abs = np.full((B, S), NEVER, dtype=np.int32)

    for b, cs in enumerate(forms):
        n, s = cs.n_nodes, cs.n_steps
        k, m = cs.n_operands, cs.n_matches
        ii[b] = cs.ii
        horizon[b] = cs.horizon
        opcode[b, :n] = cs.opcode
        exec_mask[b, :n] = cs.exec_mask
        issue[b, :n] = cs.issue
        compare[b, :n] = cs.compare
        leaf[b, :n] = cs.leaf_base
        ref[b, :n, :] = cs.ref
        op_kind[b, :n, :k] = cs.op_kind
        op_src[b, :n, :k] = np.where(cs.op_src >= 0, cs.op_src, N)
        op_dist[b, :n, :k] = cs.op_dist
        op_feed[b, :n, :k] = cs.op_feed
        op_steps[b, :n, :k, :m] = np.where(cs.op_steps >= 0, cs.op_steps, S)
        if s:
            step_src[b, :s] = cs.step_src
            step_abs[b, :s] = cs.step_abs
    return PackedBucket(
        iterations=I, hmax=hmax, ii=ii, horizon=horizon, opcode=opcode,
        exec_mask=exec_mask, issue=issue, compare=compare, leaf=leaf,
        ref=ref, op_kind=op_kind, op_src=op_src, op_dist=op_dist,
        op_feed=op_feed, op_steps=op_steps, step_src=step_src,
        step_abs=step_abs,
    )


@dataclass
class PreparedBatch:
    """Lowered + packed form of one ``mappings`` list: the reusable half
    of a batched verification (build once with :func:`prepare_batch`,
    rerun cheaply via ``simulate_batch(..., prepared=...)``)."""

    iterations: int
    n_mappings: int
    scalar_idx: List[int]            # inputs needing the scalar oracle
    batch_idx: List[int]             # inputs lowered into `forms`/`packed`
    forms: List[CompiledSim]
    packed: Optional[PackedBucket]   # None when every input fell back


def prepare_batch(mappings, iterations: int = 4) -> PreparedBatch:
    """Lower every mapping (``LoweringUnsupported`` ones are earmarked for
    the scalar oracle) and pack the rest into one padded bucket."""
    scalar_idx: List[int] = []
    batch_idx: List[int] = []
    forms: List[CompiledSim] = []
    for i, m in enumerate(mappings):
        try:
            cs = lower_mapping(m, iterations=iterations)
        except LoweringUnsupported:
            scalar_idx.append(i)
            continue
        batch_idx.append(i)
        forms.append(cs)
    return PreparedBatch(
        iterations=iterations, n_mappings=len(mappings),
        scalar_idx=scalar_idx, batch_idx=batch_idx, forms=forms,
        packed=pack_bucket(forms) if forms else None,
    )


def _values_thunk(val_b: np.ndarray, done_b: np.ndarray, node_ids):
    def build() -> Dict[Tuple[int, int], float]:
        return {
            (node_ids[r], int(it)): float(val_b[r, it])
            for r, it in np.argwhere(done_b)
        }
    return build


def _bucket_verdicts(forms: List[CompiledSim], pb: PackedBucket,
                     backend: str, tol: Tolerance) -> List[SimVerdict]:
    val, done, read_fail = run_bucket(pb, backend)
    with span("sim.check"):
        # whole-batch checks (padding rows carry compare=False, so they
        # never contribute); the per-form loop below only details the
        # failures
        cmpI = pb.compare[:, :, None]
        missing = cmpI & ~done
        bad = cmpI & done & ~close_array(val, pb.ref, tol)
        missing_any = missing.any(axis=(1, 2))
        bad_any = bad.any(axis=(1, 2))
        out: List[SimVerdict] = []
        for b, cs in enumerate(forms):
            n = cs.n_nodes
            if cs.fail_static is not None:
                out.append(SimVerdict(False, cs.fail_static,
                                      backend=backend))
            elif read_fail[b]:
                out.append(SimVerdict(
                    False, "operand value not present at read time "
                           "(missing / unrouted / mistimed route)",
                    backend=backend))
            elif missing_any[b]:
                r, it = np.argwhere(missing[b])[0]
                out.append(SimVerdict(
                    False,
                    f"node {cs.node_ids[r]} iter {it}: no value produced",
                    backend=backend))
            elif bad_any[b]:
                r, it = np.argwhere(bad[b])[0]
                out.append(SimVerdict(
                    False,
                    f"node {cs.node_ids[r]} iter {it}: got {val[b, r, it]}, "
                    f"want {cs.ref[r, it]}", backend=backend))
            else:
                out.append(SimVerdict(
                    True, backend=backend,
                    values_thunk=_values_thunk(
                        val[b, :n, :], done[b, :n, :], cs.node_ids)))
        return out


def _scalar_fallback(mapping, iterations: int) -> SimVerdict:
    from repro.sim.check import scalar_verdict

    ok, values, reason = scalar_verdict(mapping, iterations=iterations)
    return SimVerdict(ok, reason=reason, values=values, backend="scalar")


def simulate_batch(mappings, iterations: int = 4, backend: str = "auto",
                   tol: Optional[Tolerance] = None,
                   prepared: Optional[PreparedBatch] = None) -> BatchResult:
    """Batched cycle-accurate verification (see module docstring).

    Returns a :class:`BatchResult` — one :class:`SimVerdict` per input
    mapping, in input order, plus throughput metadata and the call's
    spans and counters.  Never raises on a *failing mapping* (that is a
    ``False`` verdict); raises on backend / environment faults
    (``OSError`` from fault injection, jax runtime errors, a failing
    Pallas kernel).

    Pass ``prepared`` (from :func:`prepare_batch` over the *same*
    mappings/iterations) to skip the lowering + packing half and rerun
    only the vectorized backend."""
    with record() as rec, rec.span(ROOT_SPAN):
        backend = select_backend(backend)
        faultinject.check("sim.batch", f"batch={len(mappings)}")
        tol = tol if tol is not None else tolerance_for(backend)

        if prepared is None:
            with span("sim.prepare"):
                prepared = prepare_batch(mappings, iterations=iterations)
        elif (prepared.n_mappings != len(mappings)
              or prepared.iterations != iterations):
            raise ValueError(
                f"prepared batch is for {prepared.n_mappings} mappings x "
                f"{prepared.iterations} iterations, got {len(mappings)} x "
                f"{iterations}")

        out = BatchResult([None] * len(mappings))
        out.backend = backend
        if prepared.scalar_idx:
            with span("sim.scalar_fallback"):
                for i in prepared.scalar_idx:
                    out[i] = _scalar_fallback(mappings[i], iterations)
        out.n_scalar_fallback = len(prepared.scalar_idx)
        if prepared.packed is not None:
            verdicts = _bucket_verdicts(
                prepared.forms, prepared.packed, backend, tol)
            for i, v in zip(prepared.batch_idx, verdicts):
                out[i] = v
            out.n_buckets = 1
    out.spans = rec.spans
    out.counters.update(rec.counters)
    return out


def verify_mappings(mappings, iterations: int = 3, backend: str = "auto",
                    prepared: Optional[PreparedBatch] = None,
                    ) -> List[Dict[Tuple[int, int], float]]:
    """Drop-in batched replacement for the per-mapping scalar verify loop
    in ``CompileResult.simulate``: returns the per-mapping value dicts,
    raising ``AssertionError`` on the first failing mapping (the same
    disproof contract — and the same ``VERIFY_FAILURES`` membership — as
    the scalar oracle).  ``prepared`` (e.g. rebuilt from an artifact's
    stored ``compiled_sim`` forms) skips the lowering half."""
    verdicts = simulate_batch(mappings, iterations=iterations,
                              backend=backend, prepared=prepared)
    for i, v in enumerate(verdicts):
        assert v.ok, (
            f"mapping[{i}] failed batched verification "
            f"({v.backend} backend): {v.reason}")
    return [v.values for v in verdicts]
