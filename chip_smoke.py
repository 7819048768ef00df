#!/usr/bin/env python3
"""Bring-up check of every device path on one TPU chip, in one process.

    python chip_smoke.py        # from the repository root, on a TPU host

Phases, in order (each prints its figures; none may fall back to the CPU,
to Pallas interpret mode, to plain jnp or to the scalar oracle):

1. device   — JAX must see a TPU; anything else exits non-zero at once.
2. verify   — Track A: compile the quick TABLE2 workloads in-process with
   ``repro.compiler.compile`` on ``plaid2x2``, ``plaid3x3`` and ``st4x4``,
   tile the lowered forms to a sweep-sized bucket, run
   ``simulate_batch`` on the ``jnp`` and ``pallas`` backends (cold, then
   warm on the ``PreparedBatch``) and hold every verdict and value to the
   ``numpy`` backend under ``F32_TOL``.
3. pcu      — Track A: the motif PCU kernel (``FANIN``/``FANOUT``/
   ``UNICAST``) compiled for the chip on 2**20 lanes, against
   ``repro.kernels.ref``.
4. serve    — Track B: ``llama3_2_3b`` at its published widths with random
   bf16 weights, one batch of requests through ``repro.launch.serve``
   (``serve.loop.generate``); decode is checked against a forward pass
   over the extended sequence.

The compile cache follows ``repro.launch.compile_cache``.  Compile seconds
and persistent-cache hits are printed per phase.  The figures are bring-up
information, not benchmark metrics.  The last line of standard output is
one JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from contextlib import contextmanager
from pathlib import Path

REPO = Path(__file__).resolve().parent

#: compile jobs (``job_grid`` names) whose quick-workload mappings fill
#: the verify bucket: hierarchical Plaid at two sizes and a
#: spatio-temporal fabric
VERIFY_JOBS = ("plaid", "plaid3x3", "st")
#: mappings per verify bucket: the size of an architecture sweep
BUCKET = 4096
SIM_ITERATIONS = 3
#: lanes per motif PCU call
PCU_LANES = 1 << 20
SERVE_ARCH = "llama3_2_3b"
SERVE_BATCH = 4
PROMPT_LEN = 256
NEW_TOKENS = 16
#: decode vs. forward logits, per request and step: ``||d - f|| / ||f||``.
#: Both sides run bf16 weights and activations through different attention
#: code (cached decode vs. the banded full-sequence path).  bf16 rounding
#: grows with depth: at 28 layers a reduced-width copy of this config
#: differs by up to 0.07 on the CPU (0.00 at 2 layers or in float32), the
#: full-width config by 0.105 on a TPU v5e, while a KV cache shifted by one
#: slot differs by 1.5.
SERVE_REL_TOL = 0.25


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Per-phase XLA compile seconds and persistent-cache hits/misses,
    read from JAX's own monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> None:
        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @contextmanager
    def phase(self, name: str):
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        try:
            yield
        finally:
            log(f"[{name}] wall {time.perf_counter() - t0:.2f} s, compile "
                f"{self.compile_s - c0:.2f} s, persistent cache "
                f"{self.hits - h0} hit(s) / {self.misses - m0} miss(es)")


def check_device():
    """The TPU JAX sees, or exit non-zero before any work."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev


def assert_on_chip(compiled_text: str, what: str) -> None:
    """A Pallas program built for the chip carries its Mosaic kernel as a
    ``tpu_custom_call``; interpret mode lowers to plain HLO instead."""
    import jax

    assert jax.default_backend() == "tpu", jax.default_backend()
    assert "tpu_custom_call" in compiled_text, (
        f"{what}: no tpu_custom_call in the compiled program")


# -- Track A: batched verification ------------------------------------------


def compile_mappings():
    """Quick-workload mappings of every ``VERIFY_JOBS`` fabric, compiled in
    this process (no supervised runner, no farm)."""
    from repro.compiler import compile
    from repro.compiler.pipeline import job_grid
    from repro.core.workloads import quick_workloads

    grid = job_grid()
    t0 = time.perf_counter()
    mappings = []
    for job in VERIFY_JOBS:
        arch, mapper = grid[job]
        for w in quick_workloads():
            res = compile(w, arch=arch, mapper=mapper, seed=0)
            mappings.extend(res.rebuild_mappings())
    log(f"[verify] compiled {len(mappings)} mappings "
        f"({len(quick_workloads())} workloads x {', '.join(VERIFY_JOBS)}) "
        f"on the host in {time.perf_counter() - t0:.2f} s")
    return mappings


def _same_verdicts(got, want, backend: str) -> None:
    import numpy as np

    from repro.sim.check import F32_TOL, close_array

    assert len(got) == len(want)
    assert got.n_scalar_fallback == 0, got.n_scalar_fallback
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.backend == backend, (i, g.backend)
        assert g.ok == w.ok, (i, g, w)
        if not w.ok:
            continue
        gv, wv = g.values, w.values
        assert gv.keys() == wv.keys(), i
        keys = list(wv)
        ok = close_array([gv[k] for k in keys], [wv[k] for k in keys],
                         F32_TOL)
        assert ok.all(), (i, keys[int(np.argmin(ok))])


def phase_verify() -> None:
    from repro.sim import step
    from repro.sim.batch import PreparedBatch, pack_bucket, simulate_batch
    from repro.sim.lower import lower_mapping

    unique = compile_mappings()
    forms = [lower_mapping(m, iterations=SIM_ITERATIONS) for m in unique]
    reps = -(-BUCKET // len(unique))
    mappings = (unique * reps)[:BUCKET]
    tiled = (forms * reps)[:BUCKET]
    t0 = time.perf_counter()
    prepared = PreparedBatch(
        iterations=SIM_ITERATIONS, n_mappings=BUCKET, scalar_idx=[],
        batch_idx=list(range(BUCKET)), forms=tiled,
        packed=pack_bucket(tiled))
    pb = prepared.packed
    log(f"[verify] bucket of {BUCKET} mappings packed in "
        f"{time.perf_counter() - t0:.2f} s, shape (B, N, K, M, S) = "
        f"{pb.shape}, {pb.hmax} cycles, {SIM_ITERATIONS} iterations")

    def run(backend):
        return simulate_batch(mappings, iterations=SIM_ITERATIONS,
                              backend=backend, prepared=prepared)

    want = run("numpy")
    assert want.n_scalar_fallback == 0
    assert all(v.ok for v in want), "numpy backend rejected a mapping"
    log(f"[verify] numpy reference: {len(want)} verdicts ok, "
        f"{want.mappings_per_s:.0f} mappings/s (host)")
    for backend in ("jnp", "pallas"):
        for label in ("cold", "warm"):
            got = run(backend)
            _same_verdicts(got, want, backend)
            log(f"[verify] {backend} {label}: {len(got)} mappings in "
                f"{got.wall_s:.3f} s = {got.mappings_per_s:.0f} mappings/s, "
                f"verdicts and values match numpy under F32_TOL")
    runner = step._jit_runner(pb.hmax, pb.iterations, pb.shape, True)
    assert_on_chip(runner.lower(*step.device_args(pb)).compile().as_text(),
                   "pallas cycle loop")


# -- Track A: motif PCU kernel -------------------------------------------------


def phase_pcu() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.motif_pcu import FANIN, FANOUT, UNICAST

    rng = np.random.default_rng(0)
    ins = jnp.asarray(rng.standard_normal((3, PCU_LANES)), jnp.float32)
    for name, sched in (("FANIN", FANIN), ("FANOUT", FANOUT),
                        ("UNICAST", UNICAST)):
        kw = dict(schedule=sched, n_inputs=3)
        assert_on_chip(ops.motif_pcu.lower(ins, **kw).compile().as_text(),
                       f"motif_pcu {name}")
        got = np.asarray(ops.motif_pcu(ins, **kw))
        want = np.asarray(ref.motif_pcu(sched, 3, ins))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        log(f"[pcu] {name}: {got.shape} table matches kernels.ref")


# -- Track B: full-width serving ---------------------------------------------


def phase_serve(dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import zoo
    from repro.serve.kvcache import grow_cache
    from repro.train import steps

    cfg = get_config(SERVE_ARCH)
    log(f"[serve] {cfg.arch_id}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}, "
        f"{cfg.param_count() / 1e9:.2f} B params ({cfg.dtype})")
    t0 = time.perf_counter()
    params, prompts, tokens, info = serve(
        cfg, batch=SERVE_BATCH, prompt_len=PROMPT_LEN,
        new_tokens=NEW_TOKENS, seed=0)
    tokens = np.asarray(tokens)
    log(f"[serve] generate: batch {SERVE_BATCH}, prompt {PROMPT_LEN}, "
        f"{tokens.shape[1]} new tokens in {time.perf_counter() - t0:.2f} s "
        f"(weights, compiles and run), cache length {info['cache_length']}")
    assert tokens.shape == (SERVE_BATCH, NEW_TOKENS), tokens.shape
    assert info["cache_length"] == PROMPT_LEN + NEW_TOKENS - 1

    # teacher-forced replay of the same requests through prefill + decode
    prefill = jax.jit(steps.make_prefill_step(cfg))
    decode = jax.jit(steps.make_serve_step(cfg))
    cache, logits = prefill(params, {"tokens": prompts})
    cache = grow_cache(cache, NEW_TOKENS, window=cfg.sliding_window)
    got = [logits[:, -1]]
    for i in range(NEW_TOKENS - 1):
        cache, nxt, logits = decode(params, cache,
                                    jnp.asarray(tokens[:, i:i + 1]))
        np.testing.assert_array_equal(np.asarray(nxt)[:, 0],
                                      tokens[:, i + 1])
        got.append(logits[:, -1])
    got = np.asarray(jnp.stack(got, axis=1), np.float32)   # (B, new, V)
    np.testing.assert_array_equal(got.argmax(-1), tokens)

    # reference: one forward pass over prompt + generated tokens
    ext = jnp.concatenate([prompts, jnp.asarray(tokens[:, :-1])], axis=1)

    @jax.jit
    def ref_logits(p, toks):
        h = zoo.forward(cfg, p, {"tokens": toks})[:, PROMPT_LEN - 1:]
        return (h @ p["emb"].T).astype(jnp.float32)

    want = np.asarray(ref_logits(params, ext))
    assert np.isfinite(got).all() and np.isfinite(want).all()
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))                 # (B, new)
    agree = float((want.argmax(-1) == tokens).mean())
    log(f"[serve] decode vs forward logits: max rel err {rel.max():.4f} "
        f"(bound {SERVE_REL_TOL}), greedy agreement {agree:.3f}")
    assert rel.max() <= SERVE_REL_TOL, rel.max()
    stats = dev.memory_stats() or {}
    log(f"[serve] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def main() -> int:
    dev = check_device()
    sys.path.insert(0, str(REPO / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind}, "
        f"{len(jax.devices())} device(s); jax {jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    clock.install()
    failed = []
    for name, phase in (("verify", phase_verify), ("pcu", phase_pcu),
                        ("serve", lambda: phase_serve(dev))):
        with clock.phase(name):
            try:
                phase()
            except Exception:  # noqa: BLE001 - report every phase, then fail
                traceback.print_exc()
                failed.append(name)
    log(f"total compile {clock.compile_s:.2f} s, persistent cache "
        f"{clock.hits} hit(s) / {clock.misses} miss(es)")
    if failed:
        print(f"chip_smoke: phase(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
