"""What the readers of the serving program's named scopes share.

The decode step (``train/steps.make_serve_step``, program
``jit_serve_step``) of the ``moe`` family names its parts with
``jax.named_scope``: ``mla`` (latent attention), ``moe/route`` (router,
top-k, grouping), ``moe/experts`` (each layer's slice of the held
experts' weights and the grouped matmuls), ``moe/shared``
(the shared experts) and ``moe/combine``.  The scopes live in the
``op_name`` metadata of the compiled program, which the trace's op events
do not carry, so the map from op to scope is read from the compiled text
of the cell's ``serve_step`` at the window's shapes (a compile-cache
load after the window), as ``sim_spans.op_scopes`` does for the
simulator.

A program without these scopes (one from before they existed) gives no
op under them: the readers then return ``None``, never an error.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional

import sim_spans

MODULE = "serve_step"
MOE_PARTS = ("route", "experts", "shared", "combine")
#: the TPU compiler expands ``lax.ragged_dot`` (the experts' grouped
#: matmuls, the only ones in these programs) into custom calls whose
#: ``op_name`` is this prefix and not the scope they were traced in
GROUPED_MATMUL = "ragged-dot"
#: label of an op whose metadata names none of the scopes
NO_SCOPE = "none"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\bmetadata=\{'
                    r'[^}]*?op_name="([^"]*)"', re.M)


def scope_of(op_name: str) -> str:
    """``jit(serve_step)/while/body/moe/experts/mul`` and
    ``ragged-dot-none`` -> ``moe/experts``; ``.../mla/dot_general`` ->
    ``mla``; else ``none``."""
    if op_name.startswith(GROUPED_MATMUL):
        return "moe/experts"
    parts = op_name.split("/")
    if "moe" in parts:
        rest = parts[parts.index("moe") + 1:]
        if rest and rest[0] in MOE_PARTS:
            return f"moe/{rest[0]}"
    if "mla" in parts:
        return "mla"
    return NO_SCOPE


def op_scopes(run) -> Dict[str, str]:
    """``op name -> op_name metadata`` of the cell's ``serve_step`` at the
    window's shapes, from its compiled text; kept on the run."""
    if getattr(run, "_op_scopes", None) is None:
        import jax
        import jax.numpy as jnp

        from repro.serve.kvcache import grow_cache
        from repro.train import steps

        cfg, params = run.cfg, run.params
        dev = jax.tree.leaves(params)[0].sharding

        def on_chip(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)

        def cache_of(p, prompts):
            cache, _ = steps.make_prefill_step(cfg)(p, {"tokens": prompts})
            return grow_cache(cache, run.new, window=cfg.sliding_window)

        prompts = jax.ShapeDtypeStruct((run.B, run.T), jnp.int32)
        cache = jax.tree.map(on_chip, jax.eval_shape(cache_of, params,
                                                     prompts))
        tokens = on_chip(jax.ShapeDtypeStruct((run.B, 1), jnp.int32))
        serve = jax.jit(steps.make_serve_step(cfg), donate_argnums=(1,))
        text = serve.lower(params, cache, tokens).compile().as_text()
        run._op_scopes = dict(_INSTR.findall(text))
    return run._op_scopes


def scope_ms(tr, run) -> Optional[Dict[str, float]]:
    """Device milliseconds of ``serve_step``'s ops by scope, summed over
    the window's steps, each op by its self time as ``Trace.top_ops``
    counts it; ``None`` without a step in the window.  Kept on the run for
    the trace it was read from, as every reader of the cell asks."""
    cached = getattr(run, "_scope_ms", None)
    if cached is not None and cached[0] is tr:
        return cached[1]
    calls = tr.module_calls(MODULE)
    out: Optional[Dict[str, float]] = None
    if calls:
        ops = sim_spans._ops_inside(tr.devices[0].ops, calls)
        steps = type(tr)(devices=[type(tr.devices[0])(modules=[], ops=ops)],
                         spans=[], window=tr.window)
        scopes = op_scopes(run)
        out = defaultdict(float)
        for name, secs in steps.top_ops(len(ops)):
            out[scope_of(scopes.get(name, ""))] += secs * 1e3
        out = dict(out)
    run._scope_ms = (tr, out)
    return out


def steps_in_window(tr, run) -> Optional[int]:
    """Decode steps in the window, when the trace holds every step of
    every call the window ran, else ``None``."""
    n = len(tr.module_calls(MODULE))
    want = len(run.batches) * run.work()["decode_steps"]
    return n if n and n == want else None


def roofline(tr, run, ctx, scope: str, key: str) -> Optional[float]:
    """``run.work()[key]`` bytes of the window's calls over the HBM
    bandwidth, over the device time of the ops under ``scope``, in %."""
    split = scope_ms(tr, run)
    if steps_in_window(tr, run) is None or not split or split.get(
            scope, 0.0) <= 0:
        return None
    moved = len(run.batches) * run.work()[key]
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / (
        split[scope] / 1e3)
