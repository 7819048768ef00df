"""Batched serving driver: prefill once, then decode with greedy sampling.

Small-config CPU-runnable; the same ``prefill_step``/``serve_step`` pair is
what the dry-run lowers at production shapes (decode_32k / long_500k).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import zoo
from repro.serve.kvcache import grow_cache
from repro.train import steps as steps_lib


def generate(
    cfg: ModelConfig,
    params,
    prompts: jax.Array,  # (B, T) int32
    max_new_tokens: int = 16,
    extra_batch: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Dict]:
    batch = {"tokens": prompts}
    if extra_batch:
        batch.update(extra_batch)
    prefill = jax.jit(steps_lib.make_prefill_step(cfg))
    cache, logits = prefill(params, batch)
    if max_new_tokens <= 0:
        # exactly zero new tokens: prefill only (cache stays usable for a
        # later decode); the old loop emitted one token here regardless
        tokens = jnp.zeros((prompts.shape[0], 0), jnp.int32)
        return tokens, _info(cache)
    grow = functools.partial(grow_cache, extra=max_new_tokens, window=cfg.sliding_window)
    next_tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    out: List[jax.Array] = [next_tok]
    if max_new_tokens > 1:
        # built while the prefill runs, before the cache grows: a grown
        # cache's memory may have to wait for the prefill's to be freed
        serve = jax.jit(steps_lib.make_serve_step(cfg), donate_argnums=(1,)).lower(
            params, jax.eval_shape(grow, cache), next_tok).compile()
    cache = grow(cache)
    for _ in range(max_new_tokens - 1):
        cache, next_tok, _ = serve(params, cache, next_tok)
        out.append(next_tok)
    tokens = jnp.concatenate(out, axis=1)
    return tokens, _info(cache)


def _info(cache) -> Dict:
    """The call's cache length and whatever the model counted in its cache
    (``counters``), in one transfer from the device."""
    length, counters = jax.device_get((cache["length"][0], cache.get("counters", {})))
    return {"cache_length": int(length), **counters}
