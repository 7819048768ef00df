"""KV-cache utilities.

``grow_cache`` pads a prefill-produced cache with empty decode headroom —
prefill allocates exactly the prompt length (what the dry-run lowers at
fixed shapes); serving extends it before decoding.  It grows every cache
laid out as ``(..., B, S, width)``: keys and values, or the latents of
latent attention.  SSM states (conv/h) are constant-size and need no
growth; sliding-window ring buffers are already window-bounded and wrap
correctly.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp

#: the cache entries laid out along the sequence (axis -2)
SEQ_KEYS = ("k", "v", "latent")


def grow_cache(cache: Dict, extra: int, *, window: int = 0) -> Dict:
    keys = [k for k in SEQ_KEYS if k in cache]
    if extra <= 0 or not keys:
        return cache
    S = cache[keys[0]].shape[-2]
    if window:
        # a window-bounded ring never needs to exceed the window; a
        # prompt-sized cache below the window still must grow
        extra = min(window, S + extra) - S
        if extra <= 0:
            return cache
    out = dict(cache)
    for key in keys:
        t = out[key]
        pad = [(0, 0)] * t.ndim
        pad[-2] = (0, extra)  # (..., B, S, width): grow S
        out[key] = jnp.pad(t, pad)
    if "pos" in out:
        out["pos"] = jnp.pad(out["pos"], ((0, 0), (0, extra)), constant_values=-1)
    return out
