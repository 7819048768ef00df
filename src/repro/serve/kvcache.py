"""The sequence cache: how every attention family lays out, attends and
writes the keys and values (or latents) of the positions it has seen.

Layout.  A cache holds, for ``n`` cached layers (or attention sites),
``(n, B, S, width)`` rows under ``SEQ_KEYS``: ``k`` and ``v`` of the KV
heads, or ``latent`` of latent attention.  ``pos`` (B, S) holds the
absolute position in each slot, ``-1`` for an empty one; ``length`` (B,)
counts the positions written.  Position ``p`` lives in slot ``p % S``.
``S`` is ``cache_len``: the prompt length, or at most the sliding window,
so a window-bounded cache is a ring that overwrites its oldest slot.

Prompt.  Prefill keeps each layer's last ``S`` positions
(``prompt_rows``), rolled so that slot == position % S once the prompt
is longer than the ring, and returns ``prompt_state``'s ``pos`` and
``length``.

Step.  A decode step writes position ``length`` into slot
``length % S``.  Each layer attends the cache as it was before the step,
read in place, over the slots of ``step``'s mask: written, not after the
step's position, within the window, and not the slot the new row
overwrites; the new position attends itself apart.  Each layer returns
only its new row (``row``), and ``write_rows`` writes every layer's row
after the layers have run, so the cache is never copied whole.

Growth.  Prefill allocates exactly the prompt; ``grow_cache`` pads the
rows with empty decode headroom before decoding, up to the window for a
window-bounded ring.  SSM states (conv/h) are constant-size and need no
growth.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import Spec

#: the cache entries laid out along the sequence (axis -2)
SEQ_KEYS = ("k", "v", "latent")


def cache_len(cfg, seq_len: int) -> int:
    return min(cfg.sliding_window, seq_len) if cfg.sliding_window else seq_len


def spec(cfg, n: int, batch: int, seq_len: int, *, latent: int = 0) -> Dict[str, Spec]:
    """The sequence cache of ``n`` cached layers (or attention sites):
    ``k``/``v`` of ``cfg``'s KV heads, or ``latent`` rows ``latent`` wide,
    with ``pos`` and ``length``."""
    S = cache_len(cfg, seq_len)
    # long-context decode has global_batch=1: shard the cache sequence dim
    seq_axis = "cache_seq" if batch == 1 else None
    if latent:
        rows = {"latent": Spec((n, batch, S, latent), ("layers", "batch", seq_axis, None))}
    else:
        kvd = cfg.n_kv_heads * cfg.resolved_head_dim
        rows = {key: Spec((n, batch, S, kvd), ("layers", "batch", seq_axis, "kv_heads"))
                for key in ("k", "v")}
    return {
        **rows,
        "pos": Spec((batch, S), ("batch", seq_axis), jnp.int32),  # abs position; -1 empty
        "length": Spec((batch,), ("batch",), jnp.int32),
    }


def prompt_rows(cfg, x: jax.Array) -> jax.Array:
    """What one layer's cache keeps of a prompt's ``(B, T, ...)`` entry:
    the last ``S`` positions as ``(B, S, width)`` rows, slot == position % S."""
    B, T = x.shape[:2]
    S = cache_len(cfg, T)
    rows = x.reshape(B, T, -1)[:, T - S :]
    if T > S:  # the ring has wrapped
        rows = jnp.roll(rows, (T - S) % S, axis=1)
    return rows


def prompt_state(cfg, B: int, T: int) -> Dict[str, jax.Array]:
    """``pos`` and ``length`` of a cache that ``prompt_rows`` filled."""
    S = cache_len(cfg, T)
    pos = jnp.arange(S, dtype=jnp.int32)
    if T > S:
        pos = T - S + ((pos - (T % S)) % S)  # abs position stored in each slot
    return {
        "pos": jnp.broadcast_to(pos[None], (B, S)),
        "length": jnp.full((B,), T, jnp.int32),
    }


class Step(NamedTuple):
    slot: jax.Array  # (B,) the slot the step's position goes to
    pos: jax.Array  # (B, S) ``pos`` after the step
    mask: jax.Array  # (B, S) the slots of the cache before the step it attends


def step(cache: Dict, window: int = 0) -> Step:
    """Where a decode step writes, and what it attends, in ``cache``."""
    length = cache["length"]  # (B,)
    B, S = cache["pos"].shape
    slot = (length % S).astype(jnp.int32)
    barange = jnp.arange(B)
    new_pos = cache["pos"].at[barange, slot].set(length)
    if window:
        valid = (new_pos >= 0) & ((length[:, None] - new_pos) < window)
    else:
        valid = new_pos >= 0
    valid &= new_pos <= length[:, None]
    # the slot the new row overwrites holds a position the step no longer sees
    valid &= jnp.arange(S)[None, :] != slot[:, None]
    return Step(slot, new_pos, valid)


def row(x: jax.Array, cached: jax.Array) -> jax.Array:
    """A new position's ``(B, 1, ...)`` entry as the ``(B, width)`` row the
    cache ``cached`` stores."""
    return x.reshape(x.shape[0], -1).astype(cached.dtype)


def write_rows(cache: Dict, rows: Dict[str, jax.Array], slot: jax.Array) -> Dict[str, jax.Array]:
    """``cache[key]`` with ``rows[key]`` (n, B, width), every layer's new
    row, written at each sequence's ``slot``."""
    out = {key: cache[key] for key in rows}
    for b in range(slot.shape[0]):  # in place: a scatter over (B, S) would re-lay the cache
        for key, r in rows.items():
            out[key] = lax.dynamic_update_slice(out[key], r[:, b, None, None], (0, b, slot[b], 0))
    return out


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
