"""Multi-head latent attention (DeepSeek-V3's MLA, with ``q_lora_rank`` null).

Per position, ``x @ wq`` gives each head a query of ``qk_nope_head_dim``
plain and ``qk_rope_head_dim`` rotated features.  ``x @ wkv_a`` gives the
KV latent (``kv_lora_rank`` wide, RMS-normalised by ``kv_norm``) and one
rotated key part that every head shares.  ``wkv_b`` expands the latent
into each head's plain key and its value (``v_head_dim``).  Scores are
scaled by ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.  RoPE is the
half-split rotation of ``layers.apply_rope``.

The cache holds, per position, the normalised latent and the rotated
shared key: ``kv_lora_rank + qk_rope_head_dim`` values in place of
``2 * heads * head_dim``.  Prefill runs the expanded form above.  Decode
runs the absorbed form: the key half of ``wkv_b`` folds into the query and
its value half is applied after attention, so a step reads the cached
latents once and never expands them.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import Spec


def param_spec(cfg) -> Dict[str, Spec]:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": Spec((d, H * (nope + rope)), ("embed", "heads")),
        "wkv_a": Spec((d, r + rope), ("embed", None)),
        "kv_norm": Spec((r,), (None,), init="ones"),
        "wkv_b": Spec((r, H * (nope + dv)), (None, "heads")),
        "wo": Spec((H * dv, d), ("heads", "embed")),
    }


def latent_dim(cfg) -> int:
    """Values cached per position and layer."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def _scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _query(cfg, w, x, positions):
    B, T, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ w["wq"]).reshape(B, T, cfg.n_heads, nope + cfg.qk_rope_head_dim)
    return q[..., :nope], L.apply_rope(q[..., nope:], positions, cfg.rope_theta)


def latent(cfg, w, x, positions) -> jax.Array:
    """What the cache keeps of each position: (B, T, latent_dim)."""
    r = cfg.kv_lora_rank
    ckv = x @ w["wkv_a"]
    c = L.rms_norm(ckv[..., :r], w["kv_norm"], cfg.rms_norm_eps)
    k_pe = L.apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_pe], axis=-1)


def attention(cfg, w, x, positions) -> Tuple[jax.Array, jax.Array]:
    """Causal attention over a whole sequence in the expanded form (train
    and prefill): ``(out (B, T, D), latent (B, T, latent_dim))``."""
    B, T, _ = x.shape
    H, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_nope, q_pe = _query(cfg, w, x, positions)
    lat = latent(cfg, w, x, positions)
    kv = (lat[..., :r] @ w["wkv_b"]).reshape(B, T, H, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(lat[:, :, None, r:], (B, T, H, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    v = kv[..., nope:]
    if cfg.attn_impl == "banded":
        o = L.banded_attention(q, k, v, window=cfg.sliding_window, chunk=min(cfg.attn_chunk, T))
    else:
        o = L.naive_attention(q, k, v, window=cfg.sliding_window)
    return o.reshape(B, T, -1) @ w["wo"], lat


def decode_attention(cfg, w, x, positions, cached, valid):
    """One new position per sequence in the absorbed form.

    ``x`` (B, 1, D); ``cached`` (B, S, latent_dim), this layer's cache
    before the step; ``valid`` (B, S), the cached slots this step attends.
    The new position attends itself too; it is returned as a row
    (B, latent_dim) for the caller to write into the cache.
    """
    B = x.shape[0]
    H, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dt = cached.dtype
    q_nope, q_pe = _query(cfg, w, x, positions)
    row = latent(cfg, w, x, positions)[:, 0]
    wkv_b = w["wkv_b"].reshape(r, H, nope + cfg.v_head_dim)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wkv_b[..., :nope],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat.astype(dt), q_pe[:, 0].astype(dt)], axis=-1)
    s_old = jnp.einsum("bhc,bsc->bhs", q, cached, preferred_element_type=jnp.float32)
    s_new = jnp.einsum("bhc,bc->bh", q, row.astype(dt), preferred_element_type=jnp.float32)
    s = jnp.concatenate([jnp.where(valid[:, None, :], s_old, -1e30), s_new[..., None]], -1)
    p = jax.nn.softmax(s * _scale(cfg), axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p[..., :-1].astype(dt), cached[..., :r],
                       preferred_element_type=jnp.float32)
    o_lat = o_lat + p[..., -1:] * row[:, None, :r].astype(jnp.float32)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), wkv_b[..., nope:],
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, -1).astype(x.dtype) @ w["wo"], row
