"""Dense decoder-only LM (llama-style pre-norm GQA + SwiGLU).

Covers the dense archs (stablelm-12b, qwen3-14b, llama3.2-3b,
h2o-danube-3-4b with SWA) and the VLM backbone (qwen2-vl-72b: token
*embeddings* come in pre-computed, positions are 3-axis M-RoPE ids).

Layers are stacked on a leading ``layers`` axis and walked with
``lax.scan`` so the HLO stays compact for the 512-device dry-run.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.layers import Spec


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _stack(spec_tree, n: int):
    return L.spec_map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init), spec_tree
    )


def layer_param_spec(cfg) -> Dict[str, Spec]:
    p = {
        "attn": L.attention_param_spec(cfg),
        "mlp": L.mlp_param_spec(cfg),
        "ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": Spec((cfg.d_model,), ("embed",), init="ones"),
    }
    return p


def param_spec(cfg) -> Dict[str, Spec]:
    return {
        **L.embed_param_spec(cfg),
        "layers": _stack(layer_param_spec(cfg), cfg.n_layers),
        "ln_f": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _block(cfg, w, x, positions):
    h, _ = L.attention_layer(
        cfg, w["attn"], L.rms_norm(x, w["ln1"]), positions, attn_impl=cfg.attn_impl
    )
    x = x + h
    x = x + L.swiglu(w["mlp"], L.rms_norm(x, w["ln2"]))
    return x


def forward(cfg, params, batch) -> jax.Array:
    """Returns final hidden states (B, T, D)."""
    if cfg.family == "vlm":
        x = batch["embeds"]
        positions = batch["positions"]  # (B, 3, T)
    else:
        x = L.embed_lookup(params["emb"], batch["tokens"])
        B, T = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    block = lambda xx, ww: (_block(cfg, ww, xx, positions), None)
    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, _ = L.scan_layers(cfg, block, x, params["layers"])
    return L.rms_norm(x, params["ln_f"])


def loss_fn(cfg, params, batch) -> Tuple[jax.Array, Dict]:
    h = forward(cfg, params, batch)
    nll = L.chunked_xent(h, params["emb"], batch["labels"], cfg.logits_chunk)
    return nll, {"loss": nll}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with ring-buffer KV cache
# ---------------------------------------------------------------------------


def cache_len(cfg, seq_len: int) -> int:
    return min(cfg.sliding_window, seq_len) if cfg.sliding_window else seq_len


def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    S = cache_len(cfg, seq_len)
    kvd = cfg.n_kv_heads * cfg.resolved_head_dim
    # long-context decode has global_batch=1: shard the cache sequence dim
    seq_axis = "cache_seq" if batch == 1 else None
    return {
        "k": Spec((cfg.n_layers, batch, S, kvd), ("layers", "batch", seq_axis, "kv_heads")),
        "v": Spec((cfg.n_layers, batch, S, kvd), ("layers", "batch", seq_axis, "kv_heads")),
        "pos": Spec((batch, S), ("batch", seq_axis), jnp.int32),  # abs position; -1 empty
        "length": Spec((batch,), ("batch",), jnp.int32),
    }


def prefill(cfg, params, batch) -> Tuple[Dict, jax.Array]:
    """Run the full prompt, return (cache, last-token logits)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    S = cache_len(cfg, T)
    if cfg.family == "vlm":
        x = batch["embeds"]
        positions = batch["positions"]
    else:
        x = L.embed_lookup(params["emb"], tokens)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def block(xx, ww):
        h, (k, v) = L.attention_layer(
            cfg, ww["attn"], L.rms_norm(xx, ww["ln1"]), positions, attn_impl=cfg.attn_impl
        )
        xx = xx + h
        xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
        # keep the last S positions (ring-buffer layout: slot = pos % S)
        kk = k.reshape(B, T, -1)[:, T - S :]
        vv = v.reshape(B, T, -1)[:, T - S :]
        if cfg.sliding_window and S == cfg.sliding_window:
            # roll so that slot index == abs_position % S
            shift = (T - S) % S
            kk = jnp.roll(kk, shift, axis=1)
            vv = jnp.roll(vv, shift, axis=1)
        return xx, (kk, vv)

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, (ks, vs) = L.scan_layers(cfg, block, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"])
    logits = (x[:, -1:] @ params["emb"].T).astype(jnp.float32)

    slot_pos = jnp.arange(S, dtype=jnp.int32)
    if cfg.sliding_window and S == cfg.sliding_window:
        base = T - S
        pos = base + ((slot_pos - (T % S)) % S)  # abs position stored in each slot
    else:
        pos = slot_pos
    cache = {
        "k": ks,
        "v": vs,
        "pos": jnp.broadcast_to(pos[None], (B, S)),
        "length": jnp.full((B,), T, jnp.int32),
    }
    return cache, logits


def decode_step(cfg, params, cache, tokens) -> Tuple[Dict, jax.Array]:
    """One decode step: tokens (B, 1) -> (new cache, logits (B, 1, V)).

    Each layer reads its slice of the cache in place and returns the new
    position's K/V row; one update writes every layer's row into the
    (donated) cache after the layers have run."""
    B = tokens.shape[0]
    ks, vs = cache["k"], cache["v"]
    S = ks.shape[2]
    length = cache["length"]  # (B,)
    positions = length[:, None].astype(jnp.int32)  # (B, 1)
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[:, None, :], (B, 3, 1))

    x = L.embed_lookup(params["emb"], tokens)
    slot = (length % S).astype(jnp.int32)  # (B,)
    barange = jnp.arange(B)

    new_pos = cache["pos"].at[barange, slot].set(length)
    if cfg.sliding_window:
        valid = (new_pos >= 0) & ((length[:, None] - new_pos) < cfg.sliding_window)
    else:
        valid = new_pos >= 0
    valid &= new_pos <= length[:, None]
    # the slot the new row overwrites holds a position the step no longer sees
    valid &= jnp.arange(S)[None, :] != slot[:, None]

    def block(xx, scan_in):
        ww, i = scan_in
        h = L.rms_norm(xx, ww["ln1"])
        q, k, v = L.attention_qkv(cfg, ww["attn"], h, positions)
        o = L.decode_attention_row(q, ks[i], vs[i], valid, k, v)
        xx = xx + o.reshape(B, 1, -1) @ ww["attn"]["wo"]
        xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
        return xx, (k.reshape(B, -1).astype(ks.dtype), v.reshape(B, -1).astype(vs.dtype))

    x, (k_rows, v_rows) = L.scan_layers(
        cfg, block, x, (params["layers"], jnp.arange(cfg.n_layers))
    )
    for b in range(B):  # in place: a scatter over (B, S) would re-lay the cache
        ks = lax.dynamic_update_slice(ks, k_rows[:, b, None, None], (0, b, slot[b], 0))
        vs = lax.dynamic_update_slice(vs, v_rows[:, b, None, None], (0, b, slot[b], 0))
    x = L.rms_norm(x, params["ln_f"])
    logits = (x @ params["emb"].T).astype(jnp.float32)
    new_cache = {"k": ks, "v": vs, "pos": new_pos, "length": length + 1}
    return new_cache, logits
