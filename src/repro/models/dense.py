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

from repro.models import layers as L
from repro.models.layers import Spec
from repro.serve import kvcache as kv


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


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
        "layers": L.stack_layers(layer_param_spec(cfg), cfg.n_layers),
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


def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    return kv.spec(cfg, cfg.n_layers, batch, seq_len)


def prefill(cfg, params, batch) -> Tuple[Dict, jax.Array]:
    """Run the full prompt, return (cache, last-token logits)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
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
        return xx, (kv.prompt_rows(cfg, k), kv.prompt_rows(cfg, v))

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, (ks, vs) = L.scan_layers(cfg, block, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"])
    logits = (x[:, -1:] @ params["emb"].T).astype(jnp.float32)
    return {"k": ks, "v": vs, **kv.prompt_state(cfg, B, T)}, logits


def decode_step(cfg, params, cache, tokens) -> Tuple[Dict, jax.Array]:
    """One decode step: tokens (B, 1) -> (new cache, logits (B, 1, V))."""
    B = tokens.shape[0]
    ks, vs = cache["k"], cache["v"]
    length = cache["length"]  # (B,)
    positions = length[:, None].astype(jnp.int32)  # (B, 1)
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[:, None, :], (B, 3, 1))

    x = L.embed_lookup(params["emb"], tokens)
    step = kv.step(cache, cfg.sliding_window)

    def block(xx, scan_in):
        ww, i = scan_in
        h, (k, v) = L.decode_attention_layer(
            cfg, ww["attn"], L.rms_norm(xx, ww["ln1"]), positions, ks, vs, i, step.mask
        )
        xx = xx + h
        xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
        return xx, (kv.row(k, ks), kv.row(v, vs))

    x, (k_rows, v_rows) = L.scan_layers(
        cfg, block, x, (params["layers"], jnp.arange(cfg.n_layers))
    )
    seq = kv.write_rows(cache, {"k": k_rows, "v": v_rows}, step.slot)
    x = L.rms_norm(x, params["ln_f"])
    logits = (x @ params["emb"].T).astype(jnp.float32)
    return {**seq, "pos": step.pos, "length": length + 1}, logits
