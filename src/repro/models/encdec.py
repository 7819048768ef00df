"""Whisper-style encoder-decoder backbone (audio frontend stubbed).

Encoder: bidirectional self-attention over precomputed audio-frame
embeddings (the mel-spectrogram conv frontend is a STUB per the assignment —
``input_specs`` supplies (B, enc_seq, d_model) embeddings directly).
Decoder: causal self-attention + cross-attention to the encoder output.
RoPE replaces Whisper's learned absolute positions (documented).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.layers import Spec
from repro.serve import kvcache as kv


def enc_layer_spec(cfg) -> Dict[str, Spec]:
    return {
        "attn": L.attention_param_spec(cfg),
        "mlp": L.mlp_param_spec(cfg),
        "ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def dec_layer_spec(cfg) -> Dict[str, Spec]:
    return {
        "self_attn": L.attention_param_spec(cfg),
        "cross_attn": L.attention_param_spec(cfg),
        "mlp": L.mlp_param_spec(cfg),
        "ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln_x": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def param_spec(cfg) -> Dict[str, Spec]:
    return {
        **L.embed_param_spec(cfg),
        "encoder": L.stack_layers(enc_layer_spec(cfg), cfg.n_enc_layers),
        "decoder": L.stack_layers(dec_layer_spec(cfg), cfg.n_layers),
        "ln_enc": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln_f": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def encode(cfg, params, audio_embeds: jax.Array) -> jax.Array:
    B, S, _ = audio_embeds.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def block(xx, ww):
        h, _ = L.attention_layer(
            cfg, ww["attn"], L.rms_norm(xx, ww["ln1"]), positions, causal=False
        )
        xx = xx + h
        xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
        return xx, None

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, _ = L.scan_layers(cfg, block, audio_embeds, params["encoder"])
    return L.rms_norm(x, params["ln_enc"])


def _dec_block(cfg, ww, xx, positions, enc_out, *, want_kv=False):
    h, self_kv = L.attention_layer(
        cfg, ww["self_attn"], L.rms_norm(xx, ww["ln1"]), positions, attn_impl=cfg.attn_impl
    )
    xx = xx + h
    h, cross_kv = L.attention_layer(
        cfg, ww["cross_attn"], L.rms_norm(xx, ww["ln_x"]), positions, cross_x=enc_out
    )
    xx = xx + h
    xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
    if want_kv:
        return xx, (self_kv, cross_kv)
    return xx, None


def forward(cfg, params, batch) -> jax.Array:
    enc_out = encode(cfg, params, batch["audio_embeds"])
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.embed_lookup(params["emb"], tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def block(xx, ww):
        return _dec_block(cfg, ww, xx, positions, enc_out)

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, _ = L.scan_layers(cfg, block, x, params["decoder"])
    return L.rms_norm(x, params["ln_f"])


def loss_fn(cfg, params, batch):
    h = forward(cfg, params, batch)
    nll = L.chunked_xent(h, params["emb"], batch["labels"], cfg.logits_chunk)
    return nll, {"loss": nll}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    kvd = cfg.n_kv_heads * cfg.resolved_head_dim
    Ld = cfg.n_layers
    return {
        **kv.spec(cfg, Ld, batch, seq_len),
        "xk": Spec((Ld, batch, cfg.enc_seq, kvd), ("layers", "batch", None, "kv_heads")),
        "xv": Spec((Ld, batch, cfg.enc_seq, kvd), ("layers", "batch", None, "kv_heads")),
    }


def prefill(cfg, params, batch):
    enc_out = encode(cfg, params, batch["audio_embeds"])
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.embed_lookup(params["emb"], tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def block(xx, ww):
        xx, (self_kv, cross_kv) = _dec_block(cfg, ww, xx, positions, enc_out, want_kv=True)
        (k, v), (xk, xv) = self_kv, cross_kv
        return xx, (
            kv.prompt_rows(cfg, k),
            kv.prompt_rows(cfg, v),
            xk.reshape(B, cfg.enc_seq, -1),
            xv.reshape(B, cfg.enc_seq, -1),
        )

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, (ks, vs, xks, xvs) = L.scan_layers(cfg, block, x, params["decoder"])
    x = L.rms_norm(x, params["ln_f"])
    logits = (x[:, -1:] @ params["emb"].T).astype(jnp.float32)
    return {"k": ks, "v": vs, "xk": xks, "xv": xvs, **kv.prompt_state(cfg, B, T)}, logits


def decode_step(cfg, params, cache, tokens):
    B = tokens.shape[0]
    hd = cfg.resolved_head_dim
    length = cache["length"]
    positions = length[:, None].astype(jnp.int32)
    x = L.embed_lookup(params["emb"], tokens)
    step = kv.step(cache, cfg.sliding_window)
    xvalid = jnp.ones((B, cfg.enc_seq), bool)

    def block(xx, scan_in):
        ww, i, xk, xv = scan_in
        h, (k, v) = L.decode_attention_layer(
            cfg, ww["self_attn"], L.rms_norm(xx, ww["ln1"]), positions,
            cache["k"], cache["v"], i, step.mask,
        )
        xx = xx + h
        # cross-attention against the static encoder KV
        hh = L.rms_norm(xx, ww["ln_x"])
        q = (hh @ ww["cross_attn"]["wq"]).reshape(B, 1, cfg.n_heads, hd)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        o = L.decode_attention(
            q,
            xk.reshape(B, cfg.enc_seq, cfg.n_kv_heads, hd),
            xv.reshape(B, cfg.enc_seq, cfg.n_kv_heads, hd),
            xvalid,
        )
        xx = xx + o.reshape(B, 1, -1) @ ww["cross_attn"]["wo"]
        xx = xx + L.swiglu(ww["mlp"], L.rms_norm(xx, ww["ln2"]))
        return xx, (kv.row(k, cache["k"]), kv.row(v, cache["v"]))

    x, (k_rows, v_rows) = L.scan_layers(
        cfg, block, x, (params["decoder"], jnp.arange(cfg.n_layers), cache["xk"], cache["xv"])
    )
    x = L.rms_norm(x, params["ln_f"])
    logits = (x @ params["emb"].T).astype(jnp.float32)
    seq = kv.write_rows(cache, {"k": k_rows, "v": v_rows}, step.slot)
    new_cache = dict(cache, **seq, pos=step.pos, length=length + 1)
    return new_cache, logits
