"""Zamba2-style hybrid: Mamba-2 backbone + one *shared* attention block.

The shared transformer block (attention + SwiGLU, one set of weights) is
re-applied after every ``attn_every`` Mamba-2 blocks — the Zamba trick, and
architecturally the same move as Plaid's domain-specialized PCU: one
hardwired, reused unit serving many sites. Per-site LoRA deltas from the
paper's checkpoint are omitted (documented simplification).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.layers import Spec
from repro.serve import kvcache as kv


def n_groups(cfg) -> Tuple[int, int]:
    g = cfg.n_layers // cfg.attn_every
    rem = cfg.n_layers - g * cfg.attn_every
    return g, rem


def shared_block_spec(cfg) -> Dict[str, Spec]:
    return {
        "attn": L.attention_param_spec(cfg),
        "mlp": L.mlp_param_spec(cfg),
        "ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def param_spec(cfg) -> Dict[str, Spec]:
    return {
        **L.embed_param_spec(cfg),
        "mamba": L.stack_layers(S.mamba2_param_spec(cfg), cfg.n_layers),
        "shared": shared_block_spec(cfg),
        "ln_f": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def _split_groups(cfg, stacked):
    """(L, ...) stacked mamba weights -> ((G, k, ...), (rem, ...))."""
    g, rem = n_groups(cfg)
    k = cfg.attn_every
    grouped = jax.tree.map(lambda t: t[: g * k].reshape((g, k) + t.shape[1:]), stacked)
    tail = jax.tree.map(lambda t: t[g * k :], stacked)
    return grouped, tail


def _shared_attn(cfg, shared, x, positions, *, want_kv=False):
    h, kv = L.attention_layer(
        cfg, shared["attn"], L.rms_norm(x, shared["ln1"]), positions, attn_impl=cfg.attn_impl
    )
    x = x + h
    x = x + L.swiglu(shared["mlp"], L.rms_norm(x, shared["ln2"]))
    return (x, kv) if want_kv else (x, None)


def forward(cfg, params, batch) -> jax.Array:
    x = L.embed_lookup(params["emb"], batch["tokens"])
    B, T = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    grouped, tail = _split_groups(cfg, params["mamba"])
    shared = params["shared"]

    def mamba_step(xx, ww):
        h, _ = S.mamba2_block(cfg, ww, L.rms_norm(xx, ww["ln"]))
        return xx + h, None

    policy = L.remat_policy(cfg.remat)
    mamba_step_c = jax.checkpoint(mamba_step, policy=policy) if policy else mamba_step

    def group(xx, ws):
        xx, _ = lax.scan(mamba_step_c, xx, ws)
        xx, _ = _shared_attn(cfg, shared, xx, positions)
        return xx, None

    x, _ = L.scan_layers(cfg, group, x, grouped)
    g, rem = n_groups(cfg)
    if rem:
        x, _ = L.scan_layers(cfg, mamba_step_c, x, tail)
    return L.rms_norm(x, params["ln_f"])


def loss_fn(cfg, params, batch):
    h = forward(cfg, params, batch)
    nll = L.chunked_xent(h, params["emb"], batch["labels"], cfg.logits_chunk)
    return nll, {"loss": nll}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    Di, N, K = cfg.d_inner, cfg.ssm_state, cfg.d_conv
    H, P = cfg.n_ssm_heads, cfg.d_inner // cfg.n_ssm_heads
    g, _ = n_groups(cfg)
    return {
        "conv": Spec((cfg.n_layers, batch, K - 1, Di), ("layers", "batch", None, "mlp")),
        "h": Spec(
            (cfg.n_layers, batch, H, P, N), ("layers", "batch", None, "mlp", "state"), jnp.float32
        ),
        # one KV cache per shared-attention application site
        **kv.spec(cfg, g, batch, seq_len),
    }


def prefill(cfg, params, batch):
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.embed_lookup(params["emb"], tokens)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    grouped, tail = _split_groups(cfg, params["mamba"])
    shared = params["shared"]

    def mamba_step(xx, ww):
        zero = {
            "conv": jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), xx.dtype),
            "h": jnp.zeros(
                (B, cfg.n_ssm_heads, cfg.d_inner // cfg.n_ssm_heads, cfg.ssm_state), jnp.float32
            ),
        }
        h, c = S.mamba2_block(cfg, ww, L.rms_norm(xx, ww["ln"]), zero)
        return xx + h, c

    policy = L.remat_policy(cfg.remat)
    mamba_step_c = jax.checkpoint(mamba_step, policy=policy) if policy else mamba_step

    def group(xx, ws):
        xx, caches = lax.scan(mamba_step_c, xx, ws)
        xx, (k, v) = _shared_attn(cfg, shared, xx, positions, want_kv=True)
        return xx, (caches, kv.prompt_rows(cfg, k), kv.prompt_rows(cfg, v))

    x, (gcaches, ks, vs) = L.scan_layers(cfg, group, x, grouped)
    g, rem = n_groups(cfg)
    conv = gcaches["conv"].reshape((g * cfg.attn_every,) + gcaches["conv"].shape[2:])
    hst = gcaches["h"].reshape((g * cfg.attn_every,) + gcaches["h"].shape[2:])
    if rem:
        x, tcaches = lax.scan(mamba_step_c, x, tail)
        conv = jnp.concatenate([conv, tcaches["conv"]], 0)
        hst = jnp.concatenate([hst, tcaches["h"]], 0)
    x = L.rms_norm(x, params["ln_f"])
    logits = (x[:, -1:] @ params["emb"].T).astype(jnp.float32)
    return {"conv": conv, "h": hst, "k": ks, "v": vs, **kv.prompt_state(cfg, B, T)}, logits


def decode_step(cfg, params, cache, tokens):
    length = cache["length"]
    positions = length[:, None].astype(jnp.int32)
    x = L.embed_lookup(params["emb"], tokens)
    step = kv.step(cache, cfg.sliding_window)
    grouped, tail = _split_groups(cfg, params["mamba"])
    shared = params["shared"]
    g, rem = n_groups(cfg)
    k_every = cfg.attn_every

    def mamba_dec(xx, scan_in):
        ww, conv, h = scan_in
        out, nc = S.mamba2_block(cfg, ww, L.rms_norm(xx, ww["ln"]), {"conv": conv, "h": h})
        return xx + out, (nc["conv"], nc["h"])

    def group(xx, scan_in):
        ws, conv_g, h_g, i = scan_in
        xx, (nconv, nh) = lax.scan(mamba_dec, xx, (ws, conv_g, h_g))
        # shared attention over site i's KV cache
        h, (k, v) = L.decode_attention_layer(
            cfg, shared["attn"], L.rms_norm(xx, shared["ln1"]), positions,
            cache["k"], cache["v"], i, step.mask,
        )
        xx = xx + h
        xx = xx + L.swiglu(shared["mlp"], L.rms_norm(xx, shared["ln2"]))
        return xx, (nconv, nh, kv.row(k, cache["k"]), kv.row(v, cache["v"]))

    conv_g = cache["conv"][: g * k_every].reshape((g, k_every) + cache["conv"].shape[1:])
    h_g = cache["h"][: g * k_every].reshape((g, k_every) + cache["h"].shape[1:])
    x, (nconv, nh, k_rows, v_rows) = L.scan_layers(
        cfg, group, x, (grouped, conv_g, h_g, jnp.arange(g))
    )
    conv = nconv.reshape((g * k_every,) + nconv.shape[2:])
    hst = nh.reshape((g * k_every,) + nh.shape[2:])
    if rem:
        x, (tconv, th) = lax.scan(
            mamba_dec, x, (tail, cache["conv"][g * k_every :], cache["h"][g * k_every :])
        )
        conv = jnp.concatenate([conv, tconv], 0)
        hst = jnp.concatenate([hst, th], 0)
    x = L.rms_norm(x, params["ln_f"])
    logits = (x @ params["emb"].T).astype(jnp.float32)
    seq = kv.write_rows(cache, {"k": k_rows, "v": v_rows}, step.slot)
    new_cache = {"conv": conv, "h": hst, **seq, "pos": step.pos, "length": length + 1}
    return new_cache, logits
