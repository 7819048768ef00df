"""State-space models: Mamba-1 (selective scan) and Mamba-2 (SSD).

Mamba-1 (falcon-mamba-7b): the recurrence h_t = dA_t∘h_{t-1} + dB_t x_t has a
per-(channel, state) decay, so the within-chunk attention-like (SSD) trick
does not apply. We run a **nested scan**: outer `lax.scan` over chunks
(checkpointed — only chunk-boundary states are saved for backward), inner
`lax.scan` over time steps with the discretization recomputed per step so no
(B, T, D_inner, N) tensor is ever materialized. This makes the jnp path
memory-bound on HBM state traffic — measured and attacked in §Perf; the
Pallas `selective_scan` kernel keeps h resident in VMEM (the motif-local
datapath) and is the optimized path on real TPUs.

Mamba-2 (zamba2): scalar-per-head decay ⇒ chunked SSD with dense matmuls
(intra-chunk attention-like term + inter-chunk recurrence), MXU-friendly.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.layers import Spec


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------


def dt_rank(cfg) -> int:
    return max(cfg.d_model // 16, 1)


def _chunk_len(chunk: int, T: int) -> int:
    """Largest divisor of T not exceeding the configured chunk."""
    q = min(chunk, T)
    while T % q:
        q -= 1
    return q


def mamba1_param_spec(cfg) -> Dict[str, Spec]:
    D, Di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    R = dt_rank(cfg)
    return {
        "in_proj": Spec((D, 2 * Di), ("embed", "mlp")),
        "conv_w": Spec((Di, cfg.d_conv), ("mlp", "conv")),
        "conv_b": Spec((Di,), ("mlp",), init="zeros"),
        "x_proj": Spec((Di, R + 2 * N), ("mlp", None)),
        "dt_proj": Spec((R, Di), (None, "mlp")),
        "dt_bias": Spec((Di,), ("mlp",), jnp.float32, init="ssm_dt"),
        "A_log": Spec((Di, N), ("mlp", "state"), jnp.float32, init="ssm_a"),
        "Dskip": Spec((Di,), ("mlp",), jnp.float32, init="ones"),
        "out_proj": Spec((Di, D), ("mlp", "embed")),
        "ln": Spec((D,), ("embed",), init="ones"),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array, state=None):
    """Depthwise causal conv along T. x: (B, T, C); w: (C, K).

    ``state``: (B, K-1, C) left-context for decode/prefill continuation.
    Returns (y, new_state).
    """
    B, T, C = x.shape
    K = w.shape[1]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, T+K-1, C)
    y = jnp.zeros((B, T, C), jnp.float32)
    for k in range(K):
        y = y + xp[:, k : k + T].astype(jnp.float32) * w[:, k].astype(jnp.float32)
    new_state = xp[:, -(K - 1) :] if K > 1 else state
    return (y + b.astype(jnp.float32)).astype(x.dtype), new_state


def _mamba1_scan(dt, Bm, Cm, xs, A, h0):
    """Sequential selective scan over one chunk.

    dt: (B,Q,Di) fp32; Bm/Cm: (B,Q,N) fp32; xs: (B,Q,Di); A: (Di,N) fp32;
    h0: (B,Di,N) fp32. Returns (y (B,Q,Di) fp32, hQ).
    """

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp  # (B,Di),(B,N),(B,N),(B,Di)
        dA = jnp.exp(dt_t[:, :, None] * A[None])  # (B,Di,N)
        dBx = (dt_t * x_t.astype(jnp.float32))[:, :, None] * b_t[:, None, :]
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xsw = (
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(Bm, 1, 0),
        jnp.moveaxis(Cm, 1, 0),
        jnp.moveaxis(xs, 1, 0),
    )
    h, ys = lax.scan(step, h0, xsw)
    return jnp.moveaxis(ys, 0, 1), h


def mamba1_block(cfg, w, x: jax.Array, cache: Dict = None):
    """x: (B, T, D) -> (out, new_cache). cache: {'conv', 'h'} or None."""
    B, T, D = x.shape
    Di, N = cfg.d_inner, cfg.ssm_state
    R = dt_rank(cfg)
    xz = x @ w["in_proj"]
    xs, z = xz[..., :Di], xz[..., Di:]
    conv_state = cache["conv"] if cache is not None else None
    xs, new_conv = _causal_conv(xs, w["conv_w"], w["conv_b"], conv_state)
    xs = jax.nn.silu(xs)

    proj = xs @ w["x_proj"]  # (B,T,R+2N)
    dt = jax.nn.softplus(
        proj[..., :R].astype(jnp.float32) @ w["dt_proj"].astype(jnp.float32)
        + w["dt_bias"]
    )  # (B,T,Di)
    Bm = proj[..., R : R + N].astype(jnp.float32)
    Cm = proj[..., R + N :].astype(jnp.float32)
    A = -jnp.exp(w["A_log"])  # (Di,N)

    h0 = (
        cache["h"]
        if cache is not None
        else jnp.zeros((B, Di, N), jnp.float32)
    )
    Q = _chunk_len(cfg.ssm_chunk, T)

    def chunk_body(h, inp):
        dtc, bc, cc, xc = inp
        y, h = _mamba1_scan(dtc, bc, cc, xc, A, h)
        return h, y

    def reshape_chunks(t):
        return jnp.moveaxis(t.reshape(B, T // Q, Q, t.shape[-1]), 1, 0)

    body = jax.checkpoint(chunk_body)
    hT, ys = lax.scan(
        body, h0, (reshape_chunks(dt), reshape_chunks(Bm), reshape_chunks(Cm), reshape_chunks(xs))
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, Di)
    y = y + xs.astype(jnp.float32) * w["Dskip"]
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = y @ w["out_proj"]
    new_cache = {"conv": new_conv, "h": hT} if cache is not None else None
    return out, new_cache


def mamba1_decode(cfg, w, x: jax.Array, cache: Dict):
    """Single-token step. x: (B, 1, D)."""
    out, new_cache = mamba1_block(cfg, w, x, cache)
    return out, new_cache


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------


def mamba2_param_spec(cfg) -> Dict[str, Spec]:
    D, Di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H = cfg.n_ssm_heads
    return {
        "wz": Spec((D, Di), ("embed", "mlp")),
        "wx": Spec((D, Di), ("embed", "mlp")),
        "wB": Spec((D, N), ("embed", None)),
        "wC": Spec((D, N), ("embed", None)),
        "wdt": Spec((D, H), ("embed", None)),
        "conv_w": Spec((Di, cfg.d_conv), ("mlp", "conv")),
        "conv_b": Spec((Di,), ("mlp",), init="zeros"),
        "dt_bias": Spec((H,), (None,), jnp.float32, init="ssm_dt"),
        "A_log": Spec((H,), (None,), jnp.float32, init="ssm_a"),
        "Dskip": Spec((H,), (None,), jnp.float32, init="ones"),
        "norm": Spec((Di,), ("mlp",), init="ones"),
        "out_proj": Spec((Di, D), ("mlp", "embed")),
        "ln": Spec((D,), ("embed",), init="ones"),
    }


def mamba2_block(cfg, w, x: jax.Array, cache: Dict = None):
    """Chunked SSD. x: (B, T, D) -> (out, new_cache)."""
    B, T, D = x.shape
    Di, N = cfg.d_inner, cfg.ssm_state
    H = cfg.n_ssm_heads
    P = Di // H
    z = x @ w["wz"]
    xs = x @ w["wx"]
    conv_state = cache["conv"] if cache is not None else None
    xs, new_conv = _causal_conv(xs, w["conv_w"], w["conv_b"], conv_state)
    xs = jax.nn.silu(xs)
    Bm = (x @ w["wB"]).astype(jnp.float32)  # (B,T,N)
    Cm = (x @ w["wC"]).astype(jnp.float32)
    dt = jax.nn.softplus((x @ w["wdt"]).astype(jnp.float32) + w["dt_bias"])  # (B,T,H)
    A = -jnp.exp(w["A_log"])  # (H,)
    la = dt * A  # (B,T,H) log-decay per step

    xh = xs.reshape(B, T, H, P)
    Q = _chunk_len(cfg.ssm_chunk, T)
    nC = T // Q

    def to_chunks(t):  # (B,T,...) -> (nC, B, Q, ...)
        return jnp.moveaxis(t.reshape((B, nC, Q) + t.shape[2:]), 1, 0)

    h0 = (
        cache["h"].astype(jnp.float32)
        if cache is not None
        else jnp.zeros((B, H, P, N), jnp.float32)
    )

    def chunk(h, inp):
        lac, bc, cc, xc = inp  # (B,Q,H),(B,Q,N),(B,Q,N),(B,Q,H,P)
        cum = jnp.cumsum(lac, axis=1)  # (B,Q,H)
        # intra-chunk (attention-like, causal)
        Lmat = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Q,Q,H) t,s
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        Lmat = jnp.where(mask[None, :, :, None], jnp.exp(Lmat), 0.0)
        scores = jnp.einsum("btn,bsn->bts", cc, bc)[:, :, :, None] * Lmat  # (B,Q,Q,H)
        y_intra = jnp.einsum("btsh,bshp->bthp", scores, xc.astype(jnp.float32))
        # inter-chunk (carry-in state)
        decay_t = jnp.exp(cum)  # (B,Q,H)
        y_inter = jnp.einsum("btn,bhpn->bthp", cc, h) * decay_t[..., None]
        # state update: h' = total_decay * h + sum_s decay(Q..s) B_s x_s
        tot = jnp.exp(cum[:, -1])  # (B,H)
        dec_from = jnp.exp(cum[:, -1:, :] - cum)  # (B,Q,H) decay from s to end
        hb = jnp.einsum("bsh,bsn,bshp->bhpn", dec_from, bc, xc.astype(jnp.float32))
        h = tot[:, :, None, None] * h + hb
        return h, y_intra + y_inter

    body = jax.checkpoint(chunk)
    hT, ys = lax.scan(body, h0, (to_chunks(la), to_chunks(Bm), to_chunks(Cm), to_chunks(xh)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, T, H, P)
    y = y + xh.astype(jnp.float32) * w["Dskip"][None, None, :, None]
    y = y.reshape(B, T, Di).astype(x.dtype)
    y = L.rms_norm(y * jax.nn.silu(z), w["norm"])
    out = y @ w["out_proj"]
    new_cache = {"conv": new_conv, "h": hT} if cache is not None else None
    return out, new_cache


# ---------------------------------------------------------------------------
# Falcon-Mamba LM (pure Mamba-1 stack)
# ---------------------------------------------------------------------------


def param_spec(cfg) -> Dict[str, Spec]:
    return {
        **L.embed_param_spec(cfg),
        "layers": L.stack_layers(mamba1_param_spec(cfg), cfg.n_layers),
        "ln_f": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def forward(cfg, params, batch) -> jax.Array:
    x = L.embed_lookup(params["emb"], batch["tokens"])

    def block(xx, ww):
        h, _ = mamba1_block(cfg, ww, L.rms_norm(xx, ww["ln"]))
        return xx + h, None

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, _ = L.scan_layers(cfg, block, x, params["layers"])
    return L.rms_norm(x, params["ln_f"])


def loss_fn(cfg, params, batch):
    h = forward(cfg, params, batch)
    nll = L.chunked_xent(h, params["emb"], batch["labels"], cfg.logits_chunk)
    return nll, {"loss": nll}


def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    Di, N, K = cfg.d_inner, cfg.ssm_state, cfg.d_conv
    return {
        "conv": Spec((cfg.n_layers, batch, K - 1, Di), ("layers", "batch", None, "mlp")),
        "h": Spec((cfg.n_layers, batch, Di, N), ("layers", "batch", "mlp", "state"), jnp.float32),
        "length": Spec((batch,), ("batch",), jnp.int32),
    }


def prefill(cfg, params, batch):
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.embed_lookup(params["emb"], tokens)

    def block(xx, ww):
        zero = {
            "conv": jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), xx.dtype),
            "h": jnp.zeros((B, cfg.d_inner, cfg.ssm_state), jnp.float32),
        }
        h, c = mamba1_block(cfg, ww, L.rms_norm(xx, ww["ln"]), zero)
        return xx + h, c

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    x, caches = L.scan_layers(cfg, block, x, params["layers"])
    x = L.rms_norm(x, params["ln_f"])
    logits = (x[:, -1:] @ params["emb"].T).astype(jnp.float32)
    cache = {"conv": caches["conv"], "h": caches["h"], "length": jnp.full((B,), T, jnp.int32)}
    return cache, logits


def decode_step(cfg, params, cache, tokens):
    B = tokens.shape[0]
    x = L.embed_lookup(params["emb"], tokens)  # (B,1,D)

    def block(xx, scan_in):
        ww, conv, h = scan_in
        out, nc = mamba1_decode(cfg, ww, L.rms_norm(xx, ww["ln"]), {"conv": conv, "h": h})
        return xx + out, (nc["conv"], nc["h"])

    x, (convs, hs) = L.scan_layers(cfg, block, x, (params["layers"], cache["conv"], cache["h"]))
    x = L.rms_norm(x, params["ln_f"])
    logits = (x @ params["emb"].T).astype(jnp.float32)
    return {"conv": convs, "h": hs, "length": cache["length"] + 1}, logits
