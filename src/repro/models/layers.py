"""Shared model building blocks (pure JAX, shardable under pjit).

Conventions
-----------
* Params are nested dicts of arrays. Every model module exposes
  ``param_spec(cfg)`` returning a matching nested dict of :class:`Spec`
  (shape, dtype, logical axes) — so the launcher can build
  ``ShapeDtypeStruct`` trees and ``NamedSharding`` trees without ever
  allocating memory (the multi-pod dry-run requirement).
* Logical axis names (mapped to mesh axes in ``repro.parallel.sharding``):
  ``vocab, embed, mlp, heads, kv_heads, expert, layers, batch, seq,
  cache_seq, state, conv, dt``.
* Attention uses a *banded* blockwise (flash-style) formulation: the causal
  band is walked diagonal-by-diagonal so HLO FLOPs ≈ T²/2 (vs T² for the
  naive masked path, kept as ``attn_impl='naive'`` for the §Perf baseline).
  Sliding-window attention skips diagonals beyond the window entirely
  (sub-quadratic).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"  # normal | zeros | ones | ssm_a | ssm_dt

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def spec_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=lambda x: isinstance(x, Spec))


def stack_layers(spec_tree, n: int):
    """``spec_tree`` for ``n`` layers stacked on a leading ``layers`` axis."""
    return spec_map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init), spec_tree
    )


def shapes_of(tree):
    return spec_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def axes_of(tree):
    return spec_map(lambda s: s.axes, tree)


def init_of(tree, rng: jax.Array):
    """Materialize real params (smoke tests / the 100M example only)."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, Spec)
    )
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, s in zip(keys, leaves):
        if s.init == "zeros":
            v = jnp.zeros(s.shape, s.dtype)
        elif s.init == "ones":
            v = jnp.ones(s.shape, s.dtype)
        elif s.init == "ssm_a":  # -log-uniform init for A_log
            n = s.shape[-1]
            a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), s.shape[:-1] + (1,))
            v = jnp.log(a).astype(s.dtype)
        elif s.init == "ssm_dt":
            v = jnp.full(s.shape, math.log(math.e**0.01 - 1.0), s.dtype)  # softplus^-1(0.01)
        else:
            fan_in = s.shape[0] if len(s.shape) > 1 else max(s.shape[-1], 1)
            v = (jax.random.normal(key, s.shape, jnp.float32) / math.sqrt(fan_in)).astype(s.dtype)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(dt) * scale.astype(dt)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------


def _inv_freq(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(
    x: jax.Array,  # (B, T, H, hd)
    positions: jax.Array,  # (B, T) int32   or (B, 3, T) for m_rope
    theta: float,
    m_rope_sections: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    hd = x.shape[-1]
    half = hd // 2
    inv = _inv_freq(hd, theta)  # (half,)
    if m_rope_sections is not None:
        st, sh, sw = m_rope_sections
        assert st + sh + sw == half, (m_rope_sections, half)
        # section s of the frequency spectrum reads position axis s (t/h/w)
        sec = jnp.concatenate(
            [jnp.zeros(st, jnp.int32), jnp.ones(sh, jnp.int32), 2 * jnp.ones(sw, jnp.int32)]
        )
        pos = positions.astype(jnp.float32)[:, sec, :]  # (B, half, T)
        ang = jnp.einsum("bft,f->btf", pos, inv)  # (B, T, half)
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv  # (B, T, half)
    cos = jnp.cos(ang)[:, :, None, :]  # (B, T, 1, half)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (blockwise "banded flash" in pure jnp)
# ---------------------------------------------------------------------------


def _attn_block(q, k, v, scale, bias):
    """One (q-block, kv-block) tile. q: (B,Tq,Hkv,G,hd); k/v: (B,Tk,Hkv,hd)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # (B,H,G,Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, l1 * a1 + l2 * a2, o1 * a1[..., None] + o2 * a2[..., None]


def banded_attention(
    q: jax.Array,  # (B, T, Hq, hd)
    k: jax.Array,  # (B, T, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unbounded
    chunk: int = 1024,
) -> jax.Array:
    """Blockwise attention walking the causal band diagonal-by-diagonal.

    FLOPs scale with the number of *visited* (q-block, kv-block) tiles:
    T²/2 for causal, T·window for sliding-window — the off-band tiles are
    never materialized (Plaid's "don't provision communication you don't
    use", applied to the attention score matrix).
    """
    B, T, Hq, hd = q.shape
    Hkv, dv = k.shape[2], v.shape[-1]  # values may be narrower than q and k (MLA)
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n = T // chunk
    qg = q.reshape(B, T, Hkv, G, hd)

    NEG = jnp.float32(-1e30)
    m = jnp.full((B, Hkv, G, T), NEG)
    l = jnp.zeros((B, Hkv, G, T), jnp.float32)
    o = jnp.zeros((B, Hkv, G, T, dv), jnp.float32)

    idx = jnp.arange(chunk)
    max_diag = n
    if window:
        max_diag = min(n, window // chunk + 2)

    for d in range(max_diag):
        nb = n - d  # blocks on this diagonal
        qs = qg[:, d * chunk :].reshape(B, nb, chunk, Hkv, G, hd)
        ks = k[:, : nb * chunk].reshape(B, nb, chunk, Hkv, hd)
        vs = v[:, : nb * chunk].reshape(B, nb, chunk, Hkv, dv)
        # absolute positions inside the tile
        qpos = (jnp.arange(nb)[:, None] + d) * chunk + idx[None, :]  # (nb, chunk)
        kpos = jnp.arange(nb)[:, None] * chunk + idx[None, :]
        bias = jnp.zeros((nb, 1, 1, chunk, chunk), jnp.float32)
        if causal and d == 0:
            bias = jnp.where(qpos[:, :, None] >= kpos[:, None, :], 0.0, NEG)[:, None, None]
        if window:
            bias = bias + jnp.where(
                (qpos[:, :, None] - kpos[:, None, :]) < window, 0.0, NEG
            )[:, None, None]
        bm, bl, bo = jax.vmap(
            lambda qq, kk, vv, bb: _attn_block(qq, kk, vv, scale, bb),
            in_axes=(1, 1, 1, 0),
            out_axes=1,
        )(qs, ks, vs, bias)
        # bm/bl: (B, nb, Hkv, G, chunk); bo: (B, nb, Hkv, G, chunk, hd)
        bm = jnp.moveaxis(bm, 1, 3).reshape(B, Hkv, G, nb * chunk)
        bl = jnp.moveaxis(bl, 1, 3).reshape(B, Hkv, G, nb * chunk)
        bo = jnp.moveaxis(bo, 1, 3).reshape(B, Hkv, G, nb * chunk, dv)
        sl = slice(d * chunk, None)
        m2, l2, o2 = _merge(m[..., sl], l[..., sl], o[..., sl, :], bm, bl, bo)
        m = m.at[..., sl].set(m2)
        l = l.at[..., sl].set(l2)
        o = o.at[..., sl, :].set(o2)

    out = o / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 3, 1).reshape(B, T, Hq, dv)
    return out.astype(q.dtype)


def naive_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal=True, window: int = 0
) -> jax.Array:
    """Full masked attention — the unoptimized §Perf baseline path."""
    B, T, Hq, hd = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, T, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    qpos = jnp.arange(T)[:, None] + (S - T)
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, Hq, hd)
    k_cache: jax.Array,  # (B, S, Hkv, hd)
    v_cache: jax.Array,
    valid: jax.Array,  # (B, S) bool — which cache slots are live
) -> jax.Array:
    """One query position over a whole cache that holds no new row: the
    static encoder keys and values of cross-attention, and the reference
    that ``decode_attention_row`` is tested against."""
    B, _, Hq, hd = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32), k_cache.astype(jnp.float32))
    s = s / math.sqrt(hd)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def decode_attention_row(
    q: jax.Array,  # (B, 1, Hq, hd)
    k_cache: jax.Array,  # (B, S, Hkv*hd), the layer's cache before the step
    v_cache: jax.Array,
    valid: jax.Array,  # (B, S) bool — which cached slots this step attends
    k: jax.Array,  # (B, 1, Hkv, hd), the new position's
    v: jax.Array,
) -> jax.Array:
    """Attention of a new position over a cache that does not hold it
    yet: the cached slots in ``valid`` and the new ``k``, ``v`` (which every
    query attends) in one softmax, in float32.  The cache is only read, so
    the caller writes the new row once, after every layer has run.

    Both products run over the cache as it is stored, each row ``Hkv*hd``
    wide: a query is spread over the whole row, zero outside its KV head,
    and each query keeps its own head's block of the weighted values.  So
    the compiler reads the cache in place instead of laying each head out
    apart; the products outside a query's head are by exact zeros.
    """
    B, _, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    S = k_cache.shape[1]
    own = (jnp.arange(Hkv)[:, None] == jnp.arange(Hkv))[None, :, None, :, None]
    qg = q.reshape(B, Hkv, G, 1, hd).astype(jnp.float32)
    q_row = jnp.where(own, qg, 0.0).reshape(B, Hq, Hkv * hd)
    s_old = jnp.einsum("bqc,bsc->bqs", q_row, k_cache.astype(jnp.float32))
    s_old = jnp.where(valid[:, None, None, :], s_old.reshape(B, Hkv, G, S) / math.sqrt(hd), -1e30)
    s_new = jnp.einsum("bhgd,bhd->bhg", qg[:, :, :, 0], k.reshape(B, Hkv, hd).astype(jnp.float32))
    p = jax.nn.softmax(jnp.concatenate([s_old, s_new[..., None] / math.sqrt(hd)], -1), axis=-1)
    o_row = jnp.einsum("bqs,bsc->bqc", p[..., :-1].reshape(B, Hq, S), v_cache.astype(jnp.float32))
    out = jnp.where(own, o_row.reshape(B, Hkv, G, Hkv, hd), 0.0).sum(axis=3)
    out = out + p[..., -1:] * v.reshape(B, Hkv, 1, hd).astype(jnp.float32)
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def attention_param_spec(cfg) -> Dict[str, Spec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": Spec((d, cfg.n_heads * hd), ("embed", "heads")),
        "wk": Spec((d, cfg.n_kv_heads * hd), ("embed", "kv_heads")),
        "wv": Spec((d, cfg.n_kv_heads * hd), ("embed", "kv_heads")),
        "wo": Spec((cfg.n_heads * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = Spec((hd,), (None,), init="ones")
        p["k_norm"] = Spec((hd,), (None,), init="ones")
    return p


def attention_qkv(cfg, w, x, positions):
    """Projections + qk-norm + RoPE. Returns q (B,T,Hq,hd), k, v (B,T,Hkv,hd)."""
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ w["wq"]).reshape(B, T, cfg.n_heads, hd)
    k = (x @ w["wk"]).reshape(B, T, cfg.n_kv_heads, hd)
    v = (x @ w["wv"]).reshape(B, T, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"])
        k = rms_norm(k, w["k_norm"])
    sections = cfg.m_rope_sections if cfg.m_rope else None
    q = apply_rope(q, positions, cfg.rope_theta, sections)
    k = apply_rope(k, positions, cfg.rope_theta, sections)
    return q, k, v


def attention_layer(
    cfg,
    w,
    x,
    positions,
    *,
    causal=True,
    attn_impl="banded",
    cross_x: Optional[jax.Array] = None,
):
    """Self- or cross-attention over a full sequence (train / prefill).

    ``cross_x``: encoder hidden states — k/v are projected from them (no
    RoPE), attention becomes bidirectional over the encoder axis.
    Returns (out, (k, v)) so prefill can build the cache.
    """
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    if cross_x is None:
        q, k, v = attention_qkv(cfg, w, x, positions)
    else:
        q = (x @ w["wq"]).reshape(B, T, cfg.n_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(q, w["q_norm"])
        q = apply_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections if cfg.m_rope else None)
        S = cross_x.shape[1]
        k = (cross_x @ w["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = (cross_x @ w["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            k = rms_norm(k, w["k_norm"])
        causal = False
    if attn_impl == "banded" and causal:
        o = banded_attention(
            q, k, v, causal=causal, window=cfg.sliding_window, chunk=min(cfg.attn_chunk, T)
        )
    else:
        # non-causal (encoder / cross) has no lower band to exploit
        o = naive_attention(q, k, v, causal=causal, window=cfg.sliding_window)
    out = o.reshape(B, T, -1) @ w["wo"]
    return out, (k, v)


def decode_attention_layer(cfg, w, x, positions, k_cache, v_cache, i, valid):
    """Self-attention of one new position per sequence (decode).

    ``x`` (B, 1, D), already normed; ``k_cache``/``v_cache`` (n, B, S,
    Hkv*hd), stacked caches as they were before the step, of which layer
    (or site) ``i`` is read in place; ``valid`` (B, S), the cached slots
    the step attends.  Returns (out, (k, v)): the new position's keys and
    values (B, 1, Hkv, hd), for the caller to write after every layer has
    run.
    """
    B = x.shape[0]
    q, k, v = attention_qkv(cfg, w, x, positions)
    o = decode_attention_row(q, k_cache[i], v_cache[i], valid, k, v)
    return o.reshape(B, 1, -1) @ w["wo"], (k, v)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_param_spec(cfg, d_ff=None) -> Dict[str, Spec]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w1": Spec((d, f), ("embed", "mlp")),
        "w3": Spec((d, f), ("embed", "mlp")),
        "w2": Spec((f, d), ("mlp", "embed")),
    }


def swiglu(w, x):
    """Fan-in motif: two projections meet at an elementwise gate."""
    h = jax.nn.silu(x @ w["w1"]) * (x @ w["w3"])
    return h @ w["w2"]


# ---------------------------------------------------------------------------
# Embedding + chunked cross-entropy
# ---------------------------------------------------------------------------


def embed_param_spec(cfg) -> Dict[str, Spec]:
    return {"emb": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))}


def embed_lookup(emb, tokens):
    return jnp.take(emb, tokens, axis=0)


def output_head(cfg, params) -> jax.Array:
    """The (vocab, d_model) output head: the embedding when tied."""
    return params["emb"] if cfg.tie_embeddings else params["head"]


def chunked_xent(hidden: jax.Array, emb: jax.Array, labels: jax.Array, chunk: int) -> jax.Array:
    """Next-token cross-entropy without materializing (tokens, vocab) fp32.

    Scans token chunks; each chunk's logits live only inside the (rematted)
    scan body — the fan-out of hidden→logits→(lse, label-logit) collapses
    back to two scalars per token (a unicast motif at the loss level).
    """
    B, T, D = hidden.shape
    h = hidden.reshape(B * T, D)
    y = labels.reshape(B * T)
    n = h.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, ((0, pad),))
    hc = h.reshape(-1, chunk, D)
    yc = y.reshape(-1, chunk)

    @jax.checkpoint
    def body(carry, xs):
        hh, yy = xs
        logits = (hh @ emb.T).astype(jnp.float32)  # (chunk, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yy[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = lax.scan(body, jnp.float32(0.0), (hc, yc))
    return total / n


def remat_policy(name: str):
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    return None  # 'full' -> no remat wrapper applied


def scan_layers(cfg, body, x, stacked):
    """``lax.scan`` over stacked layer weights, or an unrolled python loop
    when ``cfg.unroll_layers`` — the roofline harness compiles small unrolled
    models because XLA's cost_analysis counts a while-loop body once.
    """
    if not getattr(cfg, "unroll_layers", False):
        return lax.scan(body, x, stacked)
    n = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        wi = jax.tree.map(lambda t: t[i], stacked)
        x, y = body(x, wi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        ys = None
    return x, ys
