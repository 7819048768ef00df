"""Plain float32 reference of a Qwen3 dense decoder, layer by layer.

Follows the published Qwen3 description (Hugging Face ``Qwen3ForCausalLM``):
pre-norm blocks with RMSNorm, grouped-query attention whose queries and
keys are RMS-normalised per head before rotary embedding (``rope_theta``,
the half-split rotation), causal softmax, and a SwiGLU MLP; a final
RMSNorm and the output head.  The output head is the embedding, as the
configuration file states (``tie_word_embeddings``).

It takes only the configuration file, the seed and the token ids: the
weights are made again here from the seed (``weights.py``), one layer at a
time, and nothing of the program is imported.  All arithmetic is float32
under ``jax.default_matmul_precision("highest")``.

``quant="float8_e4m3fn"`` is the precision control: every matmul's two
operands are rounded to that type (each scaled by its own largest
magnitude), everything else as above.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights

Q_BLOCK = 512


def _round(x, quant):
    if quant is None:
        return x
    dt = jnp.dtype(quant)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / float(jnp.finfo(dt).max)
    return (x / scale).astype(dt).astype(jnp.float32) * scale


def _mm(a, b, quant, spec="...k,kn->...n"):
    return jnp.einsum(spec, _round(a, quant), _round(b, quant))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: (S, heads, hd); the half-split rotation of the published model."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("c_items", "quant"))
def _layer(x, w, c_items, quant):
    """One decoder layer over one sequence ``x`` (S, D)."""
    c = dict(c_items)
    S, _ = x.shape
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    h = rms_norm(x, w["ln1"], eps)
    q = _mm(h, w["attn/wq"], quant).reshape(S, H, hd)
    k = _mm(h, w["attn/wk"], quant).reshape(S, KV, hd)
    v = _mm(h, w["attn/wv"], quant).reshape(S, KV, hd)
    q = rope(rms_norm(q, w["attn/q_norm"], eps), pos, theta)
    k = rope(rms_norm(k, w["attn/k_norm"], eps), pos, theta)
    group = H // KV
    k = jnp.repeat(k, group, axis=1)          # query head h reads h // group
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for lo in range(0, S, Q_BLOCK):           # causal attention by blocks
        hi = min(S, lo + Q_BLOCK)
        s = _mm(q[lo:hi], k[:hi], quant, "qhd,khd->hqk") / np.sqrt(hd)
        mask = pos[lo:hi, None] >= pos[None, :hi]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(_mm(p, v[:hi], quant, "hqk,khd->qhd"))
    o = jnp.concatenate(outs, 0).reshape(S, H * hd)
    x = x + _mm(o, w["attn/wo"], quant)
    h = rms_norm(x, w["ln2"], eps)
    gate = _mm(h, w["mlp/w1"], quant)
    up = _mm(h, w["mlp/w3"], quant)
    return x + _mm(jax.nn.silu(gate) * up, w["mlp/w2"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, emb, ln_f, eps, quant):
    return _mm(rms_norm(x, ln_f, eps), emb, quant, "sd,vd->sv")


def logits(c: Dict, seed: int, seqs: Sequence[np.ndarray],
           positions: Sequence[int], quant: Optional[str] = None
           ) -> np.ndarray:
    """Logits ``(len(seqs), len(positions), vocab)`` of every sequence at
    the given positions (each predicts the token after it)."""
    c_items = tuple(sorted((k, v) for k, v in c.items()
                           if isinstance(v, (int, float))
                           and not isinstance(v, bool)))
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        top = weights.top_params(c, seed)
        xs = [top["emb"][jnp.asarray(s)] for s in seqs]
        for i in range(c["num_hidden_layers"]):
            w = weights.layer_params(c, seed, i)
            xs = [_layer(x, w, c_items, quant) for x in xs]
            del w
        idx = jnp.asarray(np.asarray(positions))
        out = [np.asarray(_head(x[idx], top["emb"], top["ln_f"], eps, quant))
               for x in xs]
    return np.stack(out)


def served_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best each served token's logit lies:
    ``ref`` (n, P, V) reference logits, ``tokens`` (n, P) served ids."""
    picked = np.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    return ref.max(-1) - picked
