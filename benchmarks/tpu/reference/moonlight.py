"""Plain float32 reference of Moonlight-16B-A3B (the DeepSeek-V3 block) at
one chip's share of the experts, layer by layer.

Follows Hugging Face's DeepSeek-V3 modelling (``DeepseekV3ForCausalLM``,
``q_lora_rank`` null, no rope scaling):

- pre-norm blocks, RMSNorm with ``rms_norm_eps``;
- multi-head latent attention in its published, expanded form: each
  head's query is ``x @ q_proj`` (``qk_nope_head_dim`` plain features,
  ``qk_rope_head_dim`` rotated); ``x @ kv_a_proj_with_mqa`` gives the
  latent (``kv_lora_rank``, normalised by ``kv_a_layernorm``) and one
  rotated key part shared by the heads; ``kv_b_proj`` expands the latent
  into each head's plain key and value; causal softmax scaled by
  ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``; ``o_proj``;
- the first ``first_k_dense_replace`` layers: a SwiGLU MLP of
  ``intermediate_size``;
- the others: sigmoid router scores over all ``router_experts``; the
  top ``num_experts_per_tok`` of the scores plus the correction bias
  (``noaux_tc`` with one group) are selected; their weights are the
  scores alone, normalised (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``; routed experts are SwiGLUs of
  ``moe_intermediate_size``; the shared experts one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``;
- a final RMSNorm and an output head of its own
  (``tie_word_embeddings`` false).

Departures, each also the program's:

- one chip's share: only the ``n_routed_experts`` experts from
  ``first_held_expert`` exist here.  The router keeps its published width
  and selection; a selected expert that is absent adds nothing.
- RoPE is the half-split rotation.  The published checkpoint's q_pe and
  k_pe columns are interleaved; with random weights that is a fixed
  permutation of the same weights.

It takes only the configuration file, the seed and the token ids; the
weights are made again here from the seed, one layer at a time, in the
layout below, and nothing of the program is imported.  All arithmetic is
float32 under ``jax.default_matmul_precision("highest")``; routing too.
Each routed expert here runs on every position, weighted by its routing
weight, which is zero where it was not selected.

``quant="float8_e4m3fn"`` is the precision control, as in ``qwen3.py``:
every matmul's two operands are rounded to that type.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights
from reference.qwen3 import Q_BLOCK, _mm, rms_norm, rope, served_gaps  # noqa: F401

#: the correction bias is uniform in [-BIAS, BIAS)
BIAS = 0.05


def held_experts(c: Dict) -> range:
    first = c["first_held_expert"]
    return range(first, first + c["n_routed_experts"])


def layout(c: Dict):
    """``(top, first-layer, MoE-layer, expert)`` leaves as ``path ->
    shape``, the paths those of the served tree.  A MoE layer's expert
    ``e`` holds ``layers/moe/w1/<e>`` and the rest; the served tree stacks
    the held experts in order."""
    D, V, H = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    r, nope, rope_d, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                           c["qk_rope_head_dim"], c["v_head_dim"])
    F, Fe = c["intermediate_size"], c["moe_intermediate_size"]
    Fs = c["n_shared_experts"] * Fe
    top = {"emb": (V, D), "head": (V, D), "ln_f": (D,)}
    attn = {"attn/wq": (D, H * (nope + rope_d)), "attn/wkv_a": (D, r + rope_d),
            "attn/kv_norm": (r,), "attn/wkv_b": (r, H * (nope + dv)),
            "attn/wo": (H * dv, D), "ln1": (D,), "ln2": (D,)}
    first = {**attn, "mlp/w1": (D, F), "mlp/w3": (D, F), "mlp/w2": (F, D)}
    moe = {**attn, "moe/router": (D, c["router_experts"]),
           "moe/router_bias": (c["router_experts"],),
           "moe/dense/w1": (D, Fs), "moe/dense/w3": (D, Fs),
           "moe/dense/w2": (Fs, D)}
    expert = {"moe/w1": (D, Fe), "moe/w3": (D, Fe), "moe/w2": (Fe, D)}
    return top, first, moe, expert


def value(words, path: str, shape: tuple, layer: int, hidden: int):
    """One leaf from the seed, in the dtype the program holds it in."""
    if path == "head":        # (vocab, hidden), drawn with fan-in hidden
        return weights.leaf(words, path, shape[::-1], layer, hidden).T
    if path.endswith("router_bias"):
        u = weights.leaf(words, path, shape, layer, hidden)   # [0.75, 1.25)
        return (u.astype(jnp.float32) - 1.0) * (4 * BIAS)
    if path.endswith("router"):
        return weights.leaf(words, path, shape, layer, hidden).astype(
            jnp.float32)
    return weights.leaf(words, path, shape, layer, hidden)


@partial(jax.jit, static_argnames=("c_items", "kind"))
def _layer_params(words, i, c_items, kind):
    c = dict(c_items)
    _, first, moe, expert = layout(c)
    D = c["hidden_size"]
    if kind == "first":
        return {p: value(words, "first_layers/" + p, s, i, D).astype(
            jnp.float32) for p, s in first.items()}
    out = {p: value(words, "layers/" + p, s, i, D).astype(jnp.float32)
           for p, s in moe.items()}
    for p, s in expert.items():
        out[p] = jnp.stack([value(words, f"layers/{p}/{e}", s, i, D)
                            for e in held_experts(c)]).astype(jnp.float32)
    return out


@partial(jax.jit, static_argnames=("c_items",))
def _top_params(words, c_items):
    c = dict(c_items)
    top = layout(c)[0]
    return {p: value(words, p, s, -1, c["hidden_size"]).astype(jnp.float32)
            for p, s in top.items()}


def _swiglu(x, w, prefix, quant):
    gate = _mm(x, w[prefix + "w1"], quant)
    up = _mm(x, w[prefix + "w3"], quant)
    return _mm(jax.nn.silu(gate) * up, w[prefix + "w2"], quant)


def _attention(x, w, c, quant):
    """Latent attention in the expanded form over one sequence (S, D)."""
    S, _ = x.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope_d, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    q = _mm(x, w["attn/wq"], quant).reshape(S, H, nope + rope_d)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    ckv = _mm(x, w["attn/wkv_a"], quant)
    latent = rms_norm(ckv[:, :r], w["attn/kv_norm"], eps)
    k_pe = rope(ckv[:, None, r:], pos, theta)                  # (S, 1, rope)
    kv = _mm(latent, w["attn/wkv_b"], quant).reshape(S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rope_d))], -1)
    v = kv[..., nope:]
    outs = []
    for lo in range(0, S, Q_BLOCK):            # causal attention by blocks
        hi = min(S, lo + Q_BLOCK)
        s = _mm(q[lo:hi], k[:hi], quant, "qhd,khd->hqk") / np.sqrt(
            nope + rope_d)
        mask = pos[lo:hi, None] >= pos[None, :hi]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        outs.append(_mm(p, v[:hi], quant, "hqk,khd->qhd"))
    o = jnp.concatenate(outs, 0).reshape(S, H * dv)
    return _mm(o, w["attn/wo"], quant)


def _experts(h, w, c, quant):
    """This share's routed experts and the shared experts over (S, D)."""
    K = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(h, w["moe/router"], quant))      # (S, E)
    _, chosen = jax.lax.top_k(scores + w["moe/router_bias"], K)
    picked = jnp.take_along_axis(scores, chosen, -1)
    picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * c["routed_scaling_factor"]
    gate = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked)      # (S, E)
    out = _swiglu(h, w, "moe/dense/", quant)
    for n, e in enumerate(held_experts(c)):
        we = {k: w["moe/" + k][n] for k in ("w1", "w3", "w2")}
        out = out + gate[:, e:e + 1] * _swiglu(h, we, "", quant)
    return out


@partial(jax.jit, static_argnames=("c_items", "quant", "kind"))
def _layer(x, w, c_items, quant, kind):
    """One decoder layer over one sequence ``x`` (S, D)."""
    c = dict(c_items)
    eps = c["rms_norm_eps"]
    x = x + _attention(rms_norm(x, w["ln1"], eps), w, c, quant)
    h = rms_norm(x, w["ln2"], eps)
    if kind == "first":
        return x + _swiglu(h, w, "mlp/", quant)
    return x + _experts(h, w, c, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, head, ln_f, eps, quant):
    return _mm(rms_norm(x, ln_f, eps), head, quant, "sd,vd->sv")


def logits(c: Dict, seed: int, seqs: Sequence[np.ndarray],
           positions: Sequence[int], quant: Optional[str] = None
           ) -> np.ndarray:
    """Logits ``(len(seqs), len(positions), vocab)`` of every sequence at
    the given positions (each predicts the token after it)."""
    c_items = weights._items(c)
    words = weights._words(seed)
    n_first = c["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        top = _top_params(words, c_items)
        xs = [top["emb"][jnp.asarray(s)] for s in seqs]
        for i in range(c["num_hidden_layers"]):
            kind, j = ("first", i) if i < n_first else ("moe", i - n_first)
            w = _layer_params(words, jnp.int32(j), c_items, kind)
            xs = [_layer(x, w, c_items, quant, kind) for x in xs]
            del w
        idx = jnp.asarray(np.asarray(positions))
        out = [np.asarray(_head(x[idx], top["head"], top["ln_f"],
                                c["rms_norm_eps"], quant)) for x in xs]
    return np.stack(out)
