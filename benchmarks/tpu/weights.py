"""Random weights of a dense decoder (Qwen3 layout), made from the seed.

Every leaf is named by its path in the served parameter tree
(``layers/attn/wq``), and layer ``i`` of a stacked leaf draws from its own
key, so the plain reference can make one layer at a time exactly as the
served model holds it.  Values come from raw random bits through exact
float operations and one rounding to bfloat16, so they are the same bits
in any program that makes them.

- matrices: uniform with standard deviation ``1 / sqrt(fan_in)`` (the
  embedding, which is also the output head, takes ``hidden_size``);
- norm scales: uniform in ``[0.75, 1.25)``, so that a scale left out
  shows in the logits.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

DTYPE = jnp.bfloat16


def layout(c: Dict) -> Tuple[Dict[str, tuple], Dict[str, tuple]]:
    """``(top-level leaves, per-layer leaves)`` as ``path -> shape``."""
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    top = {"emb": (V, D), "ln_f": (D,)}
    layer = {
        "attn/wq": (D, q), "attn/wk": (D, kv), "attn/wv": (D, kv),
        "attn/wo": (q, D), "attn/q_norm": (hd,), "attn/k_norm": (hd,),
        "ln1": (D,), "ln2": (D,),
        "mlp/w1": (D, F), "mlp/w3": (D, F), "mlp/w2": (F, D),
    }
    return top, layer


def _words(seed: int):
    """The seed as two uint32 words, handed to the jitted makers as
    arguments, so that one compiled program serves every seed."""
    return (jnp.uint32(seed & 0xFFFFFFFF),
            jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _items(c: Dict) -> tuple:
    """The configuration's numbers, hashable, as a static argument."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


def _key(words, path: str, layer):
    lo, hi = words
    k = jax.random.PRNGKey(lo)
    k = jax.random.fold_in(k, hi)
    k = jax.random.fold_in(k, zlib.crc32(path.encode()))
    return jax.random.fold_in(k, layer + 1)


def _unit(key, shape):
    """Uniform in [-0.5, 0.5): 23 random mantissa bits, exact arithmetic."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)
    return one_two - 1.5


def leaf(words, path: str, shape: tuple, layer=-1, hidden: int = 0):
    u = _unit(_key(words, path, layer), shape)
    if len(shape) == 1:                       # a norm scale
        v = 1.0 + u * 0.5
    else:
        fan_in = hidden if path == "emb" else shape[0]
        v = u * (math.sqrt(12.0) / math.sqrt(fan_in))
    return v.astype(DTYPE)


@partial(jax.jit, static_argnames=("c_items",))
def _make(words, c_items):
    c = dict(c_items)
    top, per_layer = layout(c)
    L, D = c["num_hidden_layers"], c["hidden_size"]
    out: Dict = {p: leaf(words, p, s, hidden=D) for p, s in top.items()}
    layers: Dict = {}
    for p, s in per_layer.items():
        stacked = jnp.stack([leaf(words, p, s, i, D) for i in range(L)])
        node = layers
        *parents, name = p.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[name] = stacked
    out["layers"] = layers
    return out


def make_params(c: Dict, seed: int):
    """The whole served tree (layers stacked on a leading axis), in one
    jitted call on the default device."""
    return _make(_words(seed), _items(c))


@partial(jax.jit, static_argnames=("c_items",))
def _layer(words, i, c_items):
    c = dict(c_items)
    _, per_layer = layout(c)
    return {p: leaf(words, p, s, i, c["hidden_size"]).astype(jnp.float32)
            for p, s in per_layer.items()}


def layer_params(c: Dict, seed: int, i: int) -> Dict[str, jax.Array]:
    """Layer ``i``'s leaves, flat by path, in float32."""
    return _layer(_words(seed), jnp.int32(i), _items(c))


@partial(jax.jit, static_argnames=("c_items",))
def _top(words, c_items):
    c = dict(c_items)
    top, _ = layout(c)
    return {p: leaf(words, p, s, hidden=c["hidden_size"]).astype(
        jnp.float32) for p, s in top.items()}


def top_params(c: Dict, seed: int) -> Dict[str, jax.Array]:
    return _top(_words(seed), _items(c))
