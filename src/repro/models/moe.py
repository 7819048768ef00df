"""Mixture-of-Experts decoder LM.

Covers Arctic (softmax top-2 experts beside a dense residual MLP), Granite
(softmax top-8) and the DeepSeek-V3 block of Moonlight (multi-head latent
attention, ``models/mla.py``; sigmoid routing with a correction bias used
for the selection only; shared experts; a leading dense layer; an output
head of its own).

The expert layer holds ``held_experts`` experts from
``first_held_expert`` (every expert when 0): it routes each token over all
``n_experts`` and returns only its own experts' part of the result, which
is one chip's share under expert parallelism.  No token is dropped: every
(token, expert) route is sorted by expert and the experts run as one
grouped matmul (``lax.ragged_dot``) over the routes that landed on them,
in training and serving alike.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import mla
from repro.models.layers import Spec
from repro.serve import kvcache as kv

#: what the layer counts per MoE layer; carried in the serving cache under
#: ``counters`` and returned by ``serve.loop.generate``
COUNTERS = ("moe_routed", "moe_rows")
#: the held experts' weight stacks, read by the grouped matmuls
EXPERT_WEIGHTS = ("w1", "w3", "w2")


def held_range(cfg) -> Tuple[int, int]:
    """``(first, count)`` of the experts this layer holds."""
    return cfg.first_held_expert, cfg.held_experts or cfg.n_experts


def n_moe_layers(cfg) -> int:
    return cfg.n_layers - cfg.n_dense_layers


def moe_param_spec(cfg) -> Dict[str, Spec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    g = held_range(cfg)[1]
    p = {
        "router": Spec((d, e), ("embed", None), jnp.float32),
        "w1": Spec((g, d, f), ("expert", "embed", "mlp")),
        "w3": Spec((g, d, f), ("expert", "embed", "mlp")),
        "w2": Spec((g, f, d), ("expert", "mlp", "embed")),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = Spec((e,), (None,), jnp.float32, init="zeros")
    if cfg.moe_dense_ff:
        p["dense"] = L.mlp_param_spec(cfg, cfg.moe_dense_ff)
    return p


def _layer_spec(cfg, ffn_key: str, ffn: Dict[str, Spec]) -> Dict[str, Spec]:
    return {
        "attn": mla.param_spec(cfg) if cfg.kv_lora_rank else L.attention_param_spec(cfg),
        ffn_key: ffn,
        "ln1": Spec((cfg.d_model,), ("embed",), init="ones"),
        "ln2": Spec((cfg.d_model,), ("embed",), init="ones"),
    }


def param_spec(cfg) -> Dict[str, Spec]:
    p = {
        **L.embed_param_spec(cfg),
        "layers": L.stack_layers(_layer_spec(cfg, "moe", moe_param_spec(cfg)), n_moe_layers(cfg)),
        "ln_f": Spec((cfg.d_model,), ("embed",), init="ones"),
    }
    if cfg.n_dense_layers:
        mlp = L.mlp_param_spec(cfg, cfg.dense_ff)
        p["first_layers"] = L.stack_layers(_layer_spec(cfg, "mlp", mlp), cfg.n_dense_layers)
    if not cfg.tie_embeddings:
        p["head"] = Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))
    return p


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------


def route(cfg, w, xt: jax.Array):
    """Router over every expert, in float32: ``(weights (n, K), experts
    (n, K), aux)``.  Softmax: the top-k probabilities.  Sigmoid: the top-k
    of the scores plus ``router_bias``, weighted by the scores alone.
    Either way the weights are normalised over the k and scaled by
    ``routed_scale``."""
    logits = xt.astype(jnp.float32) @ w["router"]
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_e = lax.top_k(scores + w["router_bias"], cfg.top_k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        probs = scores / jnp.sum(scores, -1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9) * cfg.routed_scale
    # load-balance auxiliary loss (Switch-style), for training
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], cfg.n_experts), axis=0)
    aux = cfg.n_experts * jnp.sum(density * jnp.mean(probs, axis=0))
    return top_w, top_e, aux


def moe_block(cfg, w, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, T, D) -> (this layer's experts' part plus the dense branch,
    aux loss, routes that landed on the held experts).

    The grouped matmul is handed all ``B*T*top_k`` routes, the held ones
    first and sorted by expert; its groups cover only the held ones.
    """
    B, T, Dm = x.shape
    K = cfg.top_k
    n = B * T
    first, G = held_range(cfg)
    xt = x.reshape(n, Dm)

    with jax.named_scope("route"):
        top_w, top_e, aux = route(cfg, w, xt)
        local = top_e.reshape(-1) - first
        held = (local >= 0) & (local < G)
        group = jnp.where(held, local, G)  # routes to absent experts sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
        rows = jnp.take(xt, order // K, axis=0)  # (n*K, D), by expert
    with jax.named_scope("experts"):
        h = jax.nn.silu(lax.ragged_dot(rows, w["w1"], sizes))
        h = h * lax.ragged_dot(rows, w["w3"], sizes)
        out = lax.ragged_dot(h, w["w2"], sizes)
    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(jnp.arange(n * K, dtype=order.dtype))
        y = jnp.take(out, back, axis=0).reshape(n, K, Dm)
        # rows past the groups are no route's result: select, never scale
        y = jnp.where(held.reshape(n, K, 1), y.astype(jnp.float32), 0.0)
        y = jnp.sum(y * top_w[..., None], axis=1)
    if cfg.moe_dense_ff:  # Arctic's residual MLP; DeepSeek's shared experts
        with jax.named_scope("shared"):
            y = y + L.swiglu(w["dense"], xt).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, T, Dm), aux, jnp.sum(held, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _norm(cfg, x, scale):
    return L.rms_norm(x, scale, cfg.rms_norm_eps)


def _attend(cfg, w, h, positions):
    """Attention over a whole sequence: ``(out, what the cache keeps)``,
    the latent under MLA, else ``(k, v)``."""
    if cfg.kv_lora_rank:
        with jax.named_scope("mla"):
            return mla.attention(cfg, w, h, positions)
    return L.attention_layer(cfg, w, h, positions, attn_impl=cfg.attn_impl)


def _ffn(cfg, w, x):
    """The block's second half: ``(x, aux, routed)``; a dense layer routes
    nothing."""
    h = _norm(cfg, x, w["ln2"])
    if "mlp" in w:
        return x + L.swiglu(w["mlp"], h), jnp.float32(0.0), jnp.int32(0)
    with jax.named_scope("moe"):
        m, aux, routed = moe_block(cfg, w["moe"], h)
    return x + m, aux, routed


def _stacks(params):
    """The layer stacks in order: the leading dense layers, then the MoE
    layers."""
    return [params[k] for k in ("first_layers", "layers") if k in params]


def _scan_serving(cfg, block, x, stack, *xs):
    """``scan_layers`` of ``block(x, (w, *xs))`` over one layer stack, as
    the serving steps run it.  The held experts' weights stay out of the
    scanned operands: each layer takes its own slice of them under
    ``moe/experts``, so the copy of it that the grouped matmul reads is
    counted with the experts and not with the loop."""
    if "moe" not in stack:
        return L.scan_layers(cfg, block, x, (stack, *xs))
    experts = {k: stack["moe"][k] for k in EXPERT_WEIGHTS}
    rest = {**stack, "moe": {k: v for k, v in stack["moe"].items() if k not in experts}}
    n = jax.tree.leaves(stack)[0].shape[0]

    def body(xx, scan_in):
        ww, j, *more = scan_in
        with jax.named_scope("moe"), jax.named_scope("experts"):
            own = {k: lax.dynamic_index_in_dim(v, j, keepdims=False) for k, v in experts.items()}
        return block(xx, ({**ww, "moe": {**ww["moe"], **own}}, *more))

    return L.scan_layers(cfg, body, x, (rest, jnp.arange(n), *xs))


def _positions(B, T):
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))


def forward(cfg, params, batch) -> Tuple[jax.Array, jax.Array]:
    x = L.embed_lookup(params["emb"], batch["tokens"])
    B, T = batch["tokens"].shape
    positions = _positions(B, T)

    def block(xx, ww):
        h, _ = _attend(cfg, ww["attn"], _norm(cfg, xx, ww["ln1"]), positions)
        xx, aux, _ = _ffn(cfg, ww, xx + h)
        return xx, aux

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    aux = []
    for stack in _stacks(params):
        x, a = L.scan_layers(cfg, block, x, stack)
        aux.append(a)
    return _norm(cfg, x, params["ln_f"]), jnp.mean(aux[-1])


def loss_fn(cfg, params, batch):
    h, aux = forward(cfg, params, batch)
    nll = L.chunked_xent(h, L.output_head(cfg, params), batch["labels"], cfg.logits_chunk)
    loss = nll + 0.01 * aux
    return loss, {"loss": loss, "nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def cache_spec(cfg, batch: int, seq_len: int) -> Dict[str, Spec]:
    counters = {k: Spec((n_moe_layers(cfg),), (None,), jnp.int32, init="zeros")
                for k in COUNTERS}
    latent = mla.latent_dim(cfg) if cfg.kv_lora_rank else 0
    return {**kv.spec(cfg, cfg.n_layers, batch, seq_len, latent=latent), "counters": counters}


def _counted(counters, routed, rows: int):
    return {"moe_routed": counters["moe_routed"] + routed,
            "moe_rows": counters["moe_rows"] + jnp.int32(rows)}


def _logits(cfg, params, x):
    x = _norm(cfg, x, params["ln_f"])
    return (x @ L.output_head(cfg, params).T).astype(jnp.float32)


def prefill(cfg, params, batch):
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.embed_lookup(params["emb"], tokens)
    positions = _positions(B, T)

    def block(xx, scan_in):
        (ww,) = scan_in
        h, kept = _attend(cfg, ww["attn"], _norm(cfg, xx, ww["ln1"]), positions)
        xx, _, routed = _ffn(cfg, ww, xx + h)
        return xx, (jax.tree.map(lambda t: kv.prompt_rows(cfg, t), kept), routed)

    policy = L.remat_policy(cfg.remat)
    if policy is not None:
        block = jax.checkpoint(block, policy=policy)
    kept, routed = [], None
    for stack in _stacks(params):
        x, (kk, routed) = _scan_serving(cfg, block, x, stack)
        kept.append(kk)
    kept = jax.tree.map(lambda *a: jnp.concatenate(a), *kept)
    zero = jnp.zeros((n_moe_layers(cfg),), jnp.int32)
    cache = {
        **kv.prompt_state(cfg, B, T),
        "counters": _counted({k: zero for k in COUNTERS}, routed, B * T * cfg.top_k),
    }
    if cfg.kv_lora_rank:
        cache["latent"] = kept
    else:
        cache["k"], cache["v"] = kept
    return cache, _logits(cfg, params, x[:, -1:])


def decode_step(cfg, params, cache, tokens):
    """One decode step.  Each layer reads its slice of the cache in place
    and returns the new position's row: the latent under MLA, else its
    keys and values."""
    B = tokens.shape[0]
    length = cache["length"]
    positions = length[:, None].astype(jnp.int32)
    step = kv.step(cache, cfg.sliding_window)
    x = L.embed_lookup(params["emb"], tokens)

    def block(xx, scan_in):
        ww, i = scan_in
        h = _norm(cfg, xx, ww["ln1"])
        if cfg.kv_lora_rank:
            with jax.named_scope("mla"):
                o, lat = mla.decode_attention(cfg, ww["attn"], h, positions,
                                              cache["latent"][i], step.mask)
            rows = {"latent": lat}
        else:
            o, (k, v) = L.decode_attention_layer(cfg, ww["attn"], h, positions,
                                                 cache["k"], cache["v"], i, step.mask)
            rows = {"k": kv.row(k, cache["k"]), "v": kv.row(v, cache["v"])}
        xx, _, routed = _ffn(cfg, ww, xx + o)
        return xx, (rows, routed)

    rows, first = [], 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        x, (r, routed) = _scan_serving(cfg, block, x, stack, jnp.arange(first, first + n))
        rows.append(r)
        first += n
    rows = jax.tree.map(lambda *a: jnp.concatenate(a), *rows)  # (layers, B, width)
    new_cache = {
        **kv.write_rows(cache, rows, step.slot),
        "pos": step.pos,
        "length": length + 1,
        "counters": _counted(cache["counters"], routed, B * cfg.top_k),
    }
    return new_cache, _logits(cfg, params, x)
