"""Moonlight-16B-A3B's block (``models/moe.py``, ``models/mla.py``) against
its plain float32 reference (``benchmarks/tpu/reference/moonlight.py``) on
seeded random weights at a small size: hidden 64, 3 layers (1 dense), 8
experts of which 4 are held, top-2, 1 shared expert, latent 32, rope 8.

Tolerances.  ``F32_TOL``: the program runs in float32 here (its weights
cast up), so the two sides differ only in the order of their sums and in
the decode's absorbed form, a reassociation of the same products; at
these widths that stays below 1e-5, and 2e-4 leaves room for the routing
weights' float32 normalisation.  The reference's float8 control (its
matmul operands rounded to e4m3, three mantissa bits) lands about 1e-1
away, so it fails this tolerance by orders of magnitude (checked below).
The program in bfloat16 is not held to the float32 reference here: at 2
of 8 experts a rounding that flips one selection moves a logit by as much
as the float8 control does.  The served comparison on the chip, at the
published widths, is the benchmark's ``served_slot_gap`` and
``served_far_share``.
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "tpu"
if str(BENCH) not in sys.path:           # appended: keep the stdlib's names
    sys.path.append(str(BENCH))

import weights  # noqa: E402
from harness import load_module  # noqa: E402
from reference import moonlight as ref  # noqa: E402

from repro.models import moe, zoo  # noqa: E402
from repro.models.layers import output_head  # noqa: E402
from repro.serve.kvcache import grow_cache  # noqa: E402

F32_TOL = 2e-4
SEED = 2**33 + 17

SMALL = {
    "name": "moonlight_small", "program_arch": "moonlight_16b_a3b",
    "reference": "reference/moonlight.py",
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_shared_experts": 1, "n_routed_experts": 4, "router_experts": 8,
    "first_held_expert": 0, "num_experts_per_tok": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 50000, "rms_norm_eps": 1e-5,
    "routed_scaling_factor": 2.446, "vocab_size": 256,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "q_lora_rank": None, "tie_word_embeddings": False,
}

CONFIG_MODULE = load_module(BENCH / "configs" / "moonlight_16b_a3b_e16.py",
                      "bench_moonlight_config")


def _model(c=SMALL):
    """The program's config of ``c`` and its weights, in float32."""
    return _built(tuple(sorted(c.items())))


@functools.lru_cache(maxsize=None)
def _built(items):
    c = dict(items)
    cfg = CONFIG_MODULE.model_config(c)
    params = CONFIG_MODULE.make_params(c, cfg, SEED)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), params)


def _tokens(n, T, vocab=SMALL["vocab_size"]):
    return np.random.default_rng(3).integers(0, vocab, (n, T)).astype(np.int32)


def _ref_logits(toks, c=SMALL, quant=None):
    return ref.logits(c, SEED, list(toks), list(range(toks.shape[1])), quant)


def test_prefill_logits_match_the_reference():
    cfg, params = _model()
    toks = _tokens(2, 24)
    want = _ref_logits(toks)
    with jax.default_matmul_precision("highest"):
        h, _ = zoo.forward(cfg, params, {"tokens": jnp.asarray(toks)})
        got = np.asarray(h @ output_head(cfg, params).T)
        _, last = zoo.prefill(cfg, params, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(got, want, rtol=F32_TOL, atol=F32_TOL)
    np.testing.assert_allclose(np.asarray(last[:, 0]), want[:, -1],
                               rtol=F32_TOL, atol=F32_TOL)
    control = _ref_logits(toks, quant="float8_e4m3fn")
    assert np.abs(control - want).max() > 100 * F32_TOL


def _prefill_then_decode(cfg, params, toks, T):
    cache, first = zoo.prefill(cfg, params, {"tokens": jnp.asarray(toks[:, :T])})
    cache = grow_cache(cache, toks.shape[1] - T)
    out = [np.asarray(first[:, 0])]
    for i in range(T, toks.shape[1]):
        cache, logits = zoo.decode_step(cfg, params, cache,
                                        jnp.asarray(toks[:, i:i + 1]))
        out.append(np.asarray(logits[:, 0]))
    return cache, np.stack(out, 1)


def test_decode_through_the_latent_cache_matches_the_reference_forward():
    """Prefill 16 tokens, then decode 5 more one at a time through the
    absorbed form and the cache of latents: every step's logits are the
    reference's full forward pass at that position."""
    cfg, params = _model()
    T, toks = 16, _tokens(2, 21)
    want = _ref_logits(toks)[:, T - 1:]
    with jax.default_matmul_precision("highest"):
        cache, got = _prefill_then_decode(cfg, params, toks, T)
    np.testing.assert_allclose(got, want, rtol=F32_TOL, atol=F32_TOL)
    assert cache["latent"].shape == (3, 2, 21, 32 + 8)
    control = _ref_logits(toks, quant="float8_e4m3fn")[:, T - 1:]
    assert np.abs(control - want).max() > 100 * F32_TOL


def _moe_layer(c, j=0):
    """The program's MoE layer ``j`` and its weights, in float32."""
    cfg, params = _model(c)
    return cfg, jax.tree.map(lambda a: a[j], params["layers"]["moe"])


def _hidden(n=40, D=SMALL["hidden_size"]):
    return jnp.asarray(np.random.default_rng(5).standard_normal((1, n, D)),
                       jnp.float32)


def _ref_experts(c, h, bias=None):
    w = ref._layer_params(weights._words(SEED), jnp.int32(0),
                          weights._items(c), "moe")
    if bias is not None:
        w = dict(w, **{"moe/router_bias": bias})
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._experts(h[0], w, c, None))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two chips' shares of 4 experts each, with the shared experts (which
    every chip computes alike) counted once, are the reference's layer
    with all 8 experts."""
    whole = dict(SMALL, n_routed_experts=8)
    h = _hidden()
    parts = []
    with jax.default_matmul_precision("highest"):
        for first in (0, 4):
            cfg, w = _moe_layer(dict(SMALL, first_held_expert=first))
            y, _, routed = moe.moe_block(cfg, w, h)
            parts.append((np.asarray(y[0]), int(routed)))
        shared = np.asarray(moe.L.swiglu(w["dense"], h[0]))
    got = parts[0][0] + parts[1][0] - shared
    np.testing.assert_allclose(got, _ref_experts(whole, h), rtol=F32_TOL,
                               atol=F32_TOL)
    assert parts[0][1] + parts[1][1] == 40 * 2      # every route, once


def test_every_token_on_one_expert_drops_none():
    """A bias that sends every token to experts 1 and 2 puts all 40 tokens
    in each of their groups, far past any capacity of 40 * 2 / 8 a
    dropping layer would give them; the result is still the reference's."""
    cfg, w = _moe_layer(SMALL)
    bias = jnp.zeros(8, jnp.float32).at[1].set(10.0).at[2].set(10.0)
    h = _hidden()
    with jax.default_matmul_precision("highest"):
        y, _, routed = moe.moe_block(cfg, dict(w, router_bias=bias), h)
        _, top_e, _ = moe.route(cfg, dict(w, router_bias=bias), h[0])
    assert sorted(np.unique(np.asarray(top_e)).tolist()) == [1, 2]
    assert int(routed) == 80
    np.testing.assert_allclose(np.asarray(y[0]), _ref_experts(SMALL, h, bias),
                               rtol=F32_TOL, atol=F32_TOL)


def test_bias_changes_the_selection_and_not_the_weights():
    cfg, w = _moe_layer(SMALL)
    h = _hidden()[0]
    bias = jnp.zeros(8, jnp.float32).at[3].set(0.3)
    with jax.default_matmul_precision("highest"):
        _, e0, _ = moe.route(cfg, dict(w, router_bias=jnp.zeros(8)), h)
        top_w, e1, _ = moe.route(cfg, dict(w, router_bias=bias), h)
        scores = np.asarray(jax.nn.sigmoid(h @ w["router"]))
    e0, e1, top_w = np.asarray(e0), np.asarray(e1), np.asarray(top_w)
    moved = (np.sort(e0, 1) != np.sort(e1, 1)).any(1)
    assert 0 < moved.sum() < len(moved)           # some selections change
    assert (e1[moved] == 3).any(1).all()          # toward the favoured expert
    picked = np.take_along_axis(scores, e1, 1)
    want = picked / picked.sum(1, keepdims=True) * 2.446
    np.testing.assert_allclose(top_w, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(_ref_experts(SMALL, _hidden(), bias),
                               np.asarray(moe.moe_block(cfg, dict(
                                   w, router_bias=bias), _hidden())[0][0]),
                               rtol=F32_TOL, atol=F32_TOL)


def test_the_untied_head_is_used():
    cfg, params = _model()
    toks = jnp.asarray(_tokens(2, 8))
    assert not cfg.tie_embeddings and params["head"].shape == params["emb"].shape
    _, logits = zoo.prefill(cfg, params, {"tokens": toks})
    _, zeroed = zoo.prefill(cfg, dict(params, head=jnp.zeros_like(params["head"])),
                            {"tokens": toks})
    assert np.abs(np.asarray(logits)).max() > 0.1
    assert np.abs(np.asarray(zeroed)).max() == 0.0


def test_param_count_is_the_held_tree():
    """``param_count`` counts what the config holds (the final norm aside),
    and the registered config holds Moonlight's published 16.0 B."""
    from repro.configs import get_config

    cfg, params = _model()
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == cfg.param_count() + cfg.d_model
    full = get_config("moonlight_16b_a3b")
    assert full.param_count() == pytest.approx(15.96e9, rel=1e-3)
    assert full.replace(held_experts=16).param_count() == pytest.approx(
        5.164e9, rel=1e-3)
