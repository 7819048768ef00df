"""Program config and weights of ``moonlight_16b_a3b_e16``:
Moonlight-16B-A3B's published widths and all 27 layers, holding 16 of
each MoE layer's 64 experts (one chip's share of a four-chip
expert-parallel deployment), served through
the repo's ``moe`` family (``models/moe.py``, ``models/mla.py``).

The sizes, the source, what was cut and what was assumed are in
``moonlight_16b_a3b_e16.json`` beside this file; its plain float32
reference, whose weight layout this module serves, is
``reference/moonlight.py``.
"""
from __future__ import annotations

from typing import Dict

#: the file's keys and the program's names for them
SIZES = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "vocab_size": "vocab_size",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "moe_intermediate_size": "d_ff",
    "intermediate_size": "dense_ff", "first_k_dense_replace": "n_dense_layers",
    "num_experts_per_tok": "top_k", "routed_scaling_factor": "routed_scale",
    "rms_norm_eps": "rms_norm_eps", "router_experts": "n_experts",
}


def model_config(c: Dict):
    """The program's ``ModelConfig`` of ``c["program_arch"]`` at this
    file's sizes, holding ``n_routed_experts`` experts from
    ``first_held_expert``.  Refuses a file whose routing or heads the
    program does not compute as the file states."""
    from repro.configs import get_config

    cfg = get_config(c["program_arch"])
    if (cfg.router != "sigmoid" or cfg.tie_embeddings
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"]
            or c["topk_method"] != "noaux_tc" or c["n_group"] != 1
            or c["q_lora_rank"] is not None or c.get("rope_scaling") is not None
            or c["tie_word_embeddings"]):
        raise ValueError(f"{c['name']}: {c['program_arch']} does not route "
                         "or attend as this file states")
    shared = c["n_shared_experts"] * c["moe_intermediate_size"]
    return cfg.replace(held_experts=c["n_routed_experts"],
                       first_held_expert=c["first_held_expert"],
                       moe_dense_ff=shared,
                       **{attr: c[key] for key, attr in SIZES.items()})


def _make(words, c_items):
    import jax
    import jax.numpy as jnp

    from reference import moonlight as ref

    c = dict(c_items)
    top, first, moe, expert = ref.layout(c)
    D, n_first = c["hidden_size"], c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_first

    def stacked(path, shape, n):
        return jax.vmap(lambda i: ref.value(words, path, shape, i, D))(
            jnp.arange(n))

    def nest(flat):
        out: Dict = {}
        for p, v in flat.items():
            *parents, name = p.split("/")
            node = out
            for part in parents:
                node = node.setdefault(part, {})
            node[name] = v
        return out

    out = {p: ref.value(words, p, s, -1, D) for p, s in top.items()}
    out["first_layers"] = nest({p: stacked("first_layers/" + p, s, n_first)
                                for p, s in first.items()})
    layers = {p: stacked("layers/" + p, s, n_moe) for p, s in moe.items()}
    for p, s in expert.items():            # (layers, held experts, ...)
        layers[p] = jnp.stack([stacked(f"layers/{p}/{e}", s, n_moe)
                               for e in ref.held_experts(c)], axis=1)
    out["layers"] = nest(layers)
    return out


def make_params(c: Dict, cfg, seed: int):
    """Weights from the seed in the reference's layout, in the tree the
    program's ``param_spec`` describes, in one jitted call on the default
    device; refuses any leaf whose shape or dtype differs."""
    import jax

    from repro.models import zoo
    from repro.models.layers import shapes_of

    import weights

    make = jax.jit(_make, static_argnames=("c_items",))
    params = make(weights._words(seed), c_items=weights._items(c))
    want = shapes_of(zoo.param_spec(cfg))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype) for w, g in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{c['name']}: weights do not match the program's "
                         "param_spec")
    return params
