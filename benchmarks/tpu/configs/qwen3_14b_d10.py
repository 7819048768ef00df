"""Builder of ``qwen3_14b_d10``: Qwen3-14B's published widths, 10 of its
40 layers, served through the repo's dense model (``models/dense.py``).

The sizes, the source, what was cut and what was assumed are in
``qwen3_14b_d10.json`` beside this file; its plain float32 reference is
``reference/qwen3.py``.
"""
from __future__ import annotations

from typing import Dict

#: the file's keys and the program's names for them
WIDTHS = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
}


def model_config(c: Dict):
    """The program's ``ModelConfig`` of ``c["program_arch"]`` run at this
    file's sizes: every width and the depth come from the file."""
    from repro.configs import get_config

    cfg = get_config(c["program_arch"])
    if not cfg.qk_norm or cfg.sliding_window or cfg.family != "dense":
        raise ValueError(f"{c['name']}: {c['program_arch']} is not a dense "
                         "qk-norm model without a window")
    if c["rms_norm_eps"] != 1e-6 or not c["tie_word_embeddings"]:
        raise ValueError(f"{c['name']}: the program's dense model has "
                         "rms_norm_eps 1e-6 and a tied output head")
    return cfg.replace(**{attr: c[key] for key, attr in WIDTHS.items()})


def make_params(c: Dict, cfg, seed: int):
    """Weights from the seed (``weights.py``), in the tree the program's
    ``param_spec`` describes; refuses any leaf whose shape or dtype
    differs."""
    import jax

    from repro.models import zoo
    from repro.models.layers import shapes_of

    import weights

    params = weights.make_params(c, seed)
    want = shapes_of(zoo.param_spec(cfg))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype) for w, g in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{c['name']}: weights do not match the program's "
                         "param_spec")
    return params
