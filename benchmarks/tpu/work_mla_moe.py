"""The bytes each decode roofline of a latent-attention, mixture-of-experts
decoder (the DeepSeek-V3 block) divides by, counted from the
configuration file's published sizes (Hugging Face key names) and the
experts it holds (``n_routed_experts`` of ``router_experts``).

Nothing here reads the program.  Every weight and cache element is served
in bfloat16, the router and its correction bias in float32.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2
F32 = 4


def sizes(c: Dict) -> Dict[str, int]:
    return {
        "D": c["hidden_size"], "H": c["num_attention_heads"],
        "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
        "rope": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
        "F": c["intermediate_size"], "Fe": c["moe_intermediate_size"],
        "Fs": c["n_shared_experts"] * c["moe_intermediate_size"],
        "E": c["router_experts"], "held": c["n_routed_experts"],
        "V": c["vocab_size"], "L": c["num_hidden_layers"],
        "first": c["first_k_dense_replace"],
    }


def mla_params(c: Dict) -> int:
    """One layer's latent attention: q, kv_a, the latent's norm, kv_b, o."""
    s = sizes(c)
    return (s["D"] * s["H"] * (s["nope"] + s["rope"])
            + s["D"] * (s["r"] + s["rope"]) + s["r"]
            + s["r"] * s["H"] * (s["nope"] + s["dv"])
            + s["H"] * s["dv"] * s["D"])


def expert_bytes(c: Dict) -> int:
    """Every held routed expert of every MoE layer, once."""
    s = sizes(c)
    return (s["L"] - s["first"]) * s["held"] * 3 * s["D"] * s["Fe"] * BF16


def latent_bytes(c: Dict, batch: int, context: int) -> int:
    """The cached latents of ``context`` positions of every sequence in
    every layer."""
    s = sizes(c)
    return s["L"] * batch * context * (s["r"] + s["rope"]) * BF16


def mla_bytes(c: Dict, batch: int, context: int) -> int:
    """Least bytes of one decode step's latent attention: every layer's
    attention weights once and the latents it attends."""
    return (sizes(c)["L"] * mla_params(c) * BF16
            + latent_bytes(c, batch, context))


def decode_bytes(c: Dict, batch: int, context: int) -> int:
    """Least bytes of one decode step: every held weight once (the
    embedding only in the rows of the step's tokens) and the latents of
    ``context`` positions of every sequence."""
    s = sizes(c)
    D = s["D"]
    norms = 2 * D
    first = mla_params(c) + 3 * D * s["F"] + norms
    moe = mla_params(c) + 3 * D * s["Fs"] + norms
    router = s["E"] * D + s["E"]                   # float32
    layers = (s["first"] * first + (s["L"] - s["first"]) * moe) * BF16
    layers += (s["L"] - s["first"]) * router * F32 + expert_bytes(c)
    top = (s["V"] * D + D + batch * D) * BF16       # head, final norm, rows
    return layers + top + latent_bytes(c, batch, context)


def generate_work(c: Dict, batch: int, prompt: int, new: int) -> Dict:
    """Bytes of the ``new - 1`` decode steps of one ``generate`` call,
    step ``i`` attending ``prompt + i + 1`` positions."""
    ctx = [prompt + i + 1 for i in range(new - 1)]
    return {
        "decode_steps": len(ctx),
        "decode_bytes": sum(decode_bytes(c, batch, n) for n in ctx),
        "expert_bytes": len(ctx) * expert_bytes(c),
        "mla_bytes": sum(mla_bytes(c, batch, n) for n in ctx),
    }
