"""Moonlight-16B-A3B — the DeepSeek-V3 block (model_type ``deepseek_v3``).

[moe] 27L d_model=2048 16H MLA (kv_lora 512, qk 128+64 rope, v 128)
vocab=163840 untied; layer 0 dense SwiGLU 11264; layers 1-26: 64 routed
experts of 1408, top-6, sigmoid scores with a selection-only correction
bias (noaux_tc, one group), normalised weights x 2.446, 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B; hf]
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(
    ModelConfig(
        arch_id="moonlight_16b_a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=1408,  # each routed expert's width (moe_intermediate_size)
        vocab_size=163840,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50_000.0,
        n_experts=64,
        top_k=6,
        moe_dense_ff=2 * 1408,  # the two shared experts, as one SwiGLU
        router="sigmoid",
        routed_scale=2.446,
        n_dense_layers=1,  # first_k_dense_replace
        dense_ff=11264,  # intermediate_size
        rms_norm_eps=1e-5,
        tie_embeddings=False,
        remat="dots",
        notes="16.0B total, ~3B active; q_lora_rank null, no rope scaling.",
    )
)
