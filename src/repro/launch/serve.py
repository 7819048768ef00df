"""Serving launcher: random-weight requests through ``serve.loop.generate``.

Full width (the registered config, random bf16 weights):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --batch 4 --prompt-len 256 --new-tokens 16
Reduced config (CPU smoke):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --smoke --new-tokens 8
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import zoo
from repro.models.layers import init_of
from repro.serve.loop import generate


def serve(cfg: ModelConfig, *, batch: int, prompt_len: int, new_tokens: int,
          seed: int = 0):
    """Answer one batch of random requests with random weights, both made
    from ``seed``; returns ``(params, prompts, tokens, info)``."""
    spec = zoo.param_spec(cfg)
    # one jitted program: no float32 copy of each full-width leaf
    params = jax.jit(lambda key: init_of(spec, key))(jax.random.PRNGKey(seed))
    k_tok, k_extra = jax.random.split(jax.random.PRNGKey(seed + 1))
    prompts = jax.random.randint(
        k_tok, (batch, prompt_len), 0, cfg.vocab_size, dtype=jnp.int32)
    extra = None
    if cfg.family == "encdec":
        extra = {
            "audio_embeds": jax.random.normal(
                k_extra, (batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        }
    elif cfg.family == "vlm":
        pos = jnp.broadcast_to(
            jnp.arange(prompt_len, dtype=jnp.int32)[None], (batch, prompt_len))
        extra = {
            "embeds": jax.random.normal(
                k_extra, (batch, prompt_len, cfg.d_model), jnp.bfloat16),
            "positions": jnp.stack([pos, pos, pos], axis=1),
        }
    tokens, info = generate(cfg, params, prompts, max_new_tokens=new_tokens,
                            extra_batch=extra)
    return params, prompts, tokens, info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU); default is full width")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, _, tokens, info = serve(cfg, batch=args.batch,
                               prompt_len=args.prompt_len,
                               new_tokens=args.new_tokens)
    print("generated:", tokens.tolist())
    print("info:", info)


if __name__ == "__main__":
    main()
