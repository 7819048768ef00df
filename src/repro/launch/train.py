"""Training launcher.

Smoke (CPU):      PYTHONPATH=src python -m repro.launch.train --arch qwen3_14b --smoke --steps 3
Production lower: the dry-run (repro.launch.dryrun) is the no-hardware path;
on a real pod this module runs the same ``make_train_step`` under
``make_production_mesh()`` with the same shardings.
"""
from __future__ import annotations

import argparse
import logging

from repro.configs import SHAPES, RunConfig, get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true", help="reduced config on CPU")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--grad-compression", default="none", choices=["none", "int8"])
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    if args.smoke:
        cfg = smoke_config(args.arch)
        shape = ShapeSpec("smoke", args.seq, args.batch, "train")
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]
    run = RunConfig(
        model=cfg, shape=shape, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every, total_steps=max(args.steps, 10),
        grad_compression=args.grad_compression,
    )
    out = train(run, steps=args.steps)
    print(f"final step {out['final_step']}  losses: "
          f"{[round(l, 4) for l in out['losses'][-5:]]}  "
          f"stragglers: {out['stragglers']}")


if __name__ == "__main__":
    main()
