"""End-to-end training driver (deliverable b): train a ~100M-param LM
(qwen1.5-0.5b family at reduced width) for a few hundred steps with
checkpointing + auto-resume.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses

import jax

from repro.configs.base import get
from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    losses = train_main([
        "--arch", "qwen1.5-0.5b", "--reduced",
        "--steps", str(args.steps),
        "--batch", "16", "--seq", "128", "--lr", "3e-3",
        "--microbatches", "2",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
        "--log-every", "20",
    ])
    assert losses[-1] < losses[0], "loss did not decrease"
    print(f"OK: loss {losses[0]:.3f} -> {losses[-1]:.3f} over "
          f"{args.steps} steps; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
