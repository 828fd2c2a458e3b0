"""End-to-end LM training driver (brief deliverable b).

Trains an xlstm-125m-family model on the synthetic Markov-bigram pipeline
with the full substrate: sharded params, AdamW, async fault-tolerant
checkpointing, deterministic restart.  Defaults are CPU-budgeted (a ~1.6M
param width-reduced stack, 120 steps, loss visibly descends below the
unigram entropy); pass --full for the real 125M config (TPU-scale).

  PYTHONPATH=src python examples/train_lm.py
  PYTHONPATH=src python examples/train_lm.py --arch qwen3 --steps 200
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import argparse

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--full", action="store_true", help="full config (TPU-scale)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_example_ckpt")
    args = ap.parse_args()

    argv = [
        "--arch", args.arch,
        "--steps", str(args.steps),
        "--batch", "8",
        "--seq", "128",
        "--lr", "3e-3",
        "--ckpt-dir", args.ckpt_dir,
        "--ckpt-every", "50",
        "--f32",
    ]
    if not args.full:
        argv.append("--reduced")
    final_loss = train_main(argv)
    print(f"[example] final loss {final_loss:.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
