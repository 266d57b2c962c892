"""End-to-end training driver: Astra-searched strategy -> real training run.

The production invocation (a ~110M-param qwen3-family model, a few hundred
steps — what you would run on a v5e slice; on this CPU container it takes
hours):

    PYTHONPATH=src python examples/train_lm.py --size 100m --steps 300

The CPU-friendly demo (~15M params, ~5 minutes, loss visibly descends to
the synthetic corpus' entropy floor):

    PYTHONPATH=src python examples/train_lm.py --size 15m --steps 200
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.arch import ModelArch
from repro.launch import train as train_mod

SIZES = {
    # ~110M: 12L x 768d (GPT-2-small-ish with SwiGLU + GQA)
    "100m": ModelArch(name="lm-100m", family="dense", num_layers=12, hidden=768,
                      heads=12, kv_heads=4, ffn=3072, vocab=32000),
    # ~15M: CPU-demo scale
    "15m": ModelArch(name="lm-15m", family="dense", num_layers=6, hidden=384,
                     heads=6, kv_heads=2, ffn=1536, vocab=4096),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=list(SIZES), default="15m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    arch = SIZES[args.size]
    print(f"training {arch.name}: {arch.total_params()/1e6:.1f}M params")

    # reuse the production driver with an explicit arch
    train_mod.main([
        "--steps", str(args.steps),
        "--batch", str(args.batch),
        "--seq", str(args.seq),
        "--lr", "3e-3",
        "--checkpoint-dir", args.checkpoint_dir,
        "--checkpoint-every", "50",
        "--log-every", "10",
    ], arch=arch)


if __name__ == "__main__":
    main()
