"""End-to-end driver: train a ~100M-param LM for a few hundred steps on the
synthetic token stream, with checkpointing + restore (deliverable (b)).

Uses yi-9b's family at reduced width so ~100M params fit CPU training.

  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import os
import tempfile

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.launch.train import train

use_compile_cache()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="yi-9b")
    args = ap.parse_args()
    ckpt = os.path.join(tempfile.gettempdir(), "repro_train_lm_ckpt")
    # ~100M params: d_model=512, 8 layers, vocab 16k
    losses = train(args.arch, steps=args.steps, batch=4, seq=256, lr=3e-4,
                   reduced=True, d_model=512, n_layers=8, ckpt_dir=ckpt,
                   ckpt_every=max(args.steps // 2, 1))
    assert losses[-1] < losses[0], "loss did not improve"
    print(f"checkpoints in {ckpt}")


if __name__ == "__main__":
    main()
