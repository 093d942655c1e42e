"""End-to-end driver: dedup → train a reduced smollm for a few hundred steps
with checkpointing (deliverable (b): train-kind end-to-end example).

    PYTHONPATH=src python examples/train_smollm.py [--steps 200]
"""

import argparse

from repro.launch.train import run
from repro.util import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_smollm")
    args = ap.parse_args()
    out = run("smollm-135m", smoke=True, steps=args.steps,
              ckpt_dir=args.ckpt_dir, resume=False, fail_at=None,
              seq_len=128, global_batch=8, ckpt_every=50, dedup=True,
              log_every=10)
    losses = out["losses"]
    print(f"trained {len(losses)} steps: loss {losses[0]:.3f} → "
          f"{losses[-1]:.3f}")


if __name__ == "__main__":
    main()
