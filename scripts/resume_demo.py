"""Flagship-scale interrupted-equals-uninterrupted demo on the real TPU.

The CPU-mesh test suite already proves resume exactness on a tiny model
(tests/test_checkpoint.py). This script demonstrates the same property at
flagship scale with everything running together — prefetch thread,
incremental CSV, Orbax checkpoint cadence, periodic eval:

  phase 1 (``--phase interrupt``): train with checkpoints every 1000 steps;
    the caller kills the process mid-run (SIGTERM, like a preemption).
  phase 2 (``--phase resume``): the identical command line resumes from the
    latest completed checkpoint and runs to 3000.

Success criterion: the resumed run's final loss equals step 3000 of the
committed uninterrupted run (outputs/tpu_dp/log.csv — same seed, data
stream, and fold_in(step) RNG) bit-for-bit.

NOTE: one flagship checkpoint is 1.08 GB of fp32 state fetched
device->host; the demo uses 3000 steps / cadence 1000.

Run:  timeout 330 python scripts/resume_demo.py --phase interrupt
      python scripts/resume_demo.py --phase resume
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["interrupt", "resume"], required=True)
    ap.add_argument("--steps", type=int, default=3000)
    args = ap.parse_args()

    from dtc_tpu.config.schema import MeshConfig, ModelConfig, OptimConfig, TrainConfig
    from dtc_tpu.train.trainer import train

    model_cfg = ModelConfig(
        vocab_size=50258, d_model=512, n_layers=12, n_heads=16, d_ff=2048,
        max_seq_len=512, dropout=0.1, param_dtype="float32",
        compute_dtype="bfloat16", attention="auto", remat="block_save_flash",
    )
    opt_cfg = OptimConfig(lr=3e-4, weight_decay=0.1, grad_clip=1.0)
    train_cfg = TrainConfig(
        seed=0, parallel="dp", batch=32, steps=args.steps, log_every=50,
        output_dir="outputs/tpu_resume", dataset="synthetic", warmup_steps=5,
        prefetch=2, prng_impl="rbg", sync_every_step=False,
        checkpoint_every=1000, resume=True, eval_every=2500, eval_batches=4,
        # Fresh interrupt phase legitimately restarts this artifact; the
        # resume phase enters via start_step > 0 and never needs the flag.
        overwrite=True,
    )
    result = train(train_cfg, model_cfg, opt_cfg)
    print(f"final loss: {result.losses[-1]:.12f}")


if __name__ == "__main__":
    main()
