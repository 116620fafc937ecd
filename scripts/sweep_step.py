"""End-to-end train-step block-size sweep at long context (on-chip).

The standalone kernel sweep (sweep_flash.py) is dispatch-bound per call,
so A/B decisions use the full train step instead: 12 layers per jit call amortize dispatch, and the
number is the one bench.py reports. Feeds PERF.md.

Usage: python scripts/sweep_step.py [--seq 4096] [--batch 4]
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

COMBOS = [
    (512, 512), (256, 512), (256, 1024), (512, 1024),
    (1024, 1024), (512, 2048), (256, 2048),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    from bench_common import time_step

    for bq, bkv in COMBOS:
        if args.seq % bq or args.seq % bkv:
            continue
        try:
            ms = min(
                time_step(
                    steps=args.steps, batch=args.batch, max_seq_len=args.seq,
                    remat="block_save_flash", block_q=bq, block_kv=bkv,
                )
                for _ in range(2)
            )
            print(f"bq={bq:5d} bkv={bkv:5d}  step {ms:8.2f} ms", flush=True)
        except Exception as e:  # noqa: BLE001
            first = (str(e).splitlines() or [""])[0]
            print(f"bq={bq:5d} bkv={bkv:5d}  FAILED: {type(e).__name__}: "
                  f"{first[:90]}", flush=True)


if __name__ == "__main__":
    main()
