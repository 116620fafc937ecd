"""MoE dispatch A/B sweep (on-chip): einsum vs sort across expert counts,
plus a capacity-factor sweep at E=8.

The measurement harness behind the PERF.md MoE tables and the
``moe_dispatch`` default decision: the two backends execute the SAME
routing (asserted in tests/test_moe.py), so every delta below is pure
dispatch/combine execution cost. The einsum path's dispatch work grows
linearly with E·cap (PERF.md round 5 attributes ~25-30 ms at E=8); the
sort path's is O(B·T·k·d) at any E — this sweep measures where (if
anywhere) the curves cross on real hardware.

Protocol matches scripts/sweep_step.py: full-train-step timing through
bench_common.time_step (12 layers per jit call amortize per-call
dispatch), best-of-2 windows. MFU on both bases is derived per row
(utils/metrics.py: "hw" counts the einsum-structural work incl. capacity
slack, "useful" counts only the k·T routed tokens — the backend-neutral
A/B number).

Usage: python scripts/sweep_moe.py [--batch 32] [--steps 15]
       [--experts 8 16 32] [--cf-sweep-e 8]
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DISPATCHES = ("einsum", "sort")
CAPACITY_FACTORS = (1.0, 1.25, 1.5, 2.0)


def _row(label: str, ms: float, batch: int, seq: int, cfg) -> None:
    import jax

    from dtc_tpu.utils.metrics import mfu

    step_s = ms / 1e3
    tok_s = batch * seq / step_s
    hw = mfu(cfg, batch, seq, step_s, jax.device_count())
    useful = mfu(cfg, batch, seq, step_s, jax.device_count(), moe_basis="useful")
    fmt = lambda u: f"{u:.4f}" if u is not None else "n/a"
    print(
        f"{label:34s} step {ms:8.2f} ms  {tok_s:9.0f} tok/s  "
        f"mfu_hw {fmt(hw)}  mfu_useful {fmt(useful)}",
        flush=True,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=15)
    ap.add_argument("--experts", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--cf-sweep-e", type=int, default=8,
                    help="expert count for the capacity-factor sweep (0 = skip)")
    args = ap.parse_args()

    from bench_common import flagship_model_cfg, time_step

    def measure(label, **knobs):
        try:
            ms = min(
                time_step(steps=args.steps, batch=args.batch,
                          max_seq_len=args.seq, remat="block_save_flash",
                          **knobs)
                for _ in range(2)
            )
            cfg = flagship_model_cfg(max_seq_len=args.seq,
                                     remat="block_save_flash", **knobs)
            _row(label, ms, args.batch, args.seq, cfg)
        except Exception as e:  # noqa: BLE001 — sweep rows fail independently
            first = (str(e).splitlines() or [""])[0]
            print(f"{label:34s} FAILED: {type(e).__name__}: {first[:80]}",
                  flush=True)

    print("# E-scaling: dispatch backend x expert count (top-2, cf=1.25)")
    for e in args.experts:
        for dispatch in DISPATCHES:
            measure(f"e{e}_{dispatch}", moe_experts=e, moe_dispatch=dispatch)

    if args.cf_sweep_e:
        print(f"# capacity-factor sweep at E={args.cf_sweep_e} (top-2)")
        for cf in CAPACITY_FACTORS:
            for dispatch in DISPATCHES:
                measure(
                    f"e{args.cf_sweep_e}_cf{cf}_{dispatch}",
                    moe_experts=args.cf_sweep_e, moe_dispatch=dispatch,
                    moe_capacity_factor=cf,
                )


if __name__ == "__main__":
    main()
