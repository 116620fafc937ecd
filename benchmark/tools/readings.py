"""The readings a cell's limits are set from, in one process on the chip:

    python benchmark/tools/readings.py --workload <cell> --seeds 12 [--controls 3] [--draws] [--out FILE]

For each seed: the program's set-up steps through the runner (a window of
``--seconds``, short: the readings need none), the float32 reference, and
the gap of each number compared (the lower readings). For the first
``--controls`` seeds also the control (the reference with int8 matmuls, put
in the program's place) and the planted faults (the reference on part of
every batch: half the rows; and one chip's share of them where the cell has
more than one; and a step that returns its parameters unchanged), each
compared with the float32 reference the same way (the upper readings). One
JSON object per line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--cpu", action="store_true", help="rehearsal: skip the look for a chip")
    ap.add_argument("--draws", action="store_true",
                    help="each seed draws its weights too: a workload's weights_seed is set aside")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, config, workload = harness.load_cell(bench, args.workload)
    if args.draws:
        workload.pop("weights_seed", None)
    from dtc_tpu.utils.dist import configure_compile_cache

    configure_compile_cache()
    if not args.cpu:
        harness.look_for_chips(int(entry["chips"]))
    import compare

    runner = harness.load_module("runners", workload["runner"])
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cell = harness.Cell(
            name=args.workload, chips=int(entry["chips"]), config=config, workload=workload,
            seed=seed, seconds=args.seconds, trace=False,
            out_dir=os.path.join(harness.HERE, "out", "readings"), require_chip=not args.cpu)
        shutil.rmtree(cell.out_dir, ignore_errors=True)
        os.makedirs(cell.out_dir)
        t0 = time.perf_counter()
        run = runner.drive(cell)
        t1 = time.perf_counter()
        ref = runner.follow(run)
        t2 = time.perf_counter()
        rec = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "program": compare.readings(run["program"], ref)}
        if i < args.controls:
            rows = int(workload["traffic"]["rows"])
            sides = {"control_int8": {"matmul": "int8"},
                     "fault_half_rows": {"rows": slice(0, rows // 2)},
                     "fault_state_unchanged": {"frozen": True}}
            if cell.chips > 1:
                sides["fault_one_chips_rows"] = {"rows": slice(0, rows // cell.chips)}
            for name, how in sides.items():
                t = time.perf_counter()
                rec[name] = compare.readings(runner.follow(run, **how), ref)
                rec[name + "_s"] = time.perf_counter() - t
        line = json.dumps(harness._plain(rec))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
