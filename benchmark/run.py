"""One run of one cell of ``BENCHMARK.json``:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is data: it finds the cell's configuration by the ``file`` of its
``configs`` entry, its workload in ``benchmark/workloads/<cell>.json``, the
runner in ``benchmark/runners/<runner>.py`` by the workload's ``runner`` key
and every metric's reader in ``benchmark/metrics/<metric>.py``, all by the
names in ``BENCHMARK.json``. A new cell, configuration, metric or runner is
new files and new entries; no file that is there needs an edit.

The last line of standard output is the result object. Without a TPU, with
another number of chips than the cell names, or without the program beside
it, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before the heavy imports: set-up starts here

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
from dataclasses import dataclass, field
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from peaks import occupied_bytes  # noqa: E402


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    require_chip: bool = True
    t_process: float = field(default_factory=lambda: T_PROCESS)


def load_module(kind: str, name: str, bench_dir: str = HERE):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, name: str, root: str = ROOT, bench_dir: str = HERE) -> tuple[dict, dict, dict]:
    """(cell entry, configuration file, workload file) for a cell's name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    entry = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    return entry, config, workload


def metrics_for(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def read_metrics(bench: dict, group: str, run: dict, bench_dir: str = HERE) -> dict[str, dict]:
    """Each metric of ``group`` this cell reports, read by its own reader.
    A reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in metrics_for(bench, group, run["cell"]):
        value = load_module("metrics", m["name"], bench_dir).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def look_for_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found platform {devices[0].platform!r}")
    if len(devices) != chips:
        raise SystemExit(f"the cell names {chips} chip(s); JAX found {len(devices)}")
    return devices


def _plain(v: Any) -> Any:
    """JSON has no inf/nan: write them as strings."""
    if isinstance(v, dict):
        return {k: _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return v


def result_line(bench: dict, run: dict, trace: bool, bench_dir: str = HERE) -> dict:
    import jax

    group = "per_layer" if trace else "end_to_end"
    d = jax.devices()[0]
    device = {
        "platform": d.platform, "kind": d.device_kind, "count": jax.device_count(),
        "memory_peak_bytes": max(map(occupied_bytes, run["memory_stats"]), default=0),
    }
    line = {
        "correct": bool(run["correct"]), "attempted": run["attempted"],
        "failed": run["failed"], "metrics": read_metrics(bench, group, run, bench_dir),
        "device": device,
    }
    if trace and run.get("trace") is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = run["trace"]["breakdown"]
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in run["checks"].items()}
    return _plain(line)


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, bench_dir: str = HERE, out_dir: str | None = None,
             require_chip: bool = True) -> dict:
    """Everything of a run but the look for a chip and the printing."""
    entry, config, workload = load_cell(bench, name, root, bench_dir)
    out_dir = out_dir or os.path.join(bench_dir, "out", name)
    shutil.rmtree(out_dir, ignore_errors=True)  # one run's files, not a history
    os.makedirs(out_dir)
    cell = Cell(name=name, chips=int(entry["chips"]), config=config, workload=workload,
                seed=seed, seconds=seconds, trace=trace, out_dir=out_dir,
                require_chip=require_chip)
    run = load_module("runners", workload["runner"], bench_dir).run(cell)
    run["t_process"] = cell.t_process
    if trace and run.get("profile_dir"):
        import xtrace

        run["trace"] = xtrace.reduce_profile(run["profile_dir"])
    return run


def write_record(run: dict, line: dict) -> None:
    """``out/<cell>/run.json``: the result line and what stood behind it."""
    keep = ("cell", "seed", "chips", "setup_phases", "step_ends", "memory_stats",
            "reference_s", "readings", "checks", "trace")
    record = {"line": line, **{k: run.get(k) for k in keep}}
    with open(os.path.join(run["out_dir"], "run.json"), "w") as f:
        json.dump(_plain(record), f, indent=1, default=str)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, _, _ = load_cell(bench, args.workload)
    try:
        from dtc_tpu.utils.dist import configure_compile_cache
    except ImportError as e:
        raise SystemExit(f"the program is not beside the benchmark: {e}")
    configure_compile_cache()  # <checkout>/.jax_cache, or where the environment says
    look_for_chips(int(entry["chips"]))

    run = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace))
    line = result_line(bench, run, bool(args.trace))
    write_record(run, line)
    for name, c in run["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'FAILED'} {({k: v for k, v in c.items() if k not in ('value', 'limit', 'ok')})}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
