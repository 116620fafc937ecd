"""A toy copy of the benchmark in a temporary directory: the committed
files, plus a configuration and a cell added as new files and new entries,
the way a later PR adds them."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TOY_MODEL = {
    "vocab_size": 250, "d_model": 64, "n_layers": 2, "n_heads": 2, "d_ff": 256,
    "max_seq_len": 64, "dropout": 0.0, "param_dtype": "float32",
    "compute_dtype": "bfloat16", "attention": "auto", "vocab_pad_multiple": 128,
}


def make(tmp: str, *, like: str = "gpt2-medium.train-b8", chips: int = 1, rows: int = 4,
         compute: str = "bfloat16", limits: dict | None = None) -> tuple[dict, str]:
    """(BENCHMARK.json's object, the benchmark directory) of a toy copy with
    one more configuration ``toy`` and cell ``toy.train``, whose workload is
    ``like``'s at toy size (its limits included, unless given)."""
    bench_dir = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(bench_dir, "configs", "toy.json"), "w") as f:
        json.dump({"model": {**TOY_MODEL, "compute_dtype": compute}}, f)
    with open(os.path.join(BENCH, "workloads", f"{like}.json")) as f:
        wl = json.load(f)
    wl["traffic"]["rows"] = rows
    wl["train"]["parallel"] = "fsdp" if chips > 1 else "dp"
    # The tests' CPU backend has four virtual devices, and the program's mesh
    # takes all of them: a one-chip cell's toy runs data-parallel over four.
    wl["train"]["mesh"] = {"pipe": 1, "data": 4, "model": 1}
    wl["trace_steps"] = [3, 6]
    if limits is not None:
        wl["limits"] = limits
    with open(os.path.join(bench_dir, "workloads", "toy.train.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy", "source": "none: a test's toy", "reduced": [],
                             "file": "benchmark/configs/toy.json", "why": "toy"})
    bench["workloads"].append({"name": "toy.train", "config": "toy", "traffic": "train",
                               "chips": chips, "why": "toy"})
    return bench, bench_dir
