"""The harness is data; the yardstick agrees with what it copies; the run
refuses a machine without a chip."""

import importlib
import json
import os
import subprocess
import sys

import pytest

import run as harness
from toy import BENCH, ROOT, make

CONFIGS = ("gpt2-medium", "gpt2-large")


def _model(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name", CONFIGS)
def test_flop_count_equals_the_programs(name):
    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.utils.metrics import gpt_step_flops

    import flops

    model = _model(name)
    cfg = ModelConfig(**model)
    for rows in (8, 32):
        assert flops.train_step_flops(model, rows, 1024) == gpt_step_flops(cfg, rows, 1024)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_shapes_are_the_programs(name):
    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.models.gpt import param_count

    import reference

    model = _model(name)
    n = sum(int(__import__("numpy").prod(s)) for s in reference.leaf_shapes(model).values())
    assert n == param_count(ModelConfig(**model))


def test_percentile_is_nearest_rank():
    from dtc_tpu.utils.percentile import nearest_rank as theirs

    from percentile import nearest_rank

    vals = [0.3, 0.1, 0.2, 0.5, 0.4]
    for q in (0.0, 0.5, 0.95, 1.0):
        assert nearest_rank(vals, q) == theirs(vals, q)
    assert nearest_rank([], 0.5) is None


def test_benchmark_json_keeps_to_the_contract():
    import re

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    cells = {w["name"] for w in bench["workloads"]}
    ends = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in ends and ends["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py")), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in ends
    for c in bench["configs"]:
        assert name.match(c["name"]) and len(c["source"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"]) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "workloads", w["name"] + ".json"))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    exposed = next(m for m in bench["per_layer"] if m["name"].startswith("collective_exposed"))
    assert all(next(w for w in bench["workloads"] if w["name"] == n)["chips"] == 4
               for n in exposed["workloads"])


@pytest.mark.parametrize("totals, expected", [
    ([40960.0] * 5, 0.0),                       # the routers stand still
    ([40000.0, 45000.0, 50000.0, 55000.0, 60000.0], 40.0),  # 20 k of wander around 50 k
    ([], None),                                 # a GPT-2 cell: no such event, nothing to read
], ids=["flat", "40k_to_60k", "no_events"])
def test_held_swing_reads_how_far_the_routers_moved(totals, expected):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "moe_held_swing_pct.train")
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    events = [{"etype": "step", "step": 1}]
    events += [{"etype": "moe_counters", "step": i, "moe_assigned_held": [t / 4] * 4}
               for i, t in enumerate(totals, 1)]
    got = harness.load_module("metrics", entry["name"]).read({"events": events})
    assert got == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("cell, expected", [
    ("gpt2-medium.train-b8", 2**31 + 5),                          # no checkpoint named: the weights are --seed's
    ("qwen3-next-80b-a3b.train-ep16share-b2x8192", 20261002),     # one checkpoint for every run; the rows stay --seed's
], ids=["from_the_seed", "one_checkpoint"])
def test_weights_seed_is_the_workloads_or_the_runs(cell, expected, monkeypatch):
    """Both runners make the program's weights and the reference's from the
    same seed: ``drive`` and ``follow`` ask the one function."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _, _, workload = harness.load_cell(json.load(f), cell)
    runner = harness.load_module("runners", workload["runner"])
    base = getattr(runner, "base", runner)
    assert base.weights_seed(workload, 2**31 + 5) == expected
    ref = importlib.import_module(workload["runner"] == "train_ref" and "reference_qwen3_next" or "reference")
    seen = []
    monkeypatch.setattr(ref, "run_steps", lambda model, optim, seed, batches, **kw: seen.append(seed))
    model = {"vocab_size": 50, "max_seq_len": 8}
    runner.follow({"workload": workload, "model": model, "seed": 2**31 + 5, "chips": 1, "optim": {},
                   "reference_module": "reference_qwen3_next"})
    assert seen == [expected]


@pytest.mark.parametrize("steps_ms, traced, expected", [
    ([550.0] * 40, None, 0.0),                               # every step on one level
    ([550.0] * 37 + [553.7] * 3, None, 100 * 3.7 / 550),     # the p95 rests on three steps a level up
    ([550.0] * 38 + [553.7] * 2, None, 0.0),                 # two such steps: the third-longest is not one
    ([550.0] * 4 + [700.0, 550.0, 9000.0] + [550.0] * 33, [5, 7], 0.0),  # the profiler's own steps are left out
    ([550.0, 551.0], None, None),                            # too few steps to have a tail
], ids=["one_level", "three_steps_up", "two_steps_up", "profiled_steps_left_out", "two_steps"])
def test_p95_over_median_reads_the_steps_that_pay_more(steps_ms, traced, expected):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "step_p95_over_median_pct.train")
    assert entry["moves"] == "train_step_ms_p95" and "workloads" not in entry
    ends, t = [], 0.0
    for ms in steps_ms:
        t += ms / 1e3
        ends.append(t)
    run = {"step_ends": ends, "profile_dir": "somewhere" if traced else None,
           "workload": {"trace_steps": traced}}
    got = harness.load_module("metrics", entry["name"]).read(run)
    assert got == (None if expected is None else pytest.approx(expected, abs=1e-9))


def test_new_files_need_no_edit(tmp_path):
    """A configuration, a cell, a per-layer metric and a runner added as new
    files and new entries are found by name."""
    bench, bench_dir = make(str(tmp_path))
    with open(os.path.join(bench_dir, "runners", "echo.py"), "w") as f:
        f.write("def run(cell):\n"
                "    return {'cell': cell.name, 'correct': True, 'checks': {}, 'attempted': 1,\n"
                "            'failed': 0, 'memory_stats': [], 'seen': cell.config['model']['d_model'],\n"
                "            't_window': cell.t_process + 1.0, 'out_dir': cell.out_dir}\n")
    with open(os.path.join(bench_dir, "metrics", "width_seen.py"), "w") as f:
        f.write("def read(run):\n    return run['seen']\n")
    with open(os.path.join(bench_dir, "metrics", "nothing_to_read.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    with open(os.path.join(bench_dir, "workloads", "toy.echo.json"), "w") as f:
        json.dump({"runner": "echo"}, f)
    bench["workloads"].append({"name": "toy.echo", "config": "toy", "traffic": "echo",
                               "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.setdefault("workloads", [w["name"] for w in bench["workloads"] if w["name"] != "toy.echo"])
    for n in ("width_seen", "nothing_to_read"):
        bench["per_layer"].append({"name": n, "unit": "count", "better": "higher",
                                   "source": "program_counter", "layer": "toy",
                                   "moves": "setup_s", "workloads": ["toy.echo"]})
    next(m for m in bench["end_to_end"] if m["name"] == "setup_s")["workloads"].append("toy.echo")
    run = harness.run_cell(bench, "toy.echo", 1, 1.0, True, root=str(tmp_path),
                           bench_dir=bench_dir, require_chip=False)
    assert harness.read_metrics(bench, "per_layer", run, bench_dir) == {
        "width_seen": {"value": 64.0, "unit": "count"}}
    assert harness.read_metrics(bench, "end_to_end", run, bench_dir) == {
        "setup_s": {"value": 1.0, "unit": "s"}}


def _run_py(cwd, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), "--workload",
         "gpt2-medium.train-b8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_machine_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and "no accelerator" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_refuses_a_directory_without_the_program(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_py(str(tmp_path))
    assert p.returncode != 0 and "not beside the benchmark" in p.stderr
    assert not p.stdout.strip()
