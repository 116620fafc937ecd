"""The ``train_ref`` runner and ``reference_lfm2_moe.py`` at toy size, as
``test_correct_qwen3_next.py`` does for its family: the program against the
reference in float32, a sound run correct with its counters read, the int8
control and the planted faults not."""

import json
import os
import shutil

import pytest

import compare
import run as harness
from toy import BENCH, ROOT

CELL = "lfm2-8b-a1b.train-ep4share-8k"
TOY = {
    "vocab_size": 256, "d_model": 64, "n_layers": 5, "n_heads": 4, "d_ff": 96, "max_seq_len": 128,
    "dropout": 0.0, "param_dtype": "float32", "compute_dtype": "bfloat16", "attention": "auto",
    "vocab_pad_multiple": 128,
    "leading_pattern": ["shortconv+swiglu"],
    "layer_pattern": ["attn+moe", "shortconv+moe", "shortconv+moe", "shortconv+moe"],
    "norm_eps": 1e-05, "norm_gain": "plain", "tie_embeddings": True,
    "n_kv_heads": 1, "attn_head_dim": 16, "rope_theta": 1000000.0, "rope_fraction": 1.0,
    "shortconv_width": 3,
    "moe_experts": 8, "moe_top_k": 2, "moe_experts_held": 2, "moe_expert_rank": 0,
    "moe_d_ff": 32, "moe_score": "sigmoid", "moe_selection_bias": True, "moe_routed_scale": 1.0,
}
# A toy's leaves are small and its bfloat16 rounding coarse, and with 8
# experts a flipped top-2 choice moves half of a token's routed sum: the toy
# is held to limits of its own, under which a sound run is correct and the
# control and each planted fault are not.
TOY_LIMITS = {"grad1": 0.15, "dparam3": 0.08}


def _files():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        return config, json.load(f)


def make(tmp: str, *, compute: str = "bfloat16", limits: dict | None = None) -> tuple[dict, str]:
    bench_dir = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("out", "__pycache__"))
    config, wl = _files()
    toy = {"model": {**TOY, "compute_dtype": compute}, "reference": config["reference"],
           "leaf_names": config["leaf_names"]}
    with open(os.path.join(bench_dir, "configs", "toy-lfm2.json"), "w") as f:
        json.dump(toy, f)
    wl["traffic"]["rows"] = 8
    wl["train"]["mesh"] = {"pipe": 1, "data": 4, "model": 1}  # the CPU backend's four devices
    wl["train"]["model"] = {"remat": "block"}
    wl["limits"] = limits or TOY_LIMITS
    with open(os.path.join(bench_dir, "workloads", "toy-lfm2.train.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-lfm2", "source": "none: a test's toy", "reduced": [],
                             "file": "benchmark/configs/toy-lfm2.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-lfm2.train", "config": "toy-lfm2",
                               "traffic": "train", "chips": 1, "why": "toy"})
    for metric in bench["per_layer"]:  # the toy reports what the cell reports
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("toy-lfm2.train")
    return bench, bench_dir


def toy_run(tmp_path, **kw):
    bench, bench_dir = make(str(tmp_path), **kw)
    run = harness.run_cell(bench, "toy-lfm2.train", seed=2**31 + 77, seconds=0.3, trace=False,
                           root=str(tmp_path), bench_dir=bench_dir, require_chip=False)
    return bench, bench_dir, run


def test_reference_agrees_with_the_program_in_float32(tmp_path):
    _, _, run = toy_run(tmp_path, compute="float32",
                        limits={"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5, "grad1": 2e-3, "dparam3": 2e-3})
    assert run["correct"], run["checks"]
    assert len(run["step_ends"]) >= 2 and run["failed"] == 0
    # the selection bias never moves but by AdamW's decay: left out of dparam3 by its zero gradient
    assert run["checks"]["dparam3"]["left_out"] >= 4


def _cpu_has_the_chip_s_peaks(monkeypatch):
    import peaks

    monkeypatch.setattr(peaks, "PEAKS", {**peaks.PEAKS, "cpu": peaks.PEAKS["TPU v5 lite"]})


def test_sound_run_is_correct_and_its_counters_are_read(tmp_path, monkeypatch):
    bench, bench_dir, run = toy_run(tmp_path)
    assert run["correct"], run["checks"]
    read = lambda name: harness.load_module("metrics", name).read(run)  # noqa: E731
    assert read("recompiles.train") == 0 and read("moe_dropped.train") == 0
    assert 1.0 <= read("moe_load_max_over_mean.train") < 4.0
    assert 0.0 < read("moe_held_swing_pct.train") < 40.0  # the scatter of 8 rows x 128 tokens on 2 held experts
    assert 1.0 < read("moe_bias_swapped_pct.train") < 50.0  # the bias is in the choice
    for traced in ("shortconv_ms.train", "shortconv_roofline.train", "mlp_ms.train",
                   "full_attn_roofline.train.lfm2-moe", "moe_experts_roofline.train.lfm2-moe"):
        assert read(traced) is None          # no trace: nothing to read, nothing raised
    _cpu_has_the_chip_s_peaks(monkeypatch)   # mfu reads a peak: only its being read is checked here
    per_layer = harness.read_metrics(bench, "per_layer", run, bench_dir)
    assert {"moe_bias_swapped_pct.train", "moe_dropped.train", "recompiles.train",
            "mfu.train.lfm2-moe"} <= set(per_layer)
    assert not {"gdn_ms.train", "mfu.train.qwen3-next", "flash_roofline.train"} & set(per_layer)


def test_mfu_reads_the_counted_assignments(tmp_path, monkeypatch):
    """``mfu.train.lfm2-moe`` on a recorded run: the step's operations with
    the held assignments the events counted, over the window, over the peak."""
    import flops_lfm2_moe as flops

    config, wl = _files()
    model = config["model"]
    events = [{"etype": "moe_counters", "step": s, "moe_assigned_held": [30000.0, 34000.0, 32768.0, 33000.0]}
              for s in (1, 2)]
    run = {"model": model, "workload": wl, "chips": 1, "events": events, "step_ends": [0.5, 1.0]}
    _cpu_has_the_chip_s_peaks(monkeypatch)
    got = harness.load_module("metrics", "mfu.train.lfm2-moe").read(run)
    want = 100.0 * flops.train_step_flops(model, 4, 8192, 129768.0) / 0.5 / 197e12
    assert got == pytest.approx(want, rel=1e-12) and 40.0 < got < 45.0
    assert harness.load_module("metrics", "mfu.train.lfm2-moe").read(
        {**run, "model": {"n_layers": 24}}) is None    # no layer pattern: not this family's count


def _follow(**how):
    runner = harness.load_module("runners", "train_ref")
    _, wl = _files()
    wl["traffic"]["rows"] = 8
    run = {"workload": wl, "model": TOY, "seed": 2**31 + 12, "chips": 1, "optim": wl["optim"],
           "reference_module": "reference_lfm2_moe"}
    return compare.judge(compare.readings(runner.follow(run, **how), runner.follow(run)), TOY_LIMITS)


@pytest.mark.parametrize("how", [{"matmul": "int8"}, {"rows": slice(0, 4)}, {"frozen": True}],
                         ids=["int8_control", "half_of_the_batch", "state_unchanged"])
def test_control_and_faults_are_not_correct(how):
    """The reference with int8 matmuls, on half the rows, or with its state
    unchanged, put in the program's place."""
    ok, checks = _follow(**how)
    assert not ok, checks
    if "frozen" in how:
        assert checks["dparam3"]["value"] == pytest.approx(1.0, abs=1e-3)
