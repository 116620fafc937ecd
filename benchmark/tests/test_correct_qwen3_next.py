"""The ``train_ref`` runner and ``reference_qwen3_next.py`` at toy size, as
``test_correct.py`` does for GPT-2: the program against the reference in
float32, a sound run correct, the int8 control and the planted faults not."""

import json
import os
import shutil

import pytest

import compare
import run as harness
from toy import BENCH, ROOT

CELL = "qwen3-next-80b-a3b.train-ep16share-b2x8192"
TOY = {
    "vocab_size": 250, "d_model": 64, "n_layers": 4, "n_heads": 2, "d_ff": 32, "max_seq_len": 128,
    "dropout": 0.0, "param_dtype": "float32", "compute_dtype": "bfloat16", "attention": "auto",
    "vocab_pad_multiple": 128,
    "layer_pattern": ["gdn+moe_shared", "gdn+moe_shared", "gdn+moe_shared", "gated_attn+moe_shared"],
    "norm_eps": 1e-06, "n_kv_heads": 1, "attn_head_dim": 32, "rope_theta": 10000000.0, "rope_fraction": 0.25,
    "gdn_key_heads": 2, "gdn_value_heads": 4, "gdn_key_dim": 16, "gdn_value_dim": 16,
    "gdn_conv_width": 4,
    "moe_experts": 8, "moe_top_k": 2, "moe_experts_held": 4, "moe_expert_rank": 0,
    "moe_d_ff": 32, "moe_shared_d_ff": 32,
}
# A toy's leaves are small and its bfloat16 rounding coarse, and with 8
# experts a flipped top-2 choice moves a quarter of a token's routed sum: the
# toy is held to limits of its own, under which a sound run is correct and
# the control and each planted fault are not.
TOY_LIMITS = {"grad1": 0.15, "dparam3": 0.08}


def make(tmp: str, *, compute: str = "bfloat16", limits: dict | None = None) -> tuple[dict, str]:
    bench_dir = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("out", "__pycache__"))
    with open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    toy = {"model": {**TOY, "compute_dtype": compute}, "reference": config["reference"],
           "leaf_names": config["leaf_names"]}
    with open(os.path.join(bench_dir, "configs", "toy-pattern.json"), "w") as f:
        json.dump(toy, f)
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        wl = json.load(f)
    wl["traffic"]["rows"] = 8
    wl["train"]["mesh"] = {"pipe": 1, "data": 4, "model": 1}  # the CPU backend's four devices
    wl["train"]["model"] = {"remat": "block"}
    wl["limits"] = limits or TOY_LIMITS
    with open(os.path.join(bench_dir, "workloads", "toy-pattern.train.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-pattern", "source": "none: a test's toy", "reduced": [],
                             "file": "benchmark/configs/toy-pattern.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-pattern.train", "config": "toy-pattern",
                               "traffic": "train", "chips": 1, "why": "toy"})
    return bench, bench_dir


def toy_run(tmp_path, **kw):
    bench, bench_dir = make(str(tmp_path), **kw)
    return harness.run_cell(bench, "toy-pattern.train", seed=2**31 + 77, seconds=0.3, trace=False,
                            root=str(tmp_path), bench_dir=bench_dir, require_chip=False)


def test_reference_agrees_with_the_program_in_float32(tmp_path):
    run = toy_run(tmp_path, compute="float32",
                  limits={"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5, "grad1": 2e-3, "dparam3": 2e-3})
    assert run["correct"], run["checks"]
    assert len(run["step_ends"]) >= 2 and run["failed"] == 0


def test_sound_run_is_correct_and_its_counters_are_read(tmp_path):
    run = toy_run(tmp_path)
    assert run["correct"], run["checks"]
    read = lambda name: harness.load_module("metrics", name).read(run)  # noqa: E731
    assert read("recompiles.train") == 0 and read("moe_dropped.train") == 0
    assert 1.0 <= read("moe_load_max_over_mean.train") < 4.0
    assert 0.0 < read("moe_held_swing_pct.train") < 20.0  # the scatter of 8 rows x 128 tokens
    assert read("gdn_ms.train") is None          # no trace: nothing to read, nothing raised
    assert read("full_attn_roofline.train") is None


def _follow(**how):
    runner = harness.load_module("runners", "train_ref")
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        wl = json.load(f)
    wl["traffic"]["rows"] = 8
    run = {"workload": wl, "model": TOY, "seed": 2**31 + 12, "chips": 1, "optim": wl["optim"],
           "reference_module": "reference_qwen3_next"}
    return compare.judge(compare.readings(runner.follow(run, **how), runner.follow(run)), TOY_LIMITS)


@pytest.mark.parametrize("how", [{"matmul": "int8"}, {"rows": slice(0, 4)}, {"frozen": True}],
                         ids=["int8_control", "half_of_the_batch", "state_unchanged"])
def test_control_and_faults_are_not_correct(how):
    """The reference with int8 matmuls, on half the rows, or with its state
    unchanged, put in the program's place."""
    ok, checks = _follow(**how)
    assert not ok, checks
    if "frozen" in how:
        # not exactly 1 at lr 1e-05: the comparison makes the first weights
        # again in a program of its own, an ulp apart on a few elements,
        # against a reference change of 3e-5 (0.99997 here; the limit is 0.08)
        assert checks["dparam3"]["value"] == pytest.approx(1.0, abs=1e-3)
