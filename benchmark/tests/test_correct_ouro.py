"""The ``train_ref`` runner and ``reference_ouro.py`` at toy size, as
``test_correct_lfm2_moe.py`` does for its family: the reference against a
second, unblocked writing of the equations; the program against the
reference in float32; a sound bfloat16 run correct with its pass counters
read; the int8 control and the planted faults not; ``flops_ouro.py`` against
a count by hand."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import run as harness
from toy import BENCH, ROOT

CELL = "ouro-2.6b.train-loop4-b2x4096"
TOY = {
    "vocab_size": 256, "d_model": 64, "n_layers": 3, "n_heads": 4, "d_ff": 96, "max_seq_len": 128,
    "dropout": 0.0, "param_dtype": "float32", "compute_dtype": "bfloat16", "attention": "auto",
    "vocab_pad_multiple": 128,
    "layer_pattern": ["attn+swiglu"], "stack_passes": 4,
    "norm_placement": "sandwich", "qk_norm": False, "norm_eps": 1e-06, "norm_gain": "plain",
    "tie_embeddings": False, "n_kv_heads": 4, "attn_head_dim": 16,
    "rope_theta": 1000000.0, "rope_fraction": 1.0,
}
# A toy's leaves are small and its bfloat16 rounding coarse: the toy is held
# to limits of its own, under which a sound run is correct and the control
# and each planted fault are not (the cell's own limits come from the chip).
TOY_LIMITS = {"grad1": 0.05, "dparam3": 0.02}


def _files():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        return config, json.load(f)


def make(tmp: str, *, compute: str = "bfloat16", limits: dict | None = None) -> tuple[dict, str]:
    bench_dir = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("out", "__pycache__"))
    config, wl = _files()
    toy = {"model": {**TOY, "compute_dtype": compute}, "reference": config["reference"],
           "leaf_names": config["leaf_names"]}
    with open(os.path.join(bench_dir, "configs", "toy-ouro.json"), "w") as f:
        json.dump(toy, f)
    wl["traffic"]["rows"] = 8
    wl["train"]["mesh"] = {"pipe": 1, "data": 4, "model": 1}  # the CPU backend's four devices
    wl["limits"] = limits or TOY_LIMITS
    with open(os.path.join(bench_dir, "workloads", "toy-ouro.train.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-ouro", "source": "none: a test's toy", "reduced": [],
                             "file": "benchmark/configs/toy-ouro.json", "why": "toy"})
    bench["workloads"].append({"name": "toy-ouro.train", "config": "toy-ouro",
                               "traffic": "train", "chips": 1, "why": "toy"})
    for metric in bench["per_layer"]:  # the toy reports what the cell reports
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("toy-ouro.train")
    return bench, bench_dir


def toy_run(tmp_path, **kw):
    bench, bench_dir = make(str(tmp_path), **kw)
    run = harness.run_cell(bench, "toy-ouro.train", seed=2**31 + 77, seconds=0.3, trace=False,
                           root=str(tmp_path), bench_dir=bench_dir, require_chip=False)
    return bench, bench_dir, run


# ---------------------------------------------------------------------------
# the reference against the equations written once more, with no blocking


def _plain_loss(w: dict, x, y, model: dict):
    """The module docstring of ``reference_ouro.py`` again: Python loops over
    passes and layers, the whole score matrix, every logit at once, no
    ``jax.checkpoint``; shares no function with the reference."""
    eps, t = model["norm_eps"], x.shape[1]
    heads, hd = model["n_heads"], model["attn_head_dim"]

    def rms(a, g):
        return g * a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)

    freq = model["rope_theta"] ** (-np.arange(0, hd, 2) / hd)
    ang = np.arange(t)[:, None] * freq[None, :]
    cos, sin = (jnp.asarray(np.concatenate([f(ang), f(ang)], -1), jnp.float32)[None, :, None, :]
                for f in (np.cos, np.sin))

    def rope(a):
        return a * cos + jnp.concatenate([-a[..., hd // 2:], a[..., : hd // 2]], -1) * sin

    h = w["wte"][x]
    ce, z = [], []
    for _ in range(model["stack_passes"]):
        u = h
        for i in range(model["n_layers"]):
            p = {k[len("blocks.0."):]: v[i] for k, v in w.items() if k.startswith("blocks.0.")}
            a = rms(u, p["norm1.g"])
            q, k, v = ((a @ p[f"attn.{n}.w"]).reshape(*a.shape[:2], heads, hd) for n in "qkv")
            s = jnp.einsum("bqhd,bkhd->bhqk", rope(q), rope(k)) / np.sqrt(hd)
            s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v).reshape(*a.shape[:2], heads * hd)
            u = u + rms(o @ p["attn.o.w"], p["norm1_post.g"])
            a = rms(u, p["norm2.g"])
            u = u + rms((jax.nn.silu(a @ p["mlp.gate.w"]) * (a @ p["mlp.up.w"])) @ p["mlp.down.w"],
                        p["norm2_post.g"])
        h = rms(u, w["norm_f.g"])
        logp = jax.nn.log_softmax((h @ w["head.w"])[..., : model["vocab_size"]], -1)
        ce.append(-jnp.take_along_axis(logp, y[..., None], -1)[..., 0])
        z.append((h @ w["exit.w"])[..., 0] + w["exit.b"][0])
    lam = [jax.nn.sigmoid(zt) for zt in z]
    left, p = 1.0, []
    for lt in lam[:-1]:
        p.append(lt * left)
        left = left * (1.0 - lt)
    p.append(left)
    per_token = sum(pt * c for pt, c in zip(p, ce)) + 0.1 * sum(pt * jnp.log(pt) for pt in p)
    return jnp.mean(per_token)


def test_reference_agrees_with_an_unblocked_writing_of_the_equations():
    import reference_ouro as ref

    w = ref.make_weights(TOY, jnp.asarray(ref.seed_words(11)))
    w["exit.b"] = w["exit.b"] + 0.3   # off the symmetric point, so a sign error in the gate shows
    batch = jnp.asarray(np.random.default_rng(3).integers(0, 256, (2, 129)), jnp.int32)
    x, y = batch[:, :-1], batch[:, 1:]
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(_plain_loss)(w, x, y, TOY)
        got, gg = jax.value_and_grad(ref.loss_fn)(w, x, y, TOY)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for name in gw:
        scale = float(jnp.max(jnp.abs(gw[name])))
        assert scale > 0 and float(jnp.max(jnp.abs(gg[name] - gw[name]))) <= 2e-4 * scale, name


# ---------------------------------------------------------------------------
# the program through the runner


def test_reference_agrees_with_the_program_in_float32(tmp_path):
    _, _, run = toy_run(tmp_path, compute="float32",
                        limits={"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5, "grad1": 2e-3, "dparam3": 2e-3})
    assert run["correct"], run["checks"]
    assert len(run["step_ends"]) >= 2 and run["failed"] == 0
    # the gate's gradient is not negligible beside the median leaf's: nothing is left out
    assert run["checks"]["dparam3"]["left_out"] == 0


def _cpu_has_the_chip_s_peaks(monkeypatch):
    import peaks

    monkeypatch.setattr(peaks, "PEAKS", {**peaks.PEAKS, "cpu": peaks.PEAKS["TPU v5 lite"]})


def test_sound_run_is_correct_and_its_counters_are_read(tmp_path, monkeypatch):
    bench, bench_dir, run = toy_run(tmp_path)
    assert run["correct"], run["checks"]
    read = lambda name: harness.load_module("metrics", name).read(run)  # noqa: E731
    assert read("recompiles.train") == 0
    assert 1.7 < read("loop_expected_passes.train") < 2.1      # p about [.5, .25, .125, .125]
    events = [e for e in run["events"] if e.get("etype") == "pass_counters"]
    assert len(events) == len(run["step_ends"]) and not [e for e in run["events"] if e.get("etype") == "moe_counters"]
    assert len(events[0]["exit_p"]) == 4 and sum(events[0]["exit_p"]) == pytest.approx(1.0, abs=1e-5)
    assert all(5.0 < c < 6.5 for c in events[0]["pass_ce"]) and 1.1 < events[0]["exit_entropy"] < 1.3
    plan = next(e for e in run["events"] if e.get("etype") == "layer_plan")
    assert (plan["passes"], plan["norm_placement"]) == (4, "sandwich")
    for traced in ("exit_ms.train", "mlp_ms.train", "full_attn_roofline.train.ouro"):
        assert read(traced) is None          # no trace: nothing to read, nothing raised
    _cpu_has_the_chip_s_peaks(monkeypatch)   # mfu reads a peak: only its being read is checked here
    per_layer = harness.read_metrics(bench, "per_layer", run, bench_dir)
    assert {"loop_expected_passes.train", "recompiles.train", "mfu.train.ouro"} <= set(per_layer)
    assert not {"moe_dropped.train", "mfu.train.lfm2-moe", "mfu.train.qwen3-next", "flash_roofline.train"} & set(per_layer)


def test_new_readers_read_nothing_from_a_program_without_passes(monkeypatch):
    """The parent's program emits no ``pass_counters`` and has no ``exit``
    scope: the readers this cell brings return None there and raise nothing."""
    _cpu_has_the_chip_s_peaks(monkeypatch)
    config, wl = _files()
    run = {"model": {"n_layers": 24, "max_seq_len": 1024}, "workload": wl, "chips": 1,
           "events": [{"etype": "step", "step": 1}], "step_ends": [0.5, 1.0], "profile_dir": None}
    for name in ("loop_expected_passes.train", "exit_ms.train", "mfu.train.ouro", "full_attn_roofline.train.ouro"):
        assert harness.load_module("metrics", name).read(run) is None, name
    looped = {**run, "model": config["model"],
              "events": [{"etype": "pass_counters", "step": s, "exit_p": [0.5, 0.25, 0.125, 0.125]} for s in (1, 2)]}
    expected_passes = harness.load_module("metrics", "loop_expected_passes.train")
    assert expected_passes.read(looped) == pytest.approx(1.875)
    # a fixed set of steps: what a window trains past the first STEPS is not read
    further = [{"etype": "pass_counters", "step": s, "exit_p": [0.5, 0.25, 0.125, 0.125]} for s in range(1, 17)]
    further += [{"etype": "pass_counters", "step": s, "exit_p": [0.0, 0.0, 0.0, 1.0]} for s in range(17, 28)]
    assert expected_passes.STEPS == 16 and expected_passes.read({**looped, "events": further}) == pytest.approx(1.875)
    got = harness.load_module("metrics", "mfu.train.ouro").read(looped)
    assert got == pytest.approx(100.0 * 113_799_453_474_816 / 0.5 / 197e12, rel=1e-9)


# ---------------------------------------------------------------------------
# the control and the planted faults


def _follow(**how):
    runner = harness.load_module("runners", "train_ref")
    _, wl = _files()
    wl["traffic"]["rows"] = 8
    run = {"workload": wl, "model": TOY, "seed": 2**31 + 12, "chips": 1, "optim": wl["optim"],
           "reference_module": "reference_ouro"}
    return compare.judge(compare.readings(runner.follow(run, **how), runner.follow(run)), TOY_LIMITS)


@pytest.mark.parametrize("how", [
    {"matmul": "int8"}, {"rows": slice(0, 4)}, {"frozen": True},
    {"fault": "three_passes"}, {"fault": "norm_not_fed_back"}, {"fault": "gated_last_pass"},
    {"fault": "no_entropy"}, {"fault": "no_post_norms"},
], ids=["int8_control", "half_of_the_batch", "state_unchanged", "three_passes", "norm_not_fed_back",
        "gated_last_pass", "no_entropy", "no_post_norms"])
def test_control_and_faults_are_not_correct(how):
    """The reference with int8 matmuls, on half the rows, with its state
    unchanged, or with one of the model's planted faults, put in the
    program's place."""
    ok, checks = _follow(**how)
    assert not ok, checks
    if "frozen" in how:
        assert checks["dparam3"]["value"] == pytest.approx(1.0, abs=1e-3)


# ---------------------------------------------------------------------------
# the count


def test_flops_by_hand():
    import flops_ouro as flops

    model = _files()[0]["model"]
    per = flops.matmul_params(model)
    # a token and layer application: 4 x 2048^2 + 3 x 2048 x 5632 matmul parameters, 2 operations each
    assert 2 * (per["attn"] + per["swiglu"]) == 2 * (16_777_216 + 34_603_008) == 102_760_448
    assert 2 * per["head"] == 201_326_592
    tokens = 2 * 4096
    causal = 12 * 2 * 4096**2 * 16 * 128 / 2 / tokens           # forward + backward, a token and application
    assert causal / 3 == 16_777_216                              # the ISSUE's 16.8 MFLOP forward
    by_hand = tokens * (3 * (32 * 102_760_448 + 4 * 201_326_592) + 32 * causal)
    assert flops.train_step_flops(model, 2, 4096) == by_hand
    assert 113.7e12 < by_hand < 113.9e12 and flops.layer_applications(model) == 32
    assert 0.17 < flops.head_share(model, 2, 4096) < 0.18       # six times the whole model's share
    whole = {**model, "n_layers": 48}
    assert 0.032 < flops.head_share(whole, 2, 4096) < 0.036
    assert flops.full_attn_step_flops(model, 2, 4096) == 32 * 7 * 2 * 2 * 4096**2 * 16 * 128 / 2
    q = 2 * 4096 * 16 * 128 * 2
    assert flops.full_attn_step_bytes(model, 2, 4096) == 32 * (12 * q + 2 * 2 * 4096 * 16 * 4)
