"""The trainer loop's one timing source (ISSUE 26): ``StepClock`` writes the
loop's phases into the profiler's own trace as ``train.*`` annotations, keeps
real start stamps for the JSONL spans, and accounts for the whole iteration
(``step_time_s + between_s``). Also the op-name paths that the benchmark's
``spans.py`` classifies a chip trace by: ``head`` on the fused head + CE in
both passes, recomputation inside the backward pass only."""

from __future__ import annotations

import glob
import re
from dataclasses import replace

import pytest

from dtc_tpu.obs import StepClock, read_jsonl
from tests.conftest import make_train_cfg

PHASES = ("data_wait", "dispatch", "rng", "launch", "block", "obs", "tail")


def _train(tmp_path, tiny_model_cfg, opt_cfg, steps, **obs):
    from dtc_tpu.train.trainer import train

    cfg = make_train_cfg("dp", steps=steps, log_every=steps, output_dir=str(tmp_path),
                         warmup_steps=1)
    cfg = replace(cfg, obs=replace(cfg.obs, memory_sample_every=0, **obs))
    result = train(cfg, tiny_model_cfg, opt_cfg)
    return result, read_jsonl(str(tmp_path / "obs" / "events.r0.jsonl"))


# ---- (a) the profiler's trace holds the loop's spans ----------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A six-step toy run under a profiler window over steps 2..4, and every
    ``train*`` event of its trace as (name, step, start_ns, end_ns)."""
    from dtc_tpu.analysis.lowering import audit_model_cfg, audit_opt_cfg
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("traced")
    _, events = _train(tmp, audit_model_cfg(), audit_opt_cfg(), 6,
                       profile_start=2, profile_stop=5)
    paths = glob.glob(str(tmp / "profile" / "**" / "*.xplane.pb"), recursive=True)
    if not paths:
        pytest.skip("no profiler session could be opened in this process")
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "train" or e.name.startswith("train."):
                    stats = dict(e.stats)
                    step = stats.get("step_num", stats.get("step"))
                    spans.append((e.name, int(step), e.start_ns, e.start_ns + e.duration_ns))
    return spans, events


@pytest.mark.parametrize("step", [2, 3, 4])
def test_traced_step_has_its_group_and_every_phase(traced_run, step):
    spans, _ = traced_run
    mine = [s for s in spans if s[1] == step]
    groups = [s for s in mine if s[0] == "train"]
    assert len(groups) == 1, groups
    for ph in PHASES:
        found = [s for s in mine if s[0] == f"train.{ph}"]
        # one of each; `obs` twice where the next step's on_step_start was
        # traced too (the last traced step's wraps stop_trace and is lost)
        assert len(found) == (2 if ph == "obs" and step < 4 else 1), (ph, found)


@pytest.mark.parametrize("step", [2, 3, 4])
def test_traced_step_spans_nest_as_specified(traced_run, step):
    spans, _ = traced_run
    by = {}
    for name, s, t0, t1 in spans:
        if s == step:
            by.setdefault(name, []).append((t0, t1))
    (g0, g1), = by["train"]
    (d0, d1), = by["train.dispatch"]
    for inner in ("train.rng", "train.launch"):
        (a, b), = by[inner]
        assert d0 <= a <= b <= d1, inner
    assert by["train.rng"][0][1] <= by["train.launch"][0][0]
    # the clocked phases and the telemetry's work sit inside the group, in
    # loop order, and do not overlap
    order = ["train.data_wait", "train.dispatch", "train.block", "train.obs", "train.tail"]
    flat = [by[n][0] for n in order]
    for (a0, a1), (b0, b1) in zip(flat, flat[1:]):
        assert a1 <= b0
    assert g0 <= flat[0][0] and flat[-1][1] <= g1
    # on_step_start's own profiler / devprof calls: between two groups
    for t0, t1 in by["train.obs"][1:]:
        assert t0 >= g1


def test_no_span_outside_the_window(traced_run):
    spans, _ = traced_run
    assert {s[1] for s in spans} == {2, 3, 4}


# ---- (b) the clock accounts for the whole iteration -----------------------


def test_step_and_between_cover_the_stamps(tiny_model_cfg, opt_cfg, tmp_path):
    result, events = _train(tmp_path, tiny_model_cfg, opt_cfg, 12)
    steps = [e for e in events if e["etype"] == "step"]
    assert [e["step"] for e in steps] == list(range(1, 13))
    for e in steps:
        assert e["rng_s"] > 0 and e["launch_s"] > 0
        assert e["rng_s"] + e["launch_s"] <= e["dispatch_s"] + 2e-6
        assert e["other_s"] >= 0
    assert steps[0]["between_s"] == 0.0
    assert all(e["between_s"] > 0 for e in steps[1:])
    # stamps[i] is taken just before step i+1's end(): differences of the
    # stamps are end-to-end, which is what step + between adds up to
    stamps = result.elapsed_times
    covered = sum(e["step_time_s"] + e["between_s"] for e in steps[1:])
    assert covered == pytest.approx(stamps[-1] - stamps[0], rel=0.01)


def test_clock_fields_and_nesting_without_a_trainer():
    clock = StepClock()
    clock.begin(1)
    with clock.phase("dispatch"):
        with clock.phase("rng"):
            pass
        with clock.phase("launch"):
            pass
    first = clock.end()
    with clock.phase("obs"):
        pass
    clock.tail()
    clock.close()
    clock.close()  # idempotent
    clock.begin(2)
    second = clock.end()
    clock.close()
    assert set(first) == {"data_wait_s", "dispatch_s", "block_s", "rng_s", "launch_s",
                          "step_time_s", "other_s", "between_s"}
    assert first["between_s"] == 0.0 and second["between_s"] > 0.0
    # nested phases are not summed into other_s a second time
    assert first["other_s"] <= first["step_time_s"] - first["dispatch_s"] + 2e-6
    assert "obs" not in second and "tail_s" not in second


# ---- (c) JSONL spans are the clock's own stamps ---------------------------


def test_jsonl_spans_equal_the_clock_stamps(tiny_model_cfg, opt_cfg, tmp_path, monkeypatch):
    from dtc_tpu.obs import Telemetry

    seen = {}
    real_end = StepClock.end

    def end(self):
        out = real_end(self)
        seen[self.step] = (self.t0, dict(self.starts), out)
        return out

    offsets = []
    real_init = Telemetry.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        offsets.append(self._clock_offset)

    monkeypatch.setattr(StepClock, "end", end)
    monkeypatch.setattr(Telemetry, "__init__", init)
    _, events = _train(tmp_path, tiny_model_cfg, opt_cfg, 4)
    off = offsets[-1]
    # the start-up's spans (once a run, their own track) and a slow step's
    # span of its excess are not lines of every step
    spans = [e for e in events if e["etype"] == "span" and e["cat"] == "train"
             and e["name"] not in ("compile", "slow_step") and e["tid"] != "train.startup"]
    assert {e["name"] for e in spans} == {"step", "data_wait", "dispatch", "block"}
    assert {e["tid"] for e in spans if e["name"] == "step"} == {"train"}
    assert {e["tid"] for e in spans if e["name"] != "step"} == {"train.phase"}
    assert len(spans) == 4 * 4  # no new line a step
    for e in spans:
        t0, starts, out = seen[e["step"]]
        start = t0 if e["name"] == "step" else starts[e["name"]]
        dur = out["step_time_s"] if e["name"] == "step" else out[f"{e['name']}_s"]
        assert e["t0"] == pytest.approx(start + off, abs=2e-6)
        assert e["dur_s"] == pytest.approx(dur, abs=1e-6)
    # phases follow one another inside the step's span, on one clock
    for step in range(1, 5):
        mine = {e["name"]: e for e in spans if e["step"] == step}
        assert mine["step"]["t0"] <= mine["data_wait"]["t0"] + 2e-6
        assert (mine["data_wait"]["t0"] + mine["data_wait"]["dur_s"]
                <= mine["dispatch"]["t0"] + 2e-6)
        assert (mine["dispatch"]["t0"] + mine["dispatch"]["dur_s"]
                <= mine["block"]["t0"] + 2e-6)
        assert (mine["block"]["t0"] + mine["block"]["dur_s"]
                <= mine["step"]["t0"] + mine["step"]["dur_s"] + 4e-6)


# ---- (d) the compiled step's op-name paths --------------------------------


@pytest.fixture(scope="module")
def step_op_names():
    """Every ``op_name`` of the compiled toy train step (block remat, fused
    head + CE, clip): what a chip's trace carries in its ``tf_op`` stat."""
    from dtc_tpu.analysis.lowering import audit_model_cfg, audit_opt_cfg, compiled_train_hlo
    from dtc_tpu.config.schema import MeshConfig
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    text = compiled_train_hlo("dp", MeshConfig(), audit_model_cfg(remat="block"),
                              audit_opt_cfg(), DEFAULT_RULES)
    # the step's own instructions; a reduction's scalar sub-computation
    # carries a bare path without the jit(...) root and never runs alone
    return [n for n in re.findall(r'op_name="([^"]+)"', text)
            if n.startswith("jit(train_step)/")]


def test_recomputation_lives_inside_the_backward_pass(step_op_names):
    remat = [n for n in step_op_names if "rematted_computation" in n]
    assert remat, "block remat left no rematted_computation path"
    assert all("transpose(" in n for n in remat)


def test_head_and_ce_carry_head_in_both_passes(step_op_names):
    head = [n for n in step_op_names if "head" in n.split("/")]
    assert any("transpose(" in n for n in head), "no backward op under head"
    assert any("transpose(" not in n and "jvp(" in n for n in head)
    # every op of the fused head + CE backward rule (the ones-column matmul
    # and the logits' softmax recomputation) sits under the head module
    fused_bwd = [n for n in step_op_names
                 if "transpose(" in n and "GPT/" in n
                 and not any(seg in n.split("/") for seg in ("stage", "embed"))]
    assert fused_bwd and all("head" in n.split("/") for n in fused_bwd), [
        n for n in fused_bwd if "head" not in n.split("/")]


def test_clip_and_update_fall_under_optimizer(step_op_names):
    neither = [n for n in step_op_names if "jvp(" not in n and "transpose(" not in n]
    arithmetic = [n for n in neither
                  if n.rsplit("/", 1)[1] in ("reduce_sum", "sqrt", "mul", "add", "div", "sub")]
    # the global norm's reductions, the clip's scale and AdamW's update
    assert any(n.endswith("/reduce_sum") for n in arithmetic)
    assert any(n.endswith("/sqrt") for n in arithmetic)
    assert all(n.split("/")[1] == "optimizer" for n in arithmetic), [
        n for n in arithmetic if n.split("/")[1] != "optimizer"]
