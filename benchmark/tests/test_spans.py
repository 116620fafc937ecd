"""``spans.py``: the classifier and the idle charging on rows written by
hand, the reader on a toy traced run of the program on the CPU (host spans
only), and the decoder on a recorded chip trace."""

import os

import pytest

import run as harness
import spans
from spans import HOST, Span
from toy import BENCH, make
from xtrace import MODULES_LINE, OPS_LINE

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "train-b8.v5e.xplane.pb.gz")
STEP = "jit_train_step(1)"
ROOT_PATH = "jit(train_step)/"


def op(t0, dur, path="", name="%fusion.1 = f32[8] fusion(...)", dev=0):
    return Span(dev, OPS_LINE, name, t0, dur, path=path and ROOT_PATH + path)


def host(name, t0, dur, step=None):
    return Span(HOST, "main/1", name, t0, dur, step=step)


def device_rows(skew=0.0):
    """Four executions of a 10 s step program, 2 s apart, on device 0, whose
    clock runs ``skew`` s behind the host's. Each holds a forward op 0-3, a
    recomputed one 3-4, a backward one 4-7 (of which 6-7 under head), the
    update 7-9, a collective without a path 9-9.5 and 0.5 s of nothing. A
    small program runs 1.0-1.1 s after each execution's end."""
    rows = []
    for k in range(4):
        t = 12.0 * k - skew
        rows += [
            Span(0, MODULES_LINE, STEP, t, 10.0),
            op(t, 10.0, name="%while.1 = (s32[]) while(...)"),  # a container: never an op
            op(t + 0.0, 3.0, "jvp(fwd)/GPT/stage/blocks/mlp/dot_general"),
            op(t + 3.0, 1.0, "transpose(jvp(fwd))/GPT/stage/checkpoint/rematted_computation/mlp/dot_general"),
            op(t + 4.0, 2.0, "transpose(jvp(fwd))/GPT/stage/checkpoint/mlp/dot_general"),
            op(t + 6.0, 1.0, "transpose(jvp(fwd))/GPT/head/dot_general"),
            op(t + 7.0, 2.0, "optimizer/add"),
            op(t + 9.0, 0.5, name="%all-reduce.3 = f32[8] all-reduce(...)"),
            Span(0, MODULES_LINE, "jit__threefry_fold_in(2)", t + 11.0, 0.1),
            op(t + 11.0, 0.1, name="%copy.1 = u32[2] copy(...)"),
        ]
    # device 1 idles more: the busiest device is 0
    rows += [Span(1, MODULES_LINE, STEP, 12.0 * k, 10.0) for k in range(4)]
    rows += [op(12.0 * k, 5.0, "jvp(fwd)/x", dev=1) for k in range(4)]
    return rows


def host_rows():
    """The loop on the host's clock: step k's execution runs k*12 .. k*12+10.
    block ends 0.3 s after it; then obs 0.2, tail 0.1, (an uncovered 0.1),
    data_wait 0.1, dispatch 1.3: 0.1 of its own, rng 0.7, 0.1 of its own,
    launch 0.4 whose enqueue comes 0.3 s in, as the next execution starts."""
    rows = []
    for k in range(4):
        t, e = 12.0 * k, 12.0 * k + 10.0
        rows += [
            host("train", t - 1.2, 12.0, k),
            host("train.launch", t - 0.3, 0.4, k),
            host(spans.ENQUEUE, t, 0.05),
            host("train.block", t + 0.1, 10.2, k),
            host("ReadSyncFlag", e + 0.25, 0.01),
            host("train.obs", e + 0.3, 0.2, k),
            host("train.tail", e + 0.5, 0.1, k),
            host("train.data_wait", e + 0.7, 0.1, k + 1),
            host("train.dispatch", e + 0.8, 1.3, k + 1),
            host("train.rng", e + 0.9, 0.7, k + 1),
            host(spans.ENQUEUE, e + 1.0, 0.01),  # the small program's: not in a launch span
        ]
    return rows


def test_classifier_takes_the_first_class_that_fits():
    c = spans.classify
    assert c("jit(s)/transpose(jvp(fwd))/GPT/checkpoint/rematted_computation/mlp/dot") == "recompute"
    assert c("jit(s)/transpose(jvp(fwd))/GPT/head/dot_general") == "bwd"
    assert c("jit(s)/optimizer/add") == "optimizer" and c("jit(s)/clip/mul") == "optimizer"
    assert c("jit(s)/jvp(fwd)/GPT/head/reduce_sum") == "fwd" and c("jit(s)/fwd/x") == "fwd"
    assert c("jit(s)/jvp(fwd)/optimizer_like/x") == "fwd"      # a segment, not a substring
    assert c("", "%all-gather-start.2 = f32[8] all-gather-start(...)") == "collective"
    assert c("jit(s)/jvp(fwd)/x", "%all-gather.2 = f32[8] all-gather(...)") == "fwd"
    assert c("", "%convert.3 = bf16[8] convert(...)") is None
    assert spans.in_head("jit(s)/transpose(jvp(fwd))/GPT/head/dot_general")
    assert not spans.in_head("jit(s)/jvp(fwd)/GPT/stage/n_heads/x")


def test_self_time_leaves_out_what_nests_inside():
    rows = [op(0.0, 10.0), op(1.0, 2.0), op(4.0, 5.0), op(5.0, 1.0), op(20.0, 1.0)]
    assert spans.self_times(rows) == pytest.approx([3.0, 2.0, 4.0, 1.0, 1.0])


def test_phases_per_period_on_hand_written_rows():
    got = spans.reduce_rows(device_rows() + host_rows())
    m, d = got["metrics"], got["detail"]
    assert d["periods"] == 2 and d["device"] == 0 and d["program"] == STEP
    assert d["period_ms"] == pytest.approx(12e3)
    assert m["fwd_ms"] == pytest.approx(3e3) and m["recompute_ms"] == pytest.approx(1e3)
    assert m["bwd_ms"] == pytest.approx(3e3) and m["optimizer_ms"] == pytest.approx(2e3)
    assert m["head_ce_ms"] == pytest.approx(1e3)           # counted in bwd too
    assert d["collective_ms"] == pytest.approx(0.5e3)
    assert d["unnamed_ms"] == pytest.approx(0.1e3)         # the small program's copy
    assert m["scope_named_pct"] == pytest.approx(100 * 9.5 / 9.6)
    assert m["idle_in_step_ms"] == pytest.approx(0.5e3)
    assert d["busy_ms"] == pytest.approx(9.6e3)
    assert d["idle_between_ms"] == pytest.approx(1.9e3)    # 2.0 less the small program


def test_idle_between_steps_is_cut_at_span_borders():
    """The gap after an execution straddles block, obs, tail, a stretch under
    no span, data_wait, dispatch's own head, rng (less the small program that
    runs inside it) and launch up to the enqueue."""
    got = spans.reduce_rows(device_rows() + host_rows())
    m, d = got["metrics"], got["detail"]
    assert (d["shift_lo_ms"], d["shift_hi_ms"]) == pytest.approx((0.0, 250.0))
    assert m["host_device_skew_ms"] == 0.0                 # causal as it stands
    assert d["idle_by_span_ms"] == pytest.approx({
        "block": 300.0, "obs": 200.0, "tail": 100.0, "(no span)": 100.0, "data_wait": 100.0,
        "dispatch": 200.0, "rng": 600.0, "launch": 300.0})
    assert m["idle_wait_ms"] == pytest.approx(300.0)
    assert m["idle_rng_ms"] == pytest.approx(600.0)        # 0.7 s less the small program
    assert m["idle_launch_ms"] == pytest.approx(300.0)     # up to the enqueue
    assert m["idle_loop_ms"] == pytest.approx(200.0 + 100.0 + 100.0 + 200.0)
    assert m["idle_named_pct"] == pytest.approx(100 * 1.8 / 1.9)
    total = sum(m[f"idle_{k}_ms"] for k in ("wait", "rng", "launch", "loop"))
    assert total + 100.0 == pytest.approx(d["idle_between_ms"])


@pytest.mark.parametrize("skew", [0.6, -0.4])
def test_a_skewed_device_clock_is_shifted_by_the_least_causal_amount(skew):
    """A device whose timeline lies 0.6 s early starts before its enqueue:
    the least shift that cures it is 0.6 s, and charges as if unskewed. One
    that lies 0.4 s late ends after its notice: -0.15 s cures that."""
    got = spans.reduce_rows(device_rows(skew=skew) + host_rows())
    m, d = got["metrics"], got["detail"]
    assert (d["shift_lo_ms"], d["shift_hi_ms"]) == pytest.approx((1e3 * skew, 250.0 + 1e3 * skew))
    if skew > 0:
        assert m["host_device_skew_ms"] == pytest.approx(600.0)
        assert m["idle_wait_ms"] == pytest.approx(300.0) and m["idle_rng_ms"] == pytest.approx(600.0)
    else:
        assert m["host_device_skew_ms"] == pytest.approx(150.0)
        # the device now ends 0.25 s into what was 0.3 s of waiting, and
        # starts 0.15 s into the next step's block
        assert m["idle_wait_ms"] == pytest.approx(50.0 + 150.0)
        assert m["idle_launch_ms"] == pytest.approx(400.0)


def test_no_causal_shift_reads_no_idle_metric():
    """Enqueue after the start by more than the notice allows: least > largest."""
    rows = [r if r.name != "ReadSyncFlag" else host(r.name, r.t0 - 0.5, r.dur)
            for r in host_rows()]
    got = spans.reduce_rows(device_rows() + rows)
    assert got["detail"]["shift_lo_ms"] > got["detail"]["shift_hi_ms"]
    assert spans.least_shift(0.0, -0.25) is None
    assert not [k for k in got["metrics"] if k.startswith(("idle_w", "idle_r", "idle_l", "idle_n", "host_"))]
    assert got["metrics"]["fwd_ms"] == pytest.approx(3e3)  # the device's own stay


def test_a_program_without_spans_reads_the_device_metrics_only():
    """The parent of PR 26 writes no ``train.*`` span: nothing raises."""
    got = spans.reduce_rows(device_rows())
    assert set(got["metrics"]) == {"idle_in_step_ms", "fwd_ms", "bwd_ms", "recompute_ms",
                                   "optimizer_ms", "head_ce_ms", "scope_named_pct"}
    assert spans.reduce_rows([]) is None
    assert spans.reduce_rows(device_rows()[:12]) is None   # two executions: no period to keep
    assert spans.metric({"profile_dir": None}, "fwd_ms") is None


def test_every_new_metric_has_its_reader_and_its_entry():
    import json

    from toy import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    run = {"profile_dir": "x", "trace": {"spans": spans.reduce_rows(device_rows() + host_rows())}}
    names = sorted(run["trace"]["spans"]["metrics"])
    assert len(names) == 13
    for name in names:
        entry = entries[f"{name}.train"]
        assert entry["moves"] == "train_tokens_per_s" and "workloads" not in entry
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        value = harness.load_module("metrics", f"{name}.train").read(run)
        assert value == run["trace"]["spans"]["metrics"][name]


def test_toy_traced_run_on_the_cpu_reads_nothing_and_raises_nothing(tmp_path):
    """The program's own profiler window on the CPU: the trace holds the
    ``train.*`` spans with their steps and no device plane, so every reader
    returns None and the line leaves the metrics out."""
    bench, bench_dir = make(str(tmp_path), rows=8)
    run = harness.run_cell(bench, "toy.train", seed=5, seconds=1.5, trace=True,
                           root=str(tmp_path), bench_dir=bench_dir, require_chip=False)
    path = spans.find_xplane(run["profile_dir"])
    assert path, "the toy run stopped before its profiler window"
    rows = spans.rows_from_xspace(spans.read_xspace(path))
    mine = [r for r in rows if r.device == HOST and r.name.startswith(spans.SPAN)]
    assert {r.name for r in mine} >= {"train." + p for p in
                                      ("data_wait", "dispatch", "rng", "launch", "block", "obs", "tail")}
    assert {r.step for r in mine if r.name == "train.launch"} == {3, 4, 5}   # trace_steps [3, 6)
    assert spans.read(run) is None
    for name in spans.reduce_rows(device_rows() + host_rows())["metrics"]:
        assert harness.load_module("metrics", f"{name}.train", bench_dir).read(run) is None


# ---- the recorded chip trace ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return spans.rows_from_xspace(spans.read_xspace(FIXTURE))


def test_decoder_joins_ops_to_their_paths_on_a_chip_trace(recorded):
    ops = [r for r in recorded if r.line == OPS_LINE]
    assert len(ops) > 1000 and {r.device for r in ops} == {0}
    with_path = [r for r in ops if r.path]
    # all but a parameter's layout copy, which is named by its argument
    assert sum(r.path.startswith("jit(train_step)/") for r in with_path) > 0.99 * len(with_path)
    assert sum(r.dur for r in with_path) > 0.9 * sum(
        r.dur for r in ops if spans.base_op(r.name) not in ("while", "conditional", "call"))
    kinds = {spans.classify(r.path, r.name) for r in ops}
    assert {"fwd", "bwd", "recompute", "optimizer"} <= kinds
    assert any(spans.in_head(r.path) and "transpose(" in r.path for r in ops)
    assert any(spans.in_head(r.path) and "transpose(" not in r.path for r in ops)
    flash = [r for r in ops if 'custom_call_target="tpu_custom_call"' in r.name]
    assert flash and all("attn_kernel" in r.path.split("/") for r in flash)


def test_recorded_trace_holds_the_programs_spans_with_their_steps(recorded):
    mine = [r for r in recorded if r.device == HOST and r.name.startswith(spans.SPAN)]
    launches = [r for r in mine if r.name == "train.launch"]
    assert len(launches) == 3 and all(r.step is not None for r in mine)
    assert sorted({r.step for r in launches}) == list(range(launches[0].step, launches[0].step + 3))
    assert any(r.name == spans.ENQUEUE for r in recorded)
    assert any(r.name in spans.NOTICES for r in recorded)


def test_recorded_trace_reduces_to_all_thirteen(recorded):
    got = spans.reduce_rows(recorded)
    m, d = got["metrics"], got["detail"]
    assert len(m) == 13 and all(v is not None for v in m.values())
    assert d["periods"] == 1 and d["shift_lo_ms"] <= d["shift_hi_ms"]
    assert m["idle_named_pct"] >= 95 and m["scope_named_pct"] >= 95
    idle = sum(m[f"idle_{k}_ms"] for k in ("wait", "rng", "launch", "loop"))
    assert idle == pytest.approx(d["idle_between_ms"] * m["idle_named_pct"] / 100)
    phases = sum(m[f"{k}_ms"] for k in ("fwd", "bwd", "recompute", "optimizer"))
    assert phases + d["collective_ms"] + d["unnamed_ms"] == pytest.approx(d["busy_ms"], rel=0.01)
    assert d["busy_ms"] + m["idle_in_step_ms"] + d["idle_between_ms"] == pytest.approx(d["period_ms"])
