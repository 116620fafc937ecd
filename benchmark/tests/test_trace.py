"""The trace reduction on rows written by hand, and on the program's
recorded Chrome-trace capture."""

import os

import pytest

import xtrace
from toy import BENCH, ROOT
from xtrace import HOST, OPS_LINE, Row


def rows():
    op = lambda d, name, t0, dur: Row(d, OPS_LINE, name, t0, dur)  # noqa: E731
    return [
        # device 0: a while (container) over everything; compute 0-4 and 6-9,
        # a collective 3-7 (exposed 4-6), a flash call inside the compute.
        op(0, "%while.1 = (s32[]) while(...)", 0.0, 10.0),
        op(0, "%fusion.3 = f32[8] fusion(...)", 0.0, 4.0),
        op(0, "%all-gather-start.2 = f32[8] all-gather-start(...)", 3.0, 4.0),
        op(0, '%attn_kernel.13 = bf16[8] custom-call(...), custom_call_target="tpu_custom_call"', 6.0, 1.5),
        op(0, "%fusion.4 = f32[8] fusion(...)", 7.5, 1.5),
        # device 1: busy 1-3 only.
        op(1, "%fusion.3 = f32[8] fusion(...)", 1.0, 2.0),
        Row(0, "XLA Modules", "jit_train_step(1)", 0.0, 9.0),
        Row(0, "XLA Modules", "jit__threefry_fold_in(2)", 9.5, 0.01),
        Row(HOST, "python#0", "PjitFunction(train_step)", 0.0, 0.1),
        Row(HOST, "python#1", "$queue.py:1 put", 0.0, 10.0),
    ]


def test_hand_computed_figures():
    t = xtrace.reduce_rows(rows())
    assert t["window_s"] == pytest.approx(9.0)          # 0.0 .. 9.0
    assert t["per_device_busy_s"] == pytest.approx([9.0, 2.0])
    assert t["busy_s"] == pytest.approx(5.5)            # mean over the devices
    assert t["collective_exposed_s"] == pytest.approx(2.0)  # 4.0 .. 6.0 on device 0
    assert t["kernels_s"] == pytest.approx({"attn_kernel": 1.5})
    assert xtrace.kernel_seconds(t, ["attn_kernel", "flash"]) == pytest.approx(1.5)
    assert xtrace.kernel_seconds(t, ["shard_map"]) is None
    assert t["steps"] == 1
    ops = dict(t["breakdown"]["device_ops"])
    assert "while" not in ops and ops["fusion"] == pytest.approx(5.5)
    assert t["breakdown"]["idle_gaps"] == []  # device 0 has none; device 1 one interval


def test_idle_gap_is_named_by_the_launching_thread():
    r = [Row(0, OPS_LINE, "%fusion.1 = f32[] fusion()", 0.0, 1.0),
         Row(0, OPS_LINE, "%fusion.2 = f32[] fusion()", 3.0, 1.0),
         Row(HOST, "python#0", "PjitFunction(step)", 0.0, 0.5),
         Row(HOST, "python#0", "$api.py:1 block_until_ready", 0.9, 2.3),
         Row(HOST, "python#0", "$trainer.py:1 _train", 0.0, 9.0),
         Row(HOST, "python#1", "$queue.py:1 put", 0.0, 9.0)]
    (name, dur), = xtrace.reduce_rows(r)["breakdown"]["idle_gaps"]
    assert dur == pytest.approx(2.0)
    assert name == "api.py:1 block_until_ready < trainer.py:1 _train"


def test_idle_gap_is_named_by_the_programs_span_over_it():
    """Where the trace holds the loop's ``train.*`` spans, a gap takes the
    name of the phase that holds most of it, not a Python frame's and not
    the whole step's ``train``."""
    r = [Row(0, OPS_LINE, "%fusion.1 = f32[] fusion()", 0.0, 1.0),
         Row(0, OPS_LINE, "%fusion.2 = f32[] fusion()", 3.0, 1.0),
         Row(HOST, "python#2", "train", 0.0, 9.0),
         Row(HOST, "python#2", "train.block", 0.2, 2.0),        # 1.2 s of the gap
         Row(HOST, "python#2", "train.dispatch", 2.2, 0.9),     # 0.1 s of its own
         Row(HOST, "python#2", "train.rng", 2.2, 0.5),
         Row(HOST, "python#2", "train.launch", 2.8, 0.3),
         Row(HOST, "python#2", "$api.py:1 block_until_ready", 0.9, 2.3),
         Row(HOST, "python#2", "PjitFunction(step)", 2.8, 0.1)]
    (name, dur), = xtrace.reduce_rows(r)["breakdown"]["idle_gaps"]
    assert (name, dur) == ("train.block", pytest.approx(2.0))


def test_recorded_chip_trace_names_its_gaps_under_python3(tmp_path):
    """The recorded v5e trace was taken under ``python3``: its host line is
    called so. Every gap is named by a phase of the loop, and the device
    figures are what they were when host lines were looked for by name."""
    import gzip

    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(BENCH, "tests", "fixtures", "train-b8.v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    rows = xtrace.rows_from_xplane(str(path))
    assert {r.name for r in rows if r.device == HOST} >= {"train.block", "train.rng", "train.launch"}
    t = xtrace.reduce_rows(rows)
    gaps = t["breakdown"]["idle_gaps"]
    assert gaps and all(name.startswith("train.") for name, _ in gaps)
    assert {name for name, _ in gaps} >= {"train.rng", "train.block"}
    # without its host rows (what a reader that wants a line called
    # ``python`` sees here) only the gaps' names differ
    bare = xtrace.reduce_rows([r for r in rows if r.device != HOST])
    assert all(name.startswith("dev0: after ") for name, _ in bare["breakdown"]["idle_gaps"])
    assert [d for _, d in gaps] == [d for _, d in bare["breakdown"]["idle_gaps"]]
    t["breakdown"]["idle_gaps"] = bare["breakdown"]["idle_gaps"]
    assert t == bare


def test_interval_arithmetic():
    assert xtrace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xtrace.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert xtrace.base_op("%all-gather-start.12 = f32[] all-gather-start()") == "all-gather-start"
    assert xtrace.is_collective("%reduce-scatter.1 = x") and not xtrace.is_collective("%fusion.1 = x")
    assert xtrace.reduce_rows([])["busy_s"] == 0.0


def test_parses_the_programs_recorded_capture():
    path = os.path.join(ROOT, "tests", "fixtures", "devprof_capture", "fixture.trace.json.gz")
    t = xtrace.reduce_rows(xtrace.rows_from_chrome(path))
    assert t["window_s"] > 0 and 0 < t["busy_s"] <= t["window_s"]
    assert t["breakdown"]["device_ops"]
