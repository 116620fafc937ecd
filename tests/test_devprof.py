"""Device-time observatory tests (ISSUE 8).

The parser/attribution tests run against the COMMITTED fixture capture
(``tests/fixtures/devprof_capture/`` — a hand-built trace.json.gz + meta
sidecar with hand-computed durations), never against live profiler
output, so they do not depend on what this backend's profiler emits.
The capture-window tests assert the MECHANICS (window lifecycle, meta
sidecar, trigger wiring, warn-not-fail on empty captures); the full
capture->attribute pipeline is exercised by ``scripts/devprof_smoke.py``
(tier-1 pre-gate).
"""

import glob
import json
import os

import pytest

from dtc_tpu.obs import devprof

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "devprof_capture"
)

# Hand-computed fixture facts (see the generator comments in the fixture):
# rows (self-time ms): fusion.1=10 (attn_qkv, fwd), fusion.2=5 (mlp, bwd),
# fusion.4=9-4=5 (optimizer; fusion.5 nests inside), fusion.5=4 (optimizer),
# copy.9=2 (data_movement), dot.11=3 (scope-less), all-reduce.7=8
# (collectives, tid 2). Umbrella events jit_train_step + "5" skipped.
TOTAL_S = 0.037
UNATTRIBUTED_S = 0.003


def load_fixture_rows():
    path = devprof.find_trace_file(FIXTURE)
    assert path, "committed fixture trace missing"
    return devprof.device_op_rows(devprof.load_trace(path))


# ---------------------------------------------------------------------------
# parser


class TestParser:
    def test_selection_skips_umbrellas_and_host(self):
        rows = load_fixture_rows()
        names = {r.name for r in rows}
        assert names == {
            "fusion.1", "fusion.2", "fusion.4", "fusion.5", "copy.9",
            "dot.11", "all-reduce.7",
        }
        # the host python thread's events never enter the device rows
        assert all(r.pid == 10 for r in rows)

    def test_typed_fields(self):
        rows = {r.name: r for r in load_fixture_rows()}
        r = rows["fusion.1"]
        assert r.hlo_module == "jit_train_step"
        assert r.t0_s == pytest.approx(0.001)
        assert r.dur_s == pytest.approx(0.010)
        assert r.kind == "compute"
        assert "attn_qkv" in r.scope
        assert rows["all-reduce.7"].kind == "collective"

    def test_cpu_fallback_selection(self):
        """A trace with NO device pid (the TFRT CPU backend) selects the
        XLA op events by their hlo_op arg instead."""
        trace = {"traceEvents": [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "X", "pid": 1, "tid": 3, "name": "dot.4", "ts": 100.0,
             "dur": 50.0, "args": {"hlo_op": "dot.4", "hlo_module": "jit_f"}},
            {"ph": "X", "pid": 1, "tid": 3, "name": "ThunkExecutor::Execute",
             "ts": 0.0, "dur": 500.0},  # no hlo_op arg: not an op event
        ]}
        rows = devprof.device_op_rows(trace)
        assert [r.name for r in rows] == ["dot.4"]
        assert rows[0].scope == ""  # CPU events carry no provenance args

    def test_self_times_nesting(self):
        rows = load_fixture_rows()
        selfs = dict(zip([r.name for r in rows], devprof.self_times(rows)))
        assert selfs["fusion.4"] == pytest.approx(0.005)  # 9ms - nested 4ms
        assert selfs["fusion.5"] == pytest.approx(0.004)
        assert selfs["fusion.1"] == pytest.approx(0.010)


# ---------------------------------------------------------------------------
# scope recovery + classification


class TestScopes:
    def test_scope_map_from_hlo(self):
        hlo = (
            'ENTRY %main {\n'
            '  %dot.11 = f32[8,97]{1,0} dot(%a, %b), '
            'metadata={op_name="jit(step)/jit(main)/jvp(fwd)/GPT/head/dot_general" '
            'source_file="x.py" source_line=1}\n'
            '  %add.1 = f32[] add(%c, %d)\n'
            "}\n"
        )
        m = devprof.scope_map_from_hlo(hlo)
        assert m == {
            "dot.11": "jit(step)/jit(main)/jvp(fwd)/GPT/head/dot_general"
        }

    def test_scope_for_strips_executor_suffixes(self):
        row = devprof.OpRow(
            name="tanh.5.clone", hlo_op="tanh.5.clone", hlo_module="m",
            scope="", t0_s=0.0, dur_s=1.0, pid=0, tid=0, kind="compute",
        )
        assert devprof.scope_for(row, {"tanh.5": "a/mlp/tanh"}) == "a/mlp/tanh"
        assert devprof.scope_for(row, {}) == ""

    @pytest.mark.parametrize("scope,component,phase", [
        ("jit(s)/jvp(fwd)/GPT/stage/blocks/attn/attn_qkv/dot", "attn_qkv", "fwd"),
        ("jit(s)/transpose(jvp(fwd))/GPT/stage/blocks/mlp/fc1/dot", "mlp", "bwd"),
        ("jit(s)/optimizer/mul", "optimizer", "optimizer"),
        ("jit(s)/jvp(GPT)/head/ln_f/rsqrt", "ln", "fwd"),  # inner wins
        ("jit(s)/jvp(GPT)/embed/wte/gather", "embed", "fwd"),
        ("jit(s)/jvp(fwd)/GPT/stage/while/body/blocks/Block_0/add",
         "residual", "fwd"),
        ("jit(s)/jvp(fwd)/GPT/stage/while/body/select_n", "scan", "fwd"),
        ("jit(generate)/prefill/GPT/stage/blocks/attn/attn_kernel/dot",
         "attn_kernel", ""),
        ("", "", ""),
    ])
    def test_classify_scope(self, scope, component, phase):
        assert devprof.classify_scope(scope) == (component, phase)


# ---------------------------------------------------------------------------
# attribution


class TestAttribution:
    def test_component_rollup_without_scope_map(self):
        att = devprof.attribute(load_fixture_rows())
        assert att.n_ops == 7
        assert att.total_s == pytest.approx(TOTAL_S)
        assert att.components["attn_qkv"] == pytest.approx(0.010)
        assert att.components["mlp"] == pytest.approx(0.005)
        assert att.components["optimizer"] == pytest.approx(0.009)
        assert att.components["data_movement"] == pytest.approx(0.002)
        assert att.components["collectives"] == pytest.approx(0.008)
        assert att.unattributed_s == pytest.approx(UNATTRIBUTED_S)
        assert att.attributed_share == pytest.approx(
            (TOTAL_S - UNATTRIBUTED_S) / TOTAL_S
        )
        assert att.phases == pytest.approx(
            {"fwd": 0.010, "bwd": 0.005, "optimizer": 0.009}
        )

    def test_overlap_and_busy(self):
        att = devprof.attribute(load_fixture_rows())
        assert att.collective_s == pytest.approx(0.008)
        assert att.compute_s == pytest.approx(0.029)
        # all-reduce [5,13]ms vs compute union: [5,11] + [12,13] = 7ms
        assert att.overlap_s == pytest.approx(0.007)
        assert att.overlap_ratio == pytest.approx(7 / 8)
        assert att.busy_s == pytest.approx(0.029)  # tid 1 self-time sum

    def test_scope_map_join_completes_attribution(self):
        sm = {"dot.11": "jit(s)/jit(main)/jvp(fwd)/GPT/head/dot_general"}
        att = devprof.attribute(load_fixture_rows(), scope_map=sm)
        assert att.components["head"] == pytest.approx(0.003)
        assert att.unattributed_s == 0.0
        assert att.attributed_share == pytest.approx(1.0)

    def test_component_table_and_mfu(self):
        att = devprof.attribute(load_fixture_rows())
        table = att.component_table(steps=2)
        assert table[0]["component"] == "attn_qkv"
        assert table[0]["s_per_step"] == pytest.approx(0.005)
        assert table[-1]["component"] == "(unattributed)"
        assert sum(r["share"] for r in table) == pytest.approx(1.0)
        # busy/step = 14.5ms; 1e9 FLOPs / (0.0145s * 1e12 FLOP/s)
        assert att.device_mfu(1.0e9, 1.0e12, steps=2) == pytest.approx(
            1.0e9 / (0.0145 * 1.0e12)
        )
        assert att.device_mfu(None, 1.0e12) is None
        assert att.device_mfu(1.0e9, None) is None

    def test_structural_gates(self):
        att = devprof.attribute(load_fixture_rows())
        g = devprof.structural_gates(att)
        assert g["all_dot_fusions_attributed"] is False
        assert g["unattributed_dot_fusions"] == ["dot.11"]
        assert g["unattributed_share_ok"] is True  # 3/37 < 10%
        sm = {"dot.11": "jit(s)/jvp(fwd)/GPT/head/dot_general"}
        g2 = devprof.structural_gates(
            devprof.attribute(load_fixture_rows(), scope_map=sm)
        )
        assert g2["all_dot_fusions_attributed"] is True
        assert g2["unattributed_share"] == 0.0

    def test_census_crosscheck_warn_band(self):
        att = devprof.attribute(load_fixture_rows())
        # 8/37 = 21.6% collective time vs a census that expects none
        assert devprof.census_crosscheck(att, {"total": 0.0})
        # a comm-heavy census with measured collectives: no warning
        assert devprof.census_crosscheck(att, {"total": 1e6}) == []
        # comm-heavy census but a capture with zero collective time
        compute_only = [r for r in load_fixture_rows() if r.kind == "compute"]
        att2 = devprof.attribute(compute_only)
        assert devprof.census_crosscheck(att2, {"total": 1e6})
        assert devprof.census_crosscheck(att2, {"total": 0.0}) == []


# ---------------------------------------------------------------------------
# merged export + capture-dir plumbing


class TestMergedExport:
    def test_wall_anchor_from_start_trace_marker(self):
        trace = devprof.load_trace(devprof.find_trace_file(FIXTURE))
        t0, wall = devprof.trace_wall_anchor(trace, 1000.0005)
        assert t0 == pytest.approx(0.0005)  # the start_trace event's ts
        assert wall == 1000.0005

    def test_analyze_capture_and_find_captures(self):
        caps = devprof.find_captures(os.path.dirname(FIXTURE))
        assert FIXTURE in caps
        res = devprof.analyze_capture(FIXTURE)
        assert res is not None
        assert res["meta"]["peak_hbm_bytes"] == 123456
        assert res["attribution"].n_ops == 7
        assert res["anchor"] == (pytest.approx(0.0005), 1000.0005)
        assert devprof.analyze_capture("/nonexistent/dir") is None

    def test_merged_chrome_trace_aligned(self):
        from dtc_tpu.obs.trace import to_chrome_trace

        res = devprof.analyze_capture(FIXTURE)
        dev = devprof.device_rows_to_events(res["rows"], anchor=res["anchor"])
        # fusion.1: trace t0=1ms, anchor trace 0.5ms -> wall 1000.001
        f1 = next(e for e in dev if e["name"] == "fusion.1")
        assert f1["t0"] == pytest.approx(1000.001)
        assert f1["component"] == "attn_qkv"
        assert f1["kind"] == "compute"
        host = [{
            "etype": "span", "name": "step", "cat": "train", "tid": "train",
            "ph": "X", "t0": 1000.0, "dur_s": 0.05, "proc": 0,
        }]
        merged = to_chrome_trace(host + dev)
        rows = [e for e in merged["traceEvents"] if e.get("cat") != "__metadata"]
        cats = {e["cat"] for e in rows}
        assert {"train", "device"} <= cats
        ts = [e["ts"] for e in rows]
        assert ts == sorted(ts)
        assert all({"name", "ph", "ts", "dur", "pid", "tid"} <= set(e) for e in rows)
        # one clock: the host span starts before the first device op and
        # the device ops land INSIDE its duration window
        host_row = next(e for e in rows if e["cat"] == "train")
        dev_ts = [e["ts"] for e in rows if e["cat"] == "device"]
        assert min(dev_ts) >= host_row["ts"]
        assert max(dev_ts) <= host_row["ts"] + host_row["dur"]


# ---------------------------------------------------------------------------
# capture windows (mechanics only; the devprof smoke covers the full path)


class TestCaptureWindows:
    def test_capture_window_meta_and_watermark(self, tmp_path):
        d = str(tmp_path / "cap")
        with devprof.CaptureWindow(
            d, steps=3, reason="unit", step_flops=1.0, peak_flops=2.0,
            comm_estimate={"total": 0.0},
        ) as cap:
            pass
        if not cap.ok:  # another test leaked an active profiler session
            pytest.skip("profiler session unavailable in this process")
        meta = devprof.load_meta(d)
        assert meta is not None
        assert meta["reason"] == "unit"
        assert meta["steps"] == 3
        assert meta["t_wall_stop"] >= meta["t_wall_start"]
        assert "peak_hbm_bytes" in meta  # explicit null on CPU
        assert meta["step_flops"] == 1.0

    def test_capture_tolerates_empty_environment(self, tmp_path):
        """The warn-not-fail contract: an environment where capture
        yields no op events (this harness) must not raise anywhere in
        the capture->analyze path."""
        d = str(tmp_path / "cap")
        with devprof.CaptureWindow(d, reason="empty") as cap:
            pass
        res = devprof.analyze_capture(d) if cap.ok else None
        if res is not None:
            att = res["attribution"]
            # no op rows -> empty-but-typed attribution, gates report not-ok
            assert att.total_s >= 0.0
            assert devprof.structural_gates(att)["unattributed_share_ok"] in (
                True, False,
            )

    def test_device_profiler_cadence_and_finalize(self, tmp_path):
        from dtc_tpu.obs import MemorySink, MetricsRegistry

        reg = MetricsRegistry()
        sink = reg.add_sink(MemorySink())
        dp = devprof.DeviceProfiler(
            str(tmp_path / "devprof"), registry=reg, every=3, n_steps=1,
        )
        for s in range(1, 6):
            dp.on_step(s)
        dp.close()
        if dp.disabled:
            pytest.skip("profiler session unavailable in this process")
        assert dp.captures == 1
        assert dp.last_artifact and os.path.isdir(dp.last_artifact)
        assert devprof.load_meta(dp.last_artifact)["reason"] == "cadence"
        evs = [e for e in sink.events if e["etype"] == "devprof"]
        assert len(evs) == 1 and evs[0]["reason"] == "cadence"

    def test_device_profiler_request_and_busy_defer(self, tmp_path):
        dp = devprof.DeviceProfiler(str(tmp_path / "devprof"), n_steps=1)
        assert dp.request("slo_breach:x") is True
        assert dp.request("second") is False  # one pending at a time
        dp.on_step(1, busy=True)  # legacy profiler window active: defer
        assert dp._prof is None and dp._pending == "slo_breach:x"
        dp.on_step(2)
        started = dp._prof is not None
        dp.on_step(3)
        dp.close()
        if dp.disabled and not dp.captures:
            pytest.skip("profiler session unavailable in this process")
        assert started
        assert dp.captures == 1
        assert "slo_breach" in devprof.load_meta(dp.last_artifact)["reason"]

    def test_telemetry_wiring(self, tmp_path):
        """Telemetry constructs the observatory, drives it from
        on_step_start, and the hung-step trigger arms a window."""
        from dtc_tpu.config.schema import ObsConfig
        from dtc_tpu.obs import Telemetry

        tele = Telemetry(
            ObsConfig(memory_sample_every=0, devprof_every=0),
            output_dir=str(tmp_path),
        )
        try:
            assert tele.devprof is not None  # devprof_on_trigger default
            tele.set_device_profile_context(
                step_flops=7.0, peak_flops=9.0, comm_estimate={"total": 1.0}
            )
            assert tele.devprof.step_flops == 7.0
            tele.on_hung_step(step=3)
            assert tele.devprof._pending == "hung_step"
            assert tele.request_device_profile() is False  # already pending
            tele.on_step_start(4)   # window opens (or warn-disables)
            tele.clock.end()
            tele.on_step_start(5)
            tele.clock.end()
            tele.on_step_start(6)
            tele.clock.end()
        finally:
            tele.close()
        if tele.devprof.disabled and not tele.devprof.captures:
            pytest.skip("profiler session unavailable in this process")
        assert tele.devprof.captures >= 1
        meta = devprof.load_meta(tele.devprof.last_artifact)
        assert meta["step_flops"] == 7.0
        assert meta["comm_estimate"] == {"total": 1.0}

    def test_slo_breach_trigger_is_edge_not_level(self, tmp_path):
        """A PERSISTENTLY breaching SLO arms exactly ONE capture (the
        objective entering the active set), not one per evaluation —
        else max_captures burns out on a single sustained breach."""
        from dtc_tpu.config.schema import ObsConfig, SloConfig
        from dtc_tpu.obs import Telemetry

        tele = Telemetry(
            ObsConfig(memory_sample_every=0),
            output_dir=str(tmp_path),
            slo_cfg=SloConfig(
                step_time_p99_s=1e-12, min_samples=1, check_every=1
            ),
        )
        calls: list[str] = []
        try:
            # Record trigger requests without opening real windows.
            tele.devprof.request = lambda reason: calls.append(reason) or True
            for s in range(1, 5):
                tele.on_step_start(s)
                tele.on_step_end(s, elapsed_s=0.0)
        finally:
            tele.close()
        assert calls == ["slo_breach:step_time_p99_s"]

    def test_devprof_constructed_without_cadence_or_trigger(self, tmp_path):
        """On-demand capture stays available when both the cadence and
        the trigger knobs are off (the observatory is inert, not absent)."""
        from dtc_tpu.config.schema import ObsConfig
        from dtc_tpu.obs import Telemetry

        tele = Telemetry(
            ObsConfig(
                memory_sample_every=0, devprof_every=0,
                devprof_on_trigger=False,
            ),
            output_dir=str(tmp_path),
        )
        try:
            assert tele.devprof is not None
            assert tele.request_device_profile("manual") is True
            # ...but triggers are honored per the knob: hung_step must NOT
            # override the explicit opt-out (the manual request stays).
            tele.on_hung_step(step=1)
            assert tele.devprof._pending == "manual"
        finally:
            tele.close()

    def test_obs_config_validation(self):
        from dtc_tpu.config.schema import ObsConfig

        with pytest.raises(ValueError):
            ObsConfig(devprof_every=-1)
        with pytest.raises(ValueError):
            ObsConfig(devprof_steps=0)


# ---------------------------------------------------------------------------
# satellites


class TestSatellites:
    def test_hbm_watermark_shape(self):
        from dtc_tpu.obs.device import hbm_watermark

        w = hbm_watermark()
        assert set(w) == {"peak_hbm_bytes", "hbm_bytes_in_use"}
        # CPU backend: explicit nulls, never a crash
        assert w["peak_hbm_bytes"] is None or w["peak_hbm_bytes"] >= 0

    def test_fixture_is_committed_not_generated(self):
        """Tests must not depend on live profiler output: the fixture's
        bytes are version-controlled and deterministic (gzip mtime=0)."""
        path = devprof.find_trace_file(FIXTURE)
        with open(path, "rb") as f:
            header = f.read(10)
        assert header[:2] == b"\x1f\x8b"          # gzip magic
        assert header[4:8] == b"\x00\x00\x00\x00"  # mtime pinned to 0
        with open(os.path.join(FIXTURE, "devprof_meta.json")) as f:
            assert json.load(f)["reason"] == "fixture"
