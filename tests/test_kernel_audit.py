"""Kernel auditor (ISSUE 20): the DMA happens-before race detector must
flag every fabricated discipline violation and pass every shipped
kernel; the shared VMEM planner's bytes must match hand arithmetic and
must not have changed any routing decision; the committed per-rung
kernel baselines must round-trip and drift loudly.

The fabricated schedules below are built by the SAME synthesizers that
mirror the shipped kernels' event emission — the green-path test proves
the synthesizers match the real recorded schedules, so a broken variant
differs from a shipped kernel in exactly the violation under test.
"""

import dataclasses
import json
import os

import pytest

from dtc_tpu.analysis import kernels as K
from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.ops import decode_fused, vmem

BUDGET = vmem.VMEM_BUDGET_BYTES


def _cfg(**over):
    base = dict(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=32, dropout=0.0, param_dtype="float32",
        compute_dtype="bfloat16",
    )
    base.update(over)
    return ModelConfig(**base)


def flagship_cfg():
    return K.rung_config("flagship")


# ---------------------------------------------------------------------------
# schedule synthesizers — mirror ops/overlap_collectives.py's emission
# ---------------------------------------------------------------------------


def ag_segment(ring=4):
    ev = [dict(kind="kernel", name="ag_matmul", ring=ring)]
    for s in range(ring):
        own = s == 0
        if s > 0:
            ev.append(dict(kind="dma_wait", step=s))
        if s < ring - 1:
            ev.append(dict(
                kind="dma_start", step=s,
                src_buf="w_own" if own else "w_slots",
                src_slot=None if own else ("rel", -s),
                dst_buf="w_slots", dst_slot=("rel", -s), dst_device=1,
            ))
        ev.append(dict(
            kind="read", step=s, buf="w_own" if own else "w_slots",
            slot=None if own else ("rel", -s),
        ))
        ev.append(dict(kind="write", step=s, buf="o", slot=None))
    return ev


def rs_segment(ring=4):
    ev = [dict(kind="kernel", name="rs_matmul", ring=ring)]
    for s in range(ring):
        if s > 0:
            ev.append(dict(kind="dma_wait", step=s))
            ev.append(dict(kind="read", step=s, buf="recv",
                           slot=("abs", s - 1)))
        if s < ring - 1:
            ev.append(dict(kind="write", step=s, buf="stage", slot=None))
            ev.append(dict(
                kind="dma_start", step=s, src_buf="stage", src_slot=None,
                dst_buf="recv", dst_slot=("abs", s), dst_device=1,
            ))
        else:
            ev.append(dict(kind="write", step=s, buf="o", slot=None))
    return ev


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# race detector: shipped kernels green, every fabricated violation fires
# ---------------------------------------------------------------------------


@pytest.mark.kernels
def test_shipped_ring_kernels_race_free():
    """Every pallas_call the module owns — ag fwd both shard modes, both
    backward legs via jax.grad, standalone rs both scatter modes — is
    recorded and happens-before-clean; the synthesizers above reproduce
    the recorded schedules exactly (so the broken fixtures differ from
    shipped kernels only in the violation)."""
    segments = K.record_ring_schedules(ring=4)
    names = [seg[0]["name"] for seg in segments]
    assert "ag_matmul" in names and "rs_matmul" in names
    for seg in segments:
        assert K.check_ring_schedule(seg) == []
    by_name = {seg[0]["name"]: seg for seg in segments}
    assert by_name["ag_matmul"] == ag_segment(ring=4)
    assert by_name["rs_matmul"] == rs_segment(ring=4)
    assert K.audit_ring_kernels(ring=4) == []


def test_synthesized_schedules_green():
    for ring in (2, 4, 8):
        assert K.check_ring_schedule(ag_segment(ring)) == []
        assert K.check_ring_schedule(rs_segment(ring)) == []


def test_recv_before_wait_fires():
    """Dropping one dma.wait(): every later read consumes a slot whose
    fill is not ordered before it, and the last send is never covered —
    the violation interpret mode's serialized DMA execution hides."""
    broken = [
        e for e in ag_segment(4)
        if not (e.get("kind") == "dma_wait" and e.get("step") == 1)
    ]
    rules = _rules(K.check_ring_schedule(broken))
    assert "kernel.race.recv_before_wait" in rules
    assert "kernel.race.unwaited_dma" in rules


def test_missing_send_wait_fires_send_rewrite():
    """No waits at all in the rs schedule: the stage buffer is rewritten
    while the previous send is still reading it (the exact discipline
    the kernel's comment promises), every send stays in flight, and the
    recv reads are uncovered."""
    broken = [e for e in rs_segment(4) if e.get("kind") != "dma_wait"]
    rules = _rules(K.check_ring_schedule(broken))
    assert "kernel.race.send_rewrite" in rules
    assert "kernel.race.unwaited_dma" in rules
    assert "kernel.race.recv_before_wait" in rules


def test_slot_reuse_fires():
    """Per-chunk recv slots collapsed to one: every later fill races the
    un-consumed previous chunk."""
    broken = [
        dict(e, dst_slot=("abs", 0)) if e.get("kind") == "dma_start" else e
        for e in rs_segment(4)
    ]
    rules = _rules(K.check_ring_schedule(broken))
    assert "kernel.race.slot_reuse" in rules


def test_unfilled_read_fires():
    broken = [
        dict(e, slot=("abs", 3))
        if e.get("kind") == "read" and e.get("step") == 1 else e
        for e in rs_segment(4)
    ]
    assert _rules(K.check_ring_schedule(broken)) == {
        "kernel.race.unfilled_read"
    }


def test_unmatched_wait_fires():
    seg = rs_segment(4) + [dict(kind="dma_wait", step=4)]
    rules = _rules(K.check_ring_schedule(seg))
    assert "kernel.race.unmatched_wait" in rules


def test_segment_split_tolerates_duplicate_traces():
    log = ag_segment(4) + ag_segment(4) + rs_segment(4)
    segs = K.split_schedule_segments(log)
    assert [s[0]["name"] for s in segs] == [
        "ag_matmul", "ag_matmul", "rs_matmul"
    ]
    assert all(K.check_ring_schedule(s) == [] for s in segs)


# ---------------------------------------------------------------------------
# planner bytes vs hand arithmetic (satellites 1 + 2)
# ---------------------------------------------------------------------------


def test_fused_layers_plan_flagship_hand_computed():
    cfg = flagship_cfg()
    dm, hd, ff, S = 512, 512, 2048, 512
    plan = vmem.fused_layers_plan(cfg, t=1)
    # 16 per-layer weight blocks, fp32: 4 (dm,hd)-class matrices,
    # 2 (dm,ff)-class, biases + LN params.
    weights = 4 * (
        4 * dm * hd + 2 * dm * ff   # wq wk wv wo, w1 w2
        + 3 * hd + 6 * dm + ff      # bq bk bv, bo ln1(2) ln2(2) b2, b1
    )
    assert plan["bytes"]["weights"] == weights == 12_609_536
    # one row's K+V tiles, bf16 (kv auto -> compute dtype)
    assert cfg.kv_store_dtype == "bfloat16"
    assert plan["bytes"]["cache_row"] == 2 * S * hd * 2 == 1_048_576
    assert plan["spec_surcharge_bytes"] == 0  # t=1 by construction
    assert plan["gate_bytes"] == weights + 2 * S * hd * 2 == 13_658_112
    assert plan["fits"] is True
    # Cross-layer double-buffering exceeds the single-buffered budget
    # (and the chip's 16 MiB default scoped limit), so the kernel states
    # its own limit: everything double-buffered plus the allowance.
    assert plan["double_buffered_bytes"] > BUDGET
    assert plan["vmem_limit_bytes"] > plan["double_buffered_bytes"]
    assert plan["vmem_limit_bytes"] < 128 * 1024 * 1024  # v5e physical


def test_spec_window_surcharge_hand_computed():
    """Satellite 2: the gate must price PR 19's k-query working set.
    Hand arithmetic for t=8, b=1 on the flagship: io grows by
    2·(t-1)·dm·cb (x + x_out) + 2·(t-1)·hd·kvb (k_new + v_new), scratch
    by 8·(t-1)·dm·cb, and the modeled in-register transients by
    (2·t·S·4 + 2·t²·4) - (2·S·4 + 2·4)."""
    cfg = flagship_cfg()
    dm, hd, S, t = 512, 512, 512, 8
    io = 2 * (t - 1) * dm * 2 + 2 * (t - 1) * hd * 2
    scratch = 8 * (t - 1) * dm * 2
    transients = (2 * t * S * 4 + 2 * t * t * 4) - (2 * S * 4 + 2 * 4)
    plan = vmem.fused_layers_plan(cfg, t=t)
    assert plan["spec_surcharge_bytes"] == io + scratch + transients == 115_192
    assert plan["gate_bytes"] == 13_658_112 + 115_192
    assert plan["fits"] is True  # flagship still clears the budget at k=8


def test_supports_fused_layers_prices_spec_window():
    """The PR 19 audit: a config whose single-query decode fits but
    whose k=8 verify window does not must be REJECTED at t=8 — the old
    gate priced one query row and would have admitted it."""
    cfg = _cfg(d_model=512, n_heads=16, d_ff=2048, max_seq_len=960)
    assert decode_fused.supports_fused_layers(cfg) is True
    assert decode_fused.supports_fused_layers(cfg, t=8) is False
    t1 = vmem.fused_layers_plan(cfg, t=1)
    t8 = vmem.fused_layers_plan(cfg, t=8)
    assert t1["gate_bytes"] <= BUDGET < t8["gate_bytes"]
    assert t8["gate_bytes"] - t1["gate_bytes"] == t8["spec_surcharge_bytes"]


def test_gate_unchanged_for_previously_supported_shapes():
    """Satellite 1 regression: unifying the estimators must not change
    routing — t=1 surcharge is identically 0, so the gate is the old
    weights+cache_row rule with EXACT weight bytes."""
    for cfg in (flagship_cfg(), _cfg(), _cfg(kv_cache_dtype="int8")):
        assert vmem.fused_layers_plan(cfg, t=1)["spec_surcharge_bytes"] == 0
    assert decode_fused.supports_fused_layers(flagship_cfg()) is True
    assert decode_fused._VMEM_BUDGET_BYTES is vmem.VMEM_BUDGET_BYTES
    assert decode_fused._SPEC_MAX_K == vmem.SPEC_MAX_K


def test_decode_plans_hand_computed():
    cfg = flagship_cfg()  # head_dim 32 -> 4 heads per 128-lane block
    single = vmem.decode_single_plan(cfg)
    assert (single["group"], single["lane_block"]) == (4, 128)
    # grid (B, H/4): per step 2 (s,128) KV tiles bf16 + q/out blocks
    assert single["per_step_bytes"] == 2 * 512 * 128 * 2 + 2 * 128 * 2
    blocked = vmem.decode_blocked_plan(cfg)
    assert blocked["per_step_bytes"] == (
        2 * 512 * 128 * 2 + 2 * 128 * 2      # one 512-chunk + io
        + 2 * 8 * 128 * 4 + 8 * 128 * 4      # m/l rows + fp32 accum
    )
    int8 = vmem.decode_single_plan(_cfg(kv_cache_dtype="int8",
                                        max_seq_len=512))
    # head_dim 16, 4 heads: 128//16=8 heads/lane-block does not divide
    # h=4 -> ONE padded all-lanes block (4, 64); int8 payload + scales
    assert (int8["group"], int8["lane_block"]) == (4, 64)
    assert int8["bytes"]["kv_tiles"] == 2 * 512 * 64 * 1
    assert int8["bytes"]["scales"] == 2 * 512 * 4 * 4


def test_packed_group_pinned_against_kernels():
    """The planner's jax-free mirror of the packed-layout grouping must
    agree with both kernel implementations for every shape class."""
    from dtc_tpu.ops import decode_attention, flash_attention

    for d, h in [(32, 16), (64, 4), (64, 2), (128, 8), (80, 4), (256, 2),
                 (64, 3), (16, 4)]:
        fg = flash_attention._packed_group(d, h)
        assert vmem.packed_group(d, h) == decode_attention._group(d, h)
        if fg is None:
            assert vmem.packed_group(d, h) == (h, h * d)  # padded block
        else:
            assert vmem.packed_group(d, h) == (fg, 128)


def test_decode_supports_routing_unchanged():
    """The vmem consult in decode_attention.supports can never flip
    routing: at the 14 MiB budget every cache under the structural
    single-tile bound fits (worst case fp32·128 lanes)."""
    from dtc_tpu.ops import decode_attention

    for s in (1, 7, 512, 2048, 4096):
        assert vmem.decode_single_tile_fits(s)
        assert decode_attention.supports(s)
    assert decode_attention.supports(5120)      # blocked path
    assert not decode_attention.supports(4100)  # neither branch
    # the bound itself: fp32 2-tile + softmax row per 128-lane block
    assert not vmem.decode_single_tile_fits(BUDGET // (2 * 128 * 4) + 1)


def test_overlap_plan_hand_computed():
    plan = vmem.overlap_plan(m=2, k_loc=16, n_loc=8, ring=4, shard_axis=0,
                             itemsize=4)
    slots = 5 * (16 // 4) * 8 * 4          # (ring+1) shard slots, fp32
    assert plan["legs"]["fwd_ag"] == 2 * 16 * 4 + 2 * 8 * 4 + slots
    assert plan["legs"]["bwd_dx_ag"] == 2 * 8 * 4 + 2 * 16 * 4 + slots
    assert plan["legs"]["bwd_dw_rs"] == vmem.rs_standalone_bytes(
        2, 16, 8, 4, 0, 4
    ) == 2 * (16 + 8) * 4 + 5 * 4 * 8 * 4
    assert plan["worst_bytes"] == max(plan["legs"].values())
    assert plan["fits"] is True
    assert plan["block"] == 4 and plan["lane_aligned"] is False
    big = vmem.overlap_plan(m=4096, k_loc=8192, n_loc=8192, ring=8,
                            shard_axis=0, itemsize=4)
    assert big["fits"] is False  # operands alone blow the budget


# ---------------------------------------------------------------------------
# lint family
# ---------------------------------------------------------------------------


def test_lint_green_on_all_rungs():
    for name in K.LADDER_RUNGS:
        cfg = K.rung_config(name)
        assert K.lint_fused_layers(cfg) == []
        assert K.lint_fused_layers(cfg, t=vmem.SPEC_MAX_K) == []


def test_lint_flags_b_variant_weight_map():
    """The fabricated broken kernel: a weight block whose index map
    varies with the row coordinate — weights would re-stream per ROW
    instead of per layer."""
    cfg = flagship_cfg()
    plan = vmem.fused_layers_grid_plan(cfg, t=1, b=2)
    row_map = lambda l, bb: (l, bb, 0)  # noqa: E731

    def broken(entry):
        name, shape, imap, space, nb = entry
        if name == "wq":
            return (name, shape, row_map, space, nb)
        return entry

    plan["in_specs"] = [broken(e) for e in plan["in_specs"]]
    findings = K.lint_grid_plan(plan)
    assert any(
        f.rule == "kernel.lint.index_map" and "wq" in f.message
        and "per layer, not per row" in f.message
        for f in findings
    )


def test_lint_flags_non_advancing_and_aliasing_maps():
    cfg = flagship_cfg()
    plan = vmem.fused_layers_grid_plan(cfg, t=1, b=2)
    stuck = lambda l, bb: (0, 0, 0)    # noqa: E731  weight never advances
    shared_row = lambda l, bb: (l, 0, 0, 0)  # noqa: E731  rows alias

    def broken(entry):
        name, shape, imap, space, nb = entry
        if name == "ln1_scale":
            return (name, shape, stuck, space, nb)
        if name == "k_row":
            return (name, shape, shared_row, space, nb)
        return entry

    plan["in_specs"] = [broken(e) for e in plan["in_specs"]]
    msgs = [f.message for f in K.lint_grid_plan(plan)]
    assert any("ln1_scale" in m and "advance with the layer" in m
               for m in msgs)
    assert any("k_row" in m and "row coordinate" in m for m in msgs)


def test_lint_flags_smem_violations():
    cfg = flagship_cfg()
    plan = vmem.fused_layers_grid_plan(cfg, t=1, b=2)
    # frontier demoted to a VMEM block-less operand
    plan["in_specs"] = [
        ("frontier", None, None, "vmem", 4) if e[0] == "frontier" else e
        for e in plan["in_specs"]
    ]
    findings = K.lint_grid_plan(plan)
    assert any(f.rule == "kernel.lint.smem" and "frontier" in f.message
               for f in findings)
    assert any(f.rule == "kernel.lint.smem" and "no SMEM scalar" in f.message
               for f in findings)


def test_gate_coverage_lint(tmp_path):
    # shipped ops/: every kernel module gates on the planner, none waived
    # (flash attention's tiles come from vmem.flash_plan since PR 27)
    assert K.lint_gate_coverage() == [] and K.PALLAS_GATE_WAIVERS == {}
    # a waived module stays visible, as info
    waived = K.lint_gate_coverage(waivers={"flash_attention.py": "for the test"})
    assert [(f.severity, f.artifact) for f in waived] == [
        ("info", "ops/flash_attention.py")
    ]
    # a module with an ungated pallas_call -> error
    (tmp_path / "rogue.py").write_text(
        "import jax.experimental.pallas as pl\n"
        "def launch(x):\n    return pl.pallas_call(lambda r, o: None)(x)\n"
    )
    found = K.lint_gate_coverage(str(tmp_path), waivers={})
    assert [(f.rule, f.severity) for f in found] == [
        ("kernel.lint.gate_coverage", "error")
    ]
    # a gate that never consults the planner -> still an error
    (tmp_path / "rogue.py").write_text(
        "import jax.experimental.pallas as pl\n"
        "def supports_rogue(n):\n    return n * 4 < 14 << 20\n"
        "def launch(x):\n    return pl.pallas_call(lambda r, o: None)(x)\n"
    )
    found = K.lint_gate_coverage(str(tmp_path), waivers={})
    assert [f.rule for f in found] == ["kernel.lint.gate_coverage"]
    assert "consult the shared planner" in found[0].message
    # the waiver downgrades to info
    found = K.lint_gate_coverage(str(tmp_path), waivers={"rogue.py": "test"})
    assert [f.severity for f in found] == ["info"]


# ---------------------------------------------------------------------------
# ladder rungs + committed baselines
# ---------------------------------------------------------------------------


def test_ladder_configs_load_and_verdicts():
    cfg350 = K.rung_config("ladder_350m")
    cfg1b = K.rung_config("ladder_1b")
    assert (cfg350.d_model, cfg350.n_layers, cfg350.head_dim) == (1024, 24, 128)
    assert (cfg1b.d_model, cfg1b.n_layers, cfg1b.head_dim) == (2048, 20, 128)
    # the honest static verdicts the baselines pin: the megakernel fits
    # the flagship only; the runtime ladder falls back automatically.
    assert decode_fused.supports_fused_layers(flagship_cfg()) is True
    assert decode_fused.supports_fused_layers(cfg350) is False
    assert decode_fused.supports_fused_layers(cfg1b) is False
    # per-layer decode kernels fit every rung (they stream the cache)
    for cfg in (cfg350, cfg1b):
        assert vmem.decode_single_plan(cfg)["fits"] is True
        assert vmem.decode_blocked_plan(cfg)["fits"] is True


#: Where each rung is defined, and the only fields the audit may set over it.
_RUNG_SOURCES = {
    "flagship": ("model_config.yaml", {"dropout", "remat", "decode_attention"}),
    "ladder_350m": ("model_ladder_350m.yaml", set()),
    "ladder_1b": ("model_ladder_1b.yaml", set()),
}


@pytest.mark.parametrize("rung", K.LADDER_RUNGS)
def test_a_rung_is_its_config_file_and_the_stated_overrides(rung):
    """Each rung is defined in one place, a file under configs/: the audit's
    config differs from the loaded file in the fields listed above, which
    are the ones ``K.LADDER_RUNGS`` states, and in nothing else."""
    from dtc_tpu.config.loader import load_yaml_dataclass

    filename, overridden = _RUNG_SOURCES[rung]
    assert K.LADDER_RUNGS[rung][0] == filename
    from_file = load_yaml_dataclass(os.path.join(K._CONFIG_DIR, filename), ModelConfig)
    audited = K.rung_config(rung)
    differs = {f.name for f in dataclasses.fields(ModelConfig)
               if getattr(audited, f.name) != getattr(from_file, f.name)}
    assert differs == overridden == set(K.LADDER_RUNGS[rung][1])


def test_committed_kernel_baselines_match_recompute():
    """The drift gate the CI pre-gate runs: recomputing every rung's
    static plan must reproduce the committed kernels_<rung>.json."""
    report = K.kernel_report()
    assert set(report["rungs"]) == set(K.LADDER_RUNGS)
    assert K.check_kernel_baselines(report, require=True) == []
    # and the committed flagship file pins what the kernel asks Mosaic for
    path = os.path.join(K.BASELINE_DIR, "kernels_flagship.json")
    with open(path) as f:
        fp = json.load(f)["fingerprint"]
    t1 = fp["kernels"]["fused_layers_t1"]
    assert t1["fits"] is True
    assert t1["vmem_limit_bytes"] > t1["double_buffered_bytes"] > BUDGET
    assert t1["gate_bytes"] == 13_658_112


def test_kernel_baseline_round_trip_and_drift(tmp_path):
    report = K.kernel_report()
    written = K.write_kernel_baselines(report, directory=str(tmp_path))
    assert len(written) == len(K.LADDER_RUNGS)
    assert K.check_kernel_baselines(report, directory=str(tmp_path)) == []
    # byte-level drift -> error naming the field
    drifted = json.loads(json.dumps(report))  # deep copy
    drifted["rungs"]["flagship"]["kernels"]["fused_layers_t1"][
        "gate_bytes"
    ] += 1
    findings = K.check_kernel_baselines(drifted, directory=str(tmp_path))
    assert [f.rule for f in findings] == ["baseline.drift"]
    assert findings[0].severity == "error"
    assert "gate_bytes" in findings[0].message
    # missing baseline: error when required, warn otherwise
    empty = tmp_path / "empty"
    empty.mkdir()
    sev = {
        f.severity
        for f in K.check_kernel_baselines(
            report, directory=str(empty), require=True
        )
    }
    assert sev == {"error"}
    sev = {
        f.severity
        for f in K.check_kernel_baselines(
            report, directory=str(empty), require=False
        )
    }
    assert sev == {"warn"}


def test_fused_layers_call_specs_come_from_planner():
    """Single-source-of-truth: the megakernel's launched BlockSpecs are
    BUILT from the grid plan — the plan's block shapes must match what
    the byte accounting sums, with the LoRA and quant variants adding
    exactly their planned operands."""
    cfg = _cfg()
    base = vmem.fused_layers_grid_plan(cfg, t=1, b=2)
    names = [e[0] for e in base["in_specs"]]
    assert names[0] == "frontier" and names[1] == "x"
    assert set(vmem.WEIGHT_BLOCK_NAMES) <= set(names)
    assert [e[0] for e in base["out_specs"]] == ["x_out", "k_new", "v_new"]
    quant = vmem.fused_layers_grid_plan(
        _cfg(kv_cache_dtype="int8"), t=1, b=2
    )
    assert [e[0] for e in quant["out_specs"]] == [
        "x_out", "k_new", "v_new", "k_scale_new", "v_scale_new"
    ]
    adapter = dataclasses.replace(
        _cfg(), adapter=__import__(
            "dtc_tpu.config.schema", fromlist=["AdapterConfig"]
        ).AdapterConfig(rank=4, target_modules=("q_proj", "fc1"))
    )
    sites = vmem.lora_sites_for(adapter)
    assert sites == ("q_proj", "fc1")
    lora = vmem.fused_layers_grid_plan(adapter, t=1, b=2, lora_sites=sites)
    lora_names = [e[0] for e in lora["in_specs"] if e[0].endswith(("_a", "_b"))]
    assert lora_names == ["q_proj_a", "q_proj_b", "fc1_a", "fc1_b"]
