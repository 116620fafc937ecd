"""Unit tests for utils/metrics.py: FLOP accounting against hand-computed
small-config values, MFU's unknown-peak behavior, and the comm-bytes
estimator per parallelism mode (ISSUE 1 satellite)."""

import pytest

from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.utils.metrics import (
    comm_bytes_per_step,
    decode_roofline_ms,
    decode_step_bytes,
    decode_step_flops,
    gpt_step_flops,
    mfu,
    moe_step_flops,
    moe_step_flops_useful,
    peak_flops_per_chip,
)

# Tiny config, small enough to hand-compute every term.
D, L, H, FF, T, V = 64, 2, 4, 128, 32, 97
PAD_V = 128  # vocab 97 rounded up to vocab_pad_multiple=128


def _cfg(**kw):
    return ModelConfig(
        vocab_size=V, d_model=D, n_layers=L, n_heads=H, d_ff=FF,
        max_seq_len=T, **kw,
    )


def _dense_param_count():
    embed = PAD_V * D + T * D
    per_block = 4 * (D * D + D) + ((D * FF + FF) + (FF * D + D)) + 4 * D
    head = 2 * D + (D * PAD_V + PAD_V)
    return embed + L * per_block + head


def test_gpt_step_flops_hand_computed():
    cfg = _cfg()
    batch = 8
    n_matmul = _dense_param_count() - PAD_V * D - T * D
    dense = 6.0 * n_matmul * batch * T
    attn = 12.0 * L * batch * T**2 * D / 2.0
    assert gpt_step_flops(cfg, batch, T) == pytest.approx(dense + attn)


def test_moe_step_flops_hand_computed():
    import math

    e, k, cf = 4, 2, 1.25
    cfg = _cfg(moe_experts=e, moe_top_k=k, moe_capacity_factor=cf)
    batch = 8
    cap = max(1, math.ceil(T * k * cf / e))
    # param_count with the MoE FFN block.
    embed = PAD_V * D + T * D
    ffn = D * e + e * (D * FF + FF + FF * D + D)
    per_block = 4 * (D * D + D) + ffn + 4 * D
    head = 2 * D + (D * PAD_V + PAD_V)
    n = embed + L * per_block + head
    n_matmul = n - PAD_V * D - T * D
    # Subtracted MoE block = the FULL per-layer MoE params incl. the
    # per-expert biases (the round-5 ADVICE bias omission), so this term
    # plus the structural term below lines up with param_count.
    n_moe = L * (D * e + e * (2 * D * FF + FF + D))
    dense = 6.0 * (n_matmul - n_moe) * batch * T
    attn = 12.0 * L * batch * T**2 * D / 2.0
    per_layer = (
        2.0 * batch * T * D * e
        + 4.0 * batch * T * e * cap * D
        + 2.0 * batch * e * cap * (2 * D * FF + FF + D)
    )
    assert moe_step_flops(cfg, batch, T) == pytest.approx(dense + attn + 3.0 * L * per_layer)


def test_moe_bias_accounting_matches_param_count():
    """The fix the round-5 ADVICE asked for, as an invariant: subtracting
    the MoE block and adding it back structurally at cap·E = T·k (every
    assignment gets a slot, no slack) must reproduce dense-6N accounting
    over the SAME param tree — i.e. the subtracted block equals the MoE
    params in param_count, biases included."""
    from dtc_tpu.models.gpt import param_count

    e, k = 4, 2
    # capacity_factor 1.0 with E | T·k: cap·E == T·k exactly.
    cfg = _cfg(moe_experts=e, moe_top_k=k, moe_capacity_factor=1.0)
    batch = 8
    n_matmul = param_count(cfg) - PAD_V * D - T * D
    n_moe = L * (D * e + e * (2 * D * FF + FF + D))
    # 6N over non-MoE matmul params + structural MoE at zero slack + attn
    # + dispatch/combine einsums.
    cap = T * k // e
    expect = (
        6.0 * (n_matmul - n_moe) * batch * T
        + 12.0 * L * batch * T**2 * D / 2.0
        + 3.0 * L * (
            2.0 * batch * T * D * e
            + 4.0 * batch * T * e * cap * D
            + 6.0 / 3.0 * batch * T * k * (2 * D * FF + FF + D)
        )
    )
    assert moe_step_flops(cfg, batch, T) == pytest.approx(expect)


def test_moe_useful_flops_below_hardware_basis():
    """The useful basis drops capacity slack and the dispatch/combine
    einsums: strictly less than the hardware basis whenever cf > 1, and
    equal to dense-minus-FFN + router + k·T-token FFN by hand."""
    e, k = 4, 2
    cfg = _cfg(moe_experts=e, moe_top_k=k, moe_capacity_factor=1.25)
    batch = 8
    useful = moe_step_flops_useful(cfg, batch, T)
    assert useful < moe_step_flops(cfg, batch, T)
    n_moe = L * (D * e + e * (2 * D * FF + FF + D))
    n_matmul = _dense_param_count() - PAD_V * D - T * D + n_moe - L * (
        (D * FF + FF) + (FF * D + D)
    )
    dense = 6.0 * (n_matmul - n_moe) * batch * T
    attn = 12.0 * L * batch * T**2 * D / 2.0
    per_layer = (
        2.0 * batch * T * D * e
        + 2.0 * batch * T * k * (2 * D * FF + FF + D)
    )
    assert useful == pytest.approx(dense + attn + 3.0 * L * per_layer)


def test_moe_flops_exceed_matched_dense_at_top2():
    """Top-2 routing with capacity slack schedules MORE matmul work than the
    dense model whose d_ff equals one expert's — sanity direction check."""
    dense = gpt_step_flops(_cfg(), 8, T)
    moe = moe_step_flops(_cfg(moe_experts=4), 8, T)
    assert moe > dense


def test_mfu_none_when_peak_unknown():
    """On CPU there is no TPU peak-FLOPs entry: mfu must return None, not 0."""
    assert peak_flops_per_chip() is None  # tests force JAX_PLATFORMS=cpu
    assert mfu(_cfg(), 8, T, 0.1, 8) is None


def test_unknown_tpu_kind_is_an_error_not_a_default():
    """A TPU whose kind is not in the peaks table must raise — an MFU or a
    roofline against another chip's peak is worse than none — while a
    known kind reads its own row (the v5e reports "TPU v5 lite")."""
    from types import SimpleNamespace

    from dtc_tpu.utils.metrics import peak_hbm_gbps

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peak_flops_per_chip(v5e) == 197e12
    assert peak_hbm_gbps(v5e) == 819.0
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v99x")
    with pytest.raises(ValueError, match="no published peaks"):
        peak_flops_per_chip(unknown)
    with pytest.raises(ValueError, match="no published peaks"):
        peak_hbm_gbps(unknown)


def test_mfu_none_on_zero_step_time():
    assert mfu(_cfg(), 8, T, 0.0, 8) is None


# ---- comm-bytes estimator -------------------------------------------------


def test_comm_bytes_none_parallel_is_zero():
    c = comm_bytes_per_step(_cfg(), 8, T, {"data": 1, "model": 1, "pipe": 1}, "none")
    assert c == {"dp_allreduce": 0.0, "tp_allreduce": 0.0, "pp_p2p": 0.0, "total": 0.0}


def test_comm_bytes_dp_ring_allreduce():
    cfg = _cfg()
    c = comm_bytes_per_step(cfg, 8, T, {"data": 4, "model": 1, "pipe": 1}, "dp")
    expect = 2.0 * (4 - 1) / 4 * _dense_param_count() * 4  # fp32 grads
    assert c["dp_allreduce"] == pytest.approx(expect)
    assert c["tp_allreduce"] == 0.0 and c["pp_p2p"] == 0.0
    assert c["total"] == pytest.approx(expect)


def test_comm_bytes_fsdp_exceeds_dp():
    """ZeRO-3 re-phases the same gradient reduction but adds the forward
    and backward parameter all-gathers: 3/2 the DP wire bytes."""
    cfg = _cfg()
    shape = {"data": 4, "model": 1, "pipe": 1}
    dp = comm_bytes_per_step(cfg, 8, T, shape, "dp")["total"]
    fsdp = comm_bytes_per_step(cfg, 8, T, shape, "fsdp")["total"]
    assert fsdp == pytest.approx(1.5 * dp)


def test_comm_bytes_tp_activation_allreduce():
    cfg = _cfg(compute_dtype="float32")
    batch = 8
    c = comm_bytes_per_step(cfg, batch, T, {"data": 1, "model": 2, "pipe": 1}, "tp")
    act = batch * T * D * 4  # fp32 activations
    expect = 4.0 * L * 2.0 * (2 - 1) / 2 * act
    assert c["tp_allreduce"] == pytest.approx(expect)
    assert c["dp_allreduce"] == 0.0


def test_decode_step_flops_hand_computed():
    cfg = _cfg()
    batch, cache_len = 4, 20
    n_matmul = _dense_param_count() - PAD_V * D - T * D
    dense = 2.0 * n_matmul * batch          # one token, forward only
    attn = 4.0 * L * batch * cache_len * D  # QK + PV single-query rows
    assert decode_step_flops(cfg, batch, cache_len) == pytest.approx(dense + attn)


def test_decode_step_bytes_components_and_batch_amortization():
    cfg = _cfg(param_dtype="float32", compute_dtype="bfloat16")
    n_matmul = _dense_param_count() - PAD_V * D - T * D
    b8 = decode_step_bytes(cfg, 8, 16)
    # Weight read is 4 bytes/param and BATCH-INDEPENDENT — the
    # amortization that makes wider decode batches win.
    assert b8["weights"] == pytest.approx(n_matmul * 4.0)
    assert decode_step_bytes(cfg, 64, 16)["weights"] == b8["weights"]
    # KV terms scale with batch and cache length, in compute dtype.
    assert b8["kv_read"] == pytest.approx(2.0 * L * 16 * (H * (D // H)) * 2 * 8)
    assert decode_step_bytes(cfg, 8, 32)["kv_read"] == 2 * b8["kv_read"]
    assert b8["kv_write"] == pytest.approx(2.0 * L * (H * (D // H)) * 2 * 8)
    assert b8["total"] == pytest.approx(
        b8["weights"] + b8["kv_read"] + b8["kv_write"] + b8["activations"]
    )


def test_decode_step_bytes_int8_branch_hand_computed():
    """ISSUE 11: the dtype-aware KV byte model. int8 moves the 1-byte
    payload PLUS the per-(position, head) fp32 scales; float overrides
    move payload-only at their element size. Weights/activations are
    untouched by the cache dtype."""
    hd = H * (D // H)
    for kv, expect_pos in (
        ("bfloat16", 2.0 * hd * 2),                  # payload only
        ("float32", 2.0 * hd * 4),
        ("int8", 2.0 * hd * 1 + 2.0 * H * 4.0),      # payload + scales
    ):
        cfg = _cfg(param_dtype="float32", compute_dtype="bfloat16",
                   kv_cache_dtype=kv)
        got = decode_step_bytes(cfg, 8, 16)
        assert got["kv_read"] == pytest.approx(L * 16 * expect_pos * 8), kv
        assert got["kv_write"] == pytest.approx(L * expect_pos * 8), kv
    # "auto" remains byte-identical to the legacy compute-dtype model.
    auto = decode_step_bytes(
        _cfg(param_dtype="float32", compute_dtype="bfloat16"), 8, 16
    )
    bf16 = decode_step_bytes(
        _cfg(param_dtype="float32", compute_dtype="bfloat16",
             kv_cache_dtype="bfloat16"), 8, 16
    )
    assert auto == bf16
    # The headline ratio: int8 cuts the KV term ~2x vs bf16 (slightly
    # less than exact 2x — the scale sidecars are counted honestly).
    int8 = decode_step_bytes(
        _cfg(param_dtype="float32", compute_dtype="bfloat16",
             kv_cache_dtype="int8"), 8, 16
    )
    ratio = bf16["kv_read"] / int8["kv_read"]
    assert 1.5 < ratio < 2.0


def test_decode_roofline_is_bytes_over_bandwidth():
    cfg = _cfg()
    total = decode_step_bytes(cfg, 8, 16)["total"]
    assert decode_roofline_ms(cfg, 8, 16, hbm_gbps=819.0) == pytest.approx(
        total / 819e9 * 1e3
    )
    # Wider batch moves the floor sublinearly: weights amortize.
    assert decode_roofline_ms(cfg, 64, 16) < 8 * decode_roofline_ms(cfg, 8, 16)


def test_comm_bytes_pp_boundary_sends():
    cfg = _cfg(compute_dtype="float32")
    batch = 8
    c = comm_bytes_per_step(
        cfg, batch, T, {"data": 1, "model": 1, "pipe": 2}, "pp", pp_microbatches=2
    )
    micro_act = (batch / 2) * T * D * 4
    expect = 2.0 * (2 - 1) * 2 * micro_act  # fwd+bwd crossings x microbatches
    assert c["pp_p2p"] == pytest.approx(expect)


def test_comm_bytes_3d_composes_all_terms():
    cfg = _cfg(compute_dtype="float32")
    c = comm_bytes_per_step(
        cfg, 8, T, {"data": 2, "model": 2, "pipe": 2}, "3d", pp_microbatches=2
    )
    assert c["dp_allreduce"] > 0 and c["tp_allreduce"] > 0 and c["pp_p2p"] > 0
    assert c["total"] == pytest.approx(
        c["dp_allreduce"] + c["tp_allreduce"] + c["pp_p2p"]
    )
    # DP reduces the per-device PARAM SHARD (tree already split by TP x PP).
    full = comm_bytes_per_step(cfg, 8, T, {"data": 2}, "dp")["dp_allreduce"]
    assert c["dp_allreduce"] == pytest.approx(full / 4)


# --------------------------------------------------------------------------
# train_memory_bytes (ISSUE 14): the analytic HBM model the static memory
# audit cross-checks. Hand-computed on the tiny config.
# --------------------------------------------------------------------------

def test_train_memory_bytes_dp_fp32_hand_computed():
    from dtc_tpu.utils.metrics import train_memory_bytes

    cfg = _cfg(compute_dtype="float32", attention="dense")
    n = _dense_param_count()
    batch = 8
    m = train_memory_bytes(cfg, batch, T, {"data": 8}, "dp")
    # dp replicates params: full tree, fp32.
    assert m["params"] == pytest.approx(n * 4.0)
    assert m["master"] == 0.0          # fp32: the params ARE the masters
    assert m["moments"] == pytest.approx(n * 8.0)
    assert m["grads"] == pytest.approx(n * 4.0)
    # Activations: per layer (10d + 2ff) per token fp32 + the dense
    # fp32 (B, H, T, T) probs, + the logits row; batch local = 1.
    b_loc = batch / 8
    layer = b_loc * T * (10 * D + 2 * FF) * 4.0 + b_loc * H * T * T * 4.0
    acts = L * layer + b_loc * T * PAD_V * 4.0
    assert m["activations"] == pytest.approx(acts)
    assert m["batch_io"] == pytest.approx(2 * b_loc * T * 4.0)
    assert m["total"] == pytest.approx(
        m["params"] + m["moments"] + m["grads"] + m["activations"]
        + m["comm_buffers"] + m["batch_io"]
    )


def test_train_memory_bytes_bf16_mixed_vs_fp32():
    """The byte story the PERF table tells: bf16_mixed halves params and
    grads, adds a 4 B/param master row, keeps fp32 moments — state is
    14 vs 12 B/param, compute-path buffers halve."""
    from dtc_tpu.utils.metrics import train_memory_bytes

    cfg32 = _cfg(compute_dtype="float32", attention="dense")
    cfgbf = _cfg(
        compute_dtype="bfloat16", param_dtype="bfloat16", attention="dense"
    )
    n = _dense_param_count()
    f = train_memory_bytes(cfg32, 8, T, {"data": 1}, "dp")
    b = train_memory_bytes(cfgbf, 8, T, {"data": 1}, "dp",
                           precision="bf16_mixed")
    assert b["params"] == pytest.approx(f["params"] / 2)
    assert b["grads"] == pytest.approx(f["grads"] / 2)
    assert b["master"] == pytest.approx(n * 4.0)
    assert b["moments"] == f["moments"]
    # State per param: 2 + 4 + 8 = 14 vs 12.
    state_b = b["params"] + b["master"] + b["moments"]
    state_f = f["params"] + f["master"] + f["moments"]
    assert state_b == pytest.approx(n * 14.0)
    assert state_f == pytest.approx(n * 12.0)


def test_train_memory_bytes_fsdp_shards_state():
    from dtc_tpu.utils.metrics import train_memory_bytes

    cfg = _cfg(compute_dtype="float32", attention="dense")
    dp = train_memory_bytes(cfg, 8, T, {"data": 8}, "dp")
    fsdp = train_memory_bytes(cfg, 8, T, {"data": 8}, "fsdp")
    # ZeRO-3: params/masters/moments/grads all shard by the data degree.
    assert fsdp["params"] == pytest.approx(dp["params"] / 8)
    assert fsdp["moments"] == pytest.approx(dp["moments"] / 8)
    # Activations are untouched by FSDP.
    assert fsdp["activations"] == pytest.approx(dp["activations"])


def test_train_memory_bytes_remat_mlp_drops_ff_intermediates():
    from dtc_tpu.utils.metrics import train_memory_bytes

    full = train_memory_bytes(
        _cfg(compute_dtype="float32", attention="dense"), 8, T,
        {"data": 1}, "dp",
    )
    mlp = train_memory_bytes(
        _cfg(compute_dtype="float32", attention="dense", remat="mlp"), 8, T,
        {"data": 1}, "dp",
    )
    drop = L * 8 * T * 2 * FF * 4.0  # the d_ff-wide fc1/gelu intermediates
    assert full["activations"] - mlp["activations"] == pytest.approx(drop)
