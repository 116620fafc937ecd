"""Throughput and MFU accounting.

The reference reports only wall-clock step time (`/root/reference/train/train.py:87-90`).
The north star demands >=40% MFU on TPU, which requires actually computing
model FLOPs and knowing per-chip peak — both live here.
"""

from __future__ import annotations

import jax

from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models.gpt import adapter_param_count, param_count

#: Published per-chip peaks by ``device_kind`` substring: dense bf16
#: FLOP/s and HBM GB/s (Google Cloud TPU documentation, per generation).
_PEAKS = (
    ("v5 lite", 197e12, 819.0),   # v5e reports device_kind "TPU v5 lite"
    ("v5e", 197e12, 819.0),
    ("v5p", 459e12, 2765.0),
    ("v6", 918e12, 1640.0),       # Trillium
    ("v4", 275e12, 1228.0),
    ("v3", 123e12, 900.0),
    ("v2", 45e12, 700.0),
)


def _tpu_peaks(device) -> tuple[float, float] | None:
    """(bf16 FLOP/s, HBM GB/s) of a TPU; None off-TPU (CPU runs have no
    device metric). A TPU whose kind is not in the table is an error,
    not a default: a utilization against the wrong peak is worse than
    none."""
    if device.platform != "tpu":
        return None
    kind = getattr(device, "device_kind", "").lower()
    for key, flops, gbps in _PEAKS:
        if key in kind:
            return flops, gbps
    raise ValueError(
        f"no published peaks for TPU device_kind {device.device_kind!r}; "
        "add it to dtc_tpu/utils/metrics._PEAKS with its source"
    )


def peak_flops_per_chip(device=None) -> float | None:
    peaks = _tpu_peaks(device or jax.devices()[0])
    return None if peaks is None else peaks[0]


def peak_hbm_gbps(device=None) -> float:
    """HBM bandwidth the decode roofline divides by: the attached TPU's
    own figure; off-TPU (CPU tests, toy bench rows) the v5e's, the chip
    the roofline was modelled for."""
    peaks = _tpu_peaks(device or jax.devices()[0])
    return HBM_GBPS_V5E if peaks is None else peaks[1]


def gpt_step_flops(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """Total training FLOPs for one step (fwd + bwd).

    Standard 6ND matmul accounting over non-embedding params plus the
    causal attention score/value term 12·L·B·T²·d_model / 2.
    """
    n = param_count(cfg)
    # wte/wpe gathers are not matmuls; lm_head IS a matmul and is counted.
    # Subtract on the padded-vocab basis param_count uses (round-1 ADVICE:
    # mixing bases counted the pad rows as matmul FLOPs).
    n_matmul = n - cfg.padded_vocab_size * cfg.d_model - cfg.max_seq_len * cfg.d_model
    tokens = batch * seq_len
    dense = 6.0 * n_matmul * tokens
    attn = 12.0 * cfg.n_layers * batch * (seq_len**2) * cfg.d_model / 2.0
    return dense + attn


def pattern_matmul_params(cfg: ModelConfig) -> dict[str, float]:
    """Matmul parameters a token passes in ONE layer of each kind of a
    layer-pattern model (``models/pattern.py``), and in the head. A routed
    expert counts at the share of tokens it expects, ``top_k / experts``;
    the embedding gather, the norms and the depthwise convolution are not
    matmuls."""
    d = cfg.d_model
    nk, nv = cfg.gdn_key_heads * cfg.gdn_key_dim, cfg.gdn_value_heads * cfg.gdn_value_dim
    q_out, kv_out = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    attn = d * q_out + 2 * d * kv_out + q_out * d
    routed = (d * cfg.moe_experts
              + cfg.experts_held * cfg.moe_top_k / max(cfg.moe_experts, 1) * 3 * d * cfg.moe_d_ff)
    return {
        "gdn": d * (2 * nk + 2 * nv) + d * 2 * cfg.gdn_value_heads + nv * d,
        "gated_attn": attn + d * q_out,
        "attn": attn,
        "shortconv": d * 3 * d + d * d,
        "moe_shared": routed + 3 * d * cfg.moe_shared_d_ff + d,
        "moe": routed,
        "swiglu": 3 * d * cfg.d_ff,
        "head": d * cfg.padded_vocab_size,
    }


def gdn_scan_flops(cfg: ModelConfig, tokens: int) -> float:
    """Least work of one Gated DeltaNet layer's recurrence, forward and
    backward: per token and value head the state is decayed (dk dv), read
    twice (S^T k, S^T q) and given a rank-one update, 7 dk dv in all;
    the backward is twice the forward."""
    return 3.0 * 7.0 * cfg.gdn_key_dim * cfg.gdn_value_dim * cfg.gdn_value_heads * tokens


def pattern_step_flops(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """Training FLOPs of one step of a layer-pattern model: 6 x matmul
    parameters x tokens (head counted), causal attention as
    :func:`gpt_step_flops` counts it (12 B T^2 H hd / 2 a layer), and the
    recurrence's least work. A looped stack (``stack_passes``) runs its
    layers and its head once a pass: both count that many times, the
    parameters once (the exit gate's d products a token and pass are left
    out). Recomputation is not counted.
    ``benchmark/flops_qwen3_next.py``, ``flops_lfm2_moe.py`` and
    ``flops_ouro.py`` hold copies; tests keep them equal."""
    tokens = batch * seq_len
    per = pattern_matmul_params(cfg)
    census = cfg.layer_census()
    passes = cfg.stack_passes
    n_matmul = passes * (sum(n * (per[m] + per[f]) for m, f, n in census) + per["head"])
    n_attn = passes * sum(n for m, _, n in census if m in ("gated_attn", "attn"))
    n_gdn = passes * sum(n for m, _, n in census if m == "gdn")
    attn = 12.0 * n_attn * batch * seq_len**2 * cfg.n_heads * cfg.head_dim / 2.0
    return 6.0 * n_matmul * tokens + attn + n_gdn * gdn_scan_flops(cfg, tokens)


def step_flops(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """The step's operation count for whatever model ``cfg`` describes."""
    if cfg.layer_pattern:
        return pattern_step_flops(cfg, batch, seq_len)
    if cfg.moe_experts > 0:
        return moe_step_flops(cfg, batch, seq_len)
    return gpt_step_flops(cfg, batch, seq_len)


def moe_step_flops(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """Training FLOPs/step for the MoE model (``moe_experts > 0``).

    Counts the matmul work the step actually schedules (6ND-style: fwd
    2x + bwd 4x per MAC), with the dense FFN term replaced by the MoE
    block's four structural matmuls: router, dispatch/combine einsums
    (contraction over T — real MXU work, see PERF.md round 5), and the
    E-expert FFN over the static capacity slots. Capacity slack means
    E*cap >= k*T slots run regardless of how many are filled — that
    overhead is the einsum-dispatch design's price and is counted, so the
    MFU here is hardware utilization, not "useful-token" utilization.
    """
    from dtc_tpu.models.gpt import moe_capacity

    cap = moe_capacity(seq_len, cfg)
    dense, attn = _moe_non_expert_flops(cfg, batch, seq_len)
    d, e, ff = cfg.d_model, cfg.moe_experts, cfg.d_ff
    # Expert FFN on the same 2-FLOPs-per-param-per-token convention the
    # dense 6N term uses (biases included), over the e·cap static slots.
    per_layer_moe = (
        2.0 * batch * seq_len * d * e                    # router
        + 2.0 * 2.0 * batch * seq_len * e * cap * d      # dispatch + combine
        + 2.0 * batch * e * cap * (2 * d * ff + ff + d)  # expert FFN
    )
    moe = 3.0 * cfg.n_layers * per_layer_moe       # fwd + 2x bwd
    return dense + attn + moe


def _moe_non_expert_flops(cfg: ModelConfig, batch: int, seq_len: int) -> tuple[float, float]:
    """Shared prelude of both MoE FLOP bases: (dense-6N minus the MoE
    block, attention). Dense accounting excludes the router/expert params
    — a token does NOT visit every expert, so their FLOPs are counted
    structurally by each basis — and the subtracted block must be the
    FULL per-layer MoE param count from param_count: router + wi/bi/wo/bo
    INCLUDING the per-expert biases (round-5 ADVICE: omitting the
    e·(ff+d) bias params left them double-counted via the 6N term). One
    definition so a future accounting fix cannot skew the hardware-vs-
    useful comparison by landing in only one basis."""
    assert cfg.moe_experts > 0
    d, e, ff = cfg.d_model, cfg.moe_experts, cfg.d_ff
    n = param_count(cfg)
    n_matmul = n - cfg.padded_vocab_size * d - cfg.max_seq_len * d
    n_moe = cfg.n_layers * (d * e + e * (2 * d * ff + ff + d))
    dense = 6.0 * (n_matmul - n_moe) * batch * seq_len
    attn = 12.0 * cfg.n_layers * batch * (seq_len**2) * d / 2.0
    return dense, attn


def moe_step_flops_useful(cfg: ModelConfig, batch: int, seq_len: int) -> float:
    """Useful-FLOPs basis for the MoE step: only the k·T routed
    token-expert assignments count (no capacity slack — drops still
    count, matching Switch's nominal compute), dispatch/combine are
    uncounted bookkeeping.

    This basis is dispatch-implementation-independent, so it is the
    honest denominator-free A/B metric between ``moe_dispatch`` backends
    (``moe_step_flops`` counts the einsum backend's structural work —
    capacity slack and the (B,T,E,cap) contractions — which the sort
    backend does not schedule). PERF.md reports both.
    """
    dense, attn = _moe_non_expert_flops(cfg, batch, seq_len)
    d, e, ff, k = cfg.d_model, cfg.moe_experts, cfg.d_ff, cfg.moe_top_k
    per_layer_moe = (
        2.0 * batch * seq_len * d * e                          # router
        + 2.0 * batch * seq_len * k * (2 * d * ff + ff + d)    # k assignments/token
    )
    return dense + attn + 3.0 * cfg.n_layers * per_layer_moe


def _dtype_bytes(dtype: str) -> int:
    from dtc_tpu.config.schema import DTYPE_BYTES

    return DTYPE_BYTES.get(dtype, 4)


#: HBM bandwidth per v5e chip (GB/s). The decode roofline models the
#: floor at peak (optimistic floor = honest "pct of roofline" ceiling);
#: on a TPU :func:`peak_hbm_gbps` reads the attached chip's own figure.
HBM_GBPS_V5E = 819.0


def decode_step_flops(cfg: ModelConfig, batch: int, cache_len: int) -> float:
    """Matmul FLOPs for ONE decode step (every sequence in the batch
    appends one token; no backward).

    2·N_matmul per token for the dense side (same N_matmul basis as
    :func:`gpt_step_flops`: embedding gathers excluded, lm_head counted)
    plus single-query attention: per layer one (1, cache_len)·head score
    row and one value contraction — 4·cache_len·d_model FLOPs/layer/token.
    Decode FLOPs are tiny (the flagship's ~0.13 GF/token is <0.001% of a
    v5e-second); the step is bandwidth-bound, which is why the roofline
    below is a byte model, not a FLOP model.

    With an active adapter (``cfg.adapter.rank > 0``) the per-token
    low-rank term rides along — 2 FLOPs per adapter param per token, the
    same convention as the dense 2·N term — so LoRA-serving roofline rows
    stay honest about the extra work every token pays.
    """
    n = param_count(cfg)
    n_matmul = n - cfg.padded_vocab_size * cfg.d_model - cfg.max_seq_len * cfg.d_model
    dense = 2.0 * n_matmul * batch
    attn = 4.0 * cfg.n_layers * batch * cache_len * cfg.d_model
    lora = 2.0 * adapter_param_count(cfg) * batch
    return dense + attn + lora


def decode_step_bytes(
    cfg: ModelConfig, batch: int, cache_len: int
) -> dict[str, float]:
    """Estimated HBM bytes moved by ONE decode step — the decode
    roofline's numerator, by component:

    - ``weights``: every matmul parameter read once per step in
      ``param_dtype`` (batch amortizes this — THE reason wider decode
      batches win; fp32 master weights make it 4 bytes/param: an
      inference deployment would halve it by serving bf16 copies).
    - ``kv_read``: both caches read up to the frontier per layer
      (``cache_len`` columns) — the bandwidth-OPTIMAL traffic a
      single-query step needs, which keeps this a true floor. Neither
      current path achieves it: the XLA oracle and the single-tile fused
      kernels read the full ``max_seq_len`` buffer, and the blocked
      kernel's beyond-frontier skip predicates the compute only (the
      pipeline still copies every block in), so measured pct-of-roofline
      carries that slack on top of launch overhead. The element size
      follows ``cfg.kv_cache_dtype``: int8 moves the 1-byte payload PLUS
      the per-(position, head) fp32 scales (counted honestly — they are
      real HBM traffic, ~1/(2·D) of the bf16 payload), so int8 cuts this
      term ~2× vs bf16 and ~4× vs fp32, not exactly.
    - ``kv_write``: the new token's k/v appended per layer (same
      dtype-and-scales accounting as ``kv_read``).
    - ``activations``: residual stream + qkv/attn-out + the d_ff-wide MLP
      intermediate crossing HBM once each per layer, plus the final
      logits row — an estimate (XLA fuses some of these into neighbors),
      kept structural so the floor is conservative (higher floor = honest
      pct-of-roofline).
    - ``lora`` (adapter-enabled models only): each batch row reads ITS
      OWN gathered factors per step — unlike the base weights, the
      per-tenant term scales with batch and cannot amortize across rows,
      which is the multi-tenant design's bandwidth price.

    Returns the components plus ``total``.
    """
    pbytes = _dtype_bytes(cfg.param_dtype)
    cbytes = _dtype_bytes(cfg.compute_dtype)
    d, ff, hd = cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim
    n = param_count(cfg)
    n_matmul = n - cfg.padded_vocab_size * d - cfg.max_seq_len * d
    weights = float(n_matmul) * pbytes
    # Per cache position per layer: both payloads in the store dtype,
    # plus — int8 only — the two fp32 per-head scale vectors
    # (ops/decode_attention.quantize_kv).
    kv_pos = 2.0 * hd * _dtype_bytes(cfg.kv_store_dtype)
    if cfg.kv_quantized:
        kv_pos += 2.0 * cfg.n_heads * 4.0
    kv_read = cfg.n_layers * cache_len * kv_pos * batch
    kv_write = cfg.n_layers * kv_pos * batch
    # Per layer: residual in/out (2d), two LN reads (2d, fp32 but count
    # cbytes — fused), qkv out (3d), attention out + proj out (2d), MLP
    # intermediate write+read (2·d_ff), MLP out (d) ≈ 10·d + 2·d_ff per
    # token; plus the (padded) logits row the head writes.
    activations = (
        cfg.n_layers * (10.0 * d + 2.0 * ff) * cbytes * batch
        + cfg.padded_vocab_size * cbytes * batch
    )
    lora = float(adapter_param_count(cfg)) * pbytes * batch
    total = weights + kv_read + kv_write + activations + lora
    return {
        "weights": weights,
        "kv_read": kv_read,
        "kv_write": kv_write,
        "activations": activations,
        "lora": lora,
        "total": total,
    }


def decode_roofline_ms(
    cfg: ModelConfig,
    batch: int,
    cache_len: int,
    hbm_gbps: float | None = None,
) -> float:
    """Memory-bandwidth floor for one decode step, in ms (Pope et al.
    2022's small-batch regime: weight + cache reads at HBM speed bound
    the step; compute is negligible at these shapes). ``cache_len``
    should be the mean frontier over the measured run (prompt +
    new_tokens/2) when scoring a bench row."""
    total = decode_step_bytes(cfg, batch, cache_len)["total"]
    if hbm_gbps is None:
        hbm_gbps = peak_hbm_gbps()
    return total / (hbm_gbps * 1e9) * 1e3


def spec_decode_step_flops(
    cfg: ModelConfig, draft_cfg: ModelConfig, batch: int, cache_len: int,
    spec_k: int,
) -> float:
    """Matmul FLOPs for ONE speculative round (ISSUE 19): the k-query
    verify launch plus the draft's ``spec_k`` propose steps (the round
    runs one draft step more than it strictly needs so both cache
    frontiers land together — counted, because it is scheduled).

    Verify: every one of the ``spec_k`` in-register query positions pays
    the full dense 2·N_matmul pass, and its attention row reads
    ``cache_len`` cache columns plus its in-window causal prefix —
    ``Σ_j (cache_len + j) = k·cache_len + k(k-1)/2`` columns total.
    """
    n = param_count(cfg)
    d = cfg.d_model
    n_matmul = n - cfg.padded_vocab_size * d - cfg.max_seq_len * d
    dense = 2.0 * n_matmul * batch * spec_k
    cols = spec_k * cache_len + spec_k * (spec_k - 1) / 2.0
    attn = 4.0 * cfg.n_layers * batch * cols * d
    draft = spec_k * decode_step_flops(draft_cfg, batch, cache_len)
    return dense + attn + draft


def spec_decode_step_bytes(
    cfg: ModelConfig, draft_cfg: ModelConfig, batch: int, cache_len: int,
    spec_k: int,
) -> dict[str, float]:
    """Estimated HBM bytes for ONE speculative round — what makes
    ``pct_of_roofline`` on spec bench rows honest about the draft's
    bandwidth price (ISSUE 19). Components:

    - ``weights`` / ``kv_read``: the TARGET's, read ONCE — this is the
      whole speculative bet: one verify launch amortizes the dominant
      stream over up to ``spec_k`` emitted tokens instead of one.
    - ``kv_write`` / ``activations``: the target's, ×``spec_k`` — every
      window position writes its k/v and runs the dense stack.
    - ``draft``: ``spec_k`` FULL single-token draft steps (the
      ``lax.scan`` re-reads the draft weights and its cache every step —
      no amortization; this is the price the accepted-token rate must
      repay, and at ``draft_layers/n_layers`` depth it is the term that
      decides whether speculation wins on bandwidth at all).

    Returns the components plus ``total``. Score spec rows against
    ``ms_per_accepted_token``, never raw launch time: a row that hides
    the draft term would report >100% roofline at accept_rate 0.
    """
    tb = decode_step_bytes(cfg, batch, cache_len)
    draft = spec_k * decode_step_bytes(draft_cfg, batch, cache_len)["total"]
    out = {
        "weights": tb["weights"],
        "kv_read": tb["kv_read"],
        "kv_write": tb["kv_write"] * spec_k,
        "activations": tb["activations"] * spec_k,
        "lora": tb["lora"],  # structurally 0: spec serving is adapter-free
        "draft": draft,
    }
    out["total"] = sum(out.values())
    return out


def tokens_accepted_per_launch(emitted: int, launches: int) -> float | None:
    """Mean tokens landed per verify launch (``n_acc + 1`` per row per
    round, so ∈ [1, spec_k] when speculation runs) — the launch-economy
    numerator every spec bench row reports. None when nothing launched."""
    if launches <= 0:
        return None
    return emitted / launches


def ms_per_accepted_token(wall_s: float, emitted: int) -> float | None:
    """Wall milliseconds per ACCEPTED (emitted) token — the spec-vs-plain
    A/B metric: plain decode's equivalent is its ms/token, and a draft
    only earns its keep when this comes in lower. Proposals never appear
    in the denominator (the honesty rule the goodput ledger enforces on
    the time side). None when nothing was emitted."""
    if emitted <= 0:
        return None
    return wall_s * 1e3 / emitted


def tp_sharded_param_count(cfg: ModelConfig) -> int:
    """Parameters Megatron TP actually shards over "model": the block
    matmul kernels, their COLUMN-parallel biases (qkv/fc1 — out_proj/fc2
    biases live on the replicated ``embed_p`` output axis), and the
    vocab-parallel lm_head. LayerNorms, row-parallel biases, and the
    wte/wpe embeddings are TP-replicated. Mirrors the DEFAULT_RULES /
    FSDP_RULES tables (tests pin it against ``param_specs``); the MoE
    expert tensors shard over "model" via the ``experts_p`` rows and are
    counted whole (router replicated)."""
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    v = cfg.padded_vocab_size
    if cfg.moe_experts > 0:
        e = cfg.moe_experts
        ffn = e * (d * f + f + f * d + d)      # wi/bi/wo/bo (experts_p)
    else:
        ffn = d * f + f + f * d                # fc1 kernel+bias, fc2 kernel
    per_block = 4 * d * d + 3 * d + ffn        # q/k/v/out kernels, qkv biases
    return L * per_block + d * v + v           # + lm_head kernel+bias


def comm_bytes_per_step(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    mesh_shape: dict[str, int],
    parallel: str,
    pp_microbatches: int = 1,
) -> dict[str, float]:
    """Estimated per-device collective traffic for ONE training step, in
    bytes, from the active parallelism config — no profiler needed.

    Standard ring-collective accounting (each of the three terms is what
    the paper's DP/TP/PP comparison trades off):

    - ``dp_allreduce``: gradient all-reduce over the ``data`` axis —
      ``2·(d-1)/d · P`` bytes per device (reduce-scatter + all-gather),
      with gradients in ``param_dtype``. FSDP pays the same wire bytes
      re-phased (param all-gather fwd + bwd, grad reduce-scatter):
      ``3·(d-1)/d · P``.
    - ``tp_allreduce``: Megatron TP's two activation all-reduces in
      forward and two in backward per layer over the ``model`` axis, on
      ``(B, T, d_model)`` activations in ``compute_dtype``.
    - ``pp_p2p``: boundary-activation sends between adjacent stages —
      ``(stages-1)`` cuts crossed forward and backward by every
      microbatch.

    Combined DP×FSDP×TP meshes (``parallel == "fsdp"`` with ``model > 1``
    — configs/train_config_3d.yaml, ISSUE 12): the naive
    ``n_params / model`` per-device share over-divides, because TP only
    shards the matmul family (qkv/out/fc1/fc2 kernels + their
    column-parallel biases, lm_head) while LayerNorms, row-parallel
    biases, and the embeddings stay TP-replicated — and FSDP gathers /
    reduce-scatters each device's ACTUAL share. The 3d term therefore
    splits the tree: ``n_tp_sharded / model + n_tp_replicated``. Plain DP
    keeps the historical formula (committed audit baselines pin it).
    The estimate is transport-independent on purpose: the overlapped
    ring (ops/overlap_collectives.py) re-phases exactly these wire bytes
    under compute, it does not change them — which is what lets the
    census cross-check hold for both ``collectives:`` modes.

    Returns per-collective estimates plus their ``total``; all terms are
    0.0 for axes of size 1, so the dict is safe to emit unconditionally.
    """
    d_axis = max(mesh_shape.get("data", 1), 1)
    m_axis = max(mesh_shape.get("model", 1), 1)
    p_axis = max(mesh_shape.get("pipe", 1), 1)
    pbytes = _dtype_bytes(cfg.param_dtype)
    abytes = _dtype_bytes(cfg.compute_dtype)
    n_params = param_count(cfg)

    dp = 0.0
    if d_axis > 1:
        factor = 3.0 if parallel == "fsdp" else 2.0
        if parallel == "fsdp" and m_axis > 1:
            # DP×FSDP×TP: per-device share = TP-sharded params / model +
            # the TP-replicated remainder (each TP rank stores and
            # gathers its own full copy of those).
            n_tp = tp_sharded_param_count(cfg)
            local_params = (n_tp / m_axis + (n_params - n_tp)) / p_axis
        else:
            # Per-device parameter share: TP/PP already split the tree.
            local_params = n_params / (m_axis * p_axis)
        dp = factor * (d_axis - 1) / d_axis * local_params * pbytes

    tp = 0.0
    if m_axis > 1:
        act = batch * seq_len * cfg.d_model * abytes / d_axis  # per-device B shard
        tp = 4.0 * cfg.n_layers * 2.0 * (m_axis - 1) / m_axis * act

    pp = 0.0
    if p_axis > 1:
        micro = batch / max(pp_microbatches, 1) / d_axis
        act = micro * seq_len * cfg.d_model * abytes
        pp = 2.0 * (p_axis - 1) * pp_microbatches * act

    return {
        "dp_allreduce": dp,
        "tp_allreduce": tp,
        "pp_p2p": pp,
        "total": dp + tp + pp,
    }


def train_memory_bytes(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    mesh_shape: dict[str, int],
    parallel: str,
    precision: str = "fp32",
) -> dict[str, float]:
    """Analytic per-device HBM budget for ONE training step, in bytes —
    the cross-check target of the graph auditor's static memory plan
    (``dtc_tpu/analysis/memory.py``) and the first metrics helper that
    accounts OPTIMIZER-STATE bytes at all (ROADMAP item 3: the all-fp32
    AdamW state is the dominant residency at scale; Rajbhandari et al.'s
    ZeRO accounting is the model here).

    Components, all per device (TP/FSDP split applied the same way
    :func:`comm_bytes_per_step` splits its dp term):

    - ``params``: the model's resident parameters in ``param_dtype``
      (bf16_mixed: 2 bytes — the policy stores bf16 params).
    - ``master``: fp32 master weights (bf16_mixed only; 0 under fp32 —
      the params ARE the masters). The honest accounting: bf16_mixed
      state is params 2 + master 4 + moments 8 = 14 B/param vs fp32's
      12 — the +2 master tax buys the halved param/grad bytes every
      fwd+bwd pass actually touches.
    - ``moments``: AdamW mu+nu, fp32 under both policies (2 x 4 bytes).
    - ``grads``: the transient gradient tree in ``param_dtype`` (bf16
      halves it — and it is also the DP/FSDP wire payload).
    - ``activations``: saved-for-backward estimate — per layer the
      residual/qkv/attn-out/proj/MLP intermediates (~10·d + 2·d_ff per
      token in ``compute_dtype``) plus, for dense attention, the fp32
      (B, H, T, T) probability tensor autodiff saves (flash recomputes
      it — the kernel's O(T) memory claim), plus the logits row. remat
      "block"/"mlp" drop the block/MLP share and keep residuals.
    - ``comm_buffers``: the collective landing buffers, taken as the
      wire-byte estimate (:func:`comm_bytes_per_step` total).
    - ``batch_io``: the token batch (x, y) in int32.

    Structural estimate, not a simulator: XLA fuses, rematerializes, and
    reuses buffers — the audit cross-check applies a wide warn-band and
    the committed baselines pin the measured numbers.
    """
    d_axis = max(mesh_shape.get("data", 1), 1)
    m_axis = max(mesh_shape.get("model", 1), 1)
    p_axis = max(mesh_shape.get("pipe", 1), 1)
    n = param_count(cfg)
    n_tp = tp_sharded_param_count(cfg)

    # Per-device parameter share: TP shards only the matmul family; FSDP
    # shards everything over "data"; PP splits layers.
    local = (n_tp / m_axis + (n - n_tp)) / p_axis
    if parallel == "fsdp" and d_axis > 1:
        local = local / d_axis

    pbytes = float(_dtype_bytes("bfloat16" if precision == "bf16_mixed"
                                else cfg.param_dtype))
    cbytes = float(_dtype_bytes(cfg.compute_dtype))
    params = local * pbytes
    master = local * 4.0 if precision == "bf16_mixed" else 0.0
    moments = local * 8.0
    grads = local * pbytes

    b_loc = batch / d_axis
    dm, ff = cfg.d_model, cfg.d_ff
    per_tok = (10.0 * dm + 2.0 * ff) * cbytes
    layer_acts = b_loc * seq_len * per_tok
    if cfg.attention == "dense":
        # Dense attention saves the fp32 (B, H, T, T) probs for backward.
        layer_acts += b_loc * cfg.n_heads * (seq_len ** 2) * 4.0
    n_layers = cfg.n_layers / p_axis
    if cfg.remat_mode in ("block", "block_save_flash"):
        # Block remat keeps one residual per layer + one block's working
        # set; model the residuals only (conservative floor).
        acts = n_layers * b_loc * seq_len * dm * cbytes + layer_acts
    elif cfg.remat_mode == "mlp":
        acts = n_layers * (layer_acts - b_loc * seq_len * 2.0 * ff * cbytes)
    else:
        acts = n_layers * layer_acts
    acts += b_loc * seq_len * cfg.padded_vocab_size * cbytes / m_axis  # logits
    comm = comm_bytes_per_step(
        cfg, batch, seq_len, mesh_shape, parallel
    )["total"]
    batch_io = 2.0 * b_loc * seq_len * 4.0
    total = params + master + moments + grads + acts + comm + batch_io
    return {
        "params": params,
        "master": master,
        "moments": moments,
        "grads": grads,
        "activations": acts,
        "comm_buffers": comm,
        "batch_io": batch_io,
        "total": total,
    }


def mfu(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    step_time_s: float,
    n_chips: int,
    moe_basis: str = "hardware",
) -> float | None:
    """Model FLOPs utilization; None off-TPU or at zero step time.

    ``moe_basis`` selects the MoE FLOP accounting (dense models ignore
    it): "hardware" = :func:`moe_step_flops` (einsum-structural work,
    capacity slack + dispatch counted), "useful" =
    :func:`moe_step_flops_useful` (k·T routed tokens only — the
    dispatch-backend-independent A/B number the PERF.md MoE tables lead
    with).
    """
    peak = peak_flops_per_chip()
    if peak is None or step_time_s <= 0:
        return None
    if cfg.moe_experts > 0 and not cfg.layer_pattern and moe_basis == "useful":
        flops = moe_step_flops_useful(cfg, batch, seq_len)
    else:
        flops = step_flops(cfg, batch, seq_len)
    return flops / (step_time_s * peak * n_chips)
