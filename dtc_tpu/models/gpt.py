"""GPT model — flax linen, strategy-agnostic, pipeline-splittable.

Capability parity with the reference model family
(`/root/reference/model/GPTModel.py`, `TransformerBlock.py`,
`CausalSelfAttention.py`, `MLP.py`): decoder-only pre-LN GPT-2-style
transformer with learned absolute position embeddings, separate q/k/v
projections, GELU MLP, dropout, and a pipeline-splittable embed/stage/head
decomposition with scan-over-layers parameter stacking — the structure both
TP sharding rules and PP stage-chunking key on
(`/root/reference/model/GPTModel.py:25-82`).

TPU-native differences:

- The split is *module-level* (GPTEmbed / GPTStage / GPTHead composed by
  GPT), not method-level: pipeline stages apply the sub-modules standalone
  with their own param subtrees — no ``method=`` plumbing — and the full
  param tree is already {"embed", "stage", "head"}, so the PP layout is a
  leaf reshape, not a re-init (the reference re-inits per stage with
  different keys, `/root/reference/train/train.py:143-161`).
- No ``parallel: str`` branches in model code. Activations carry *logical*
  axis names via ``nn.with_logical_constraint``; the active rule table +
  mesh shape decide physical sharding (cf. reference's per-strategy branches
  at `/root/reference/model/CausalSelfAttention.py:28-31,49-50`).
- Mixed precision: the storage/compute pair flows from config
  (``param_dtype``/``compute_dtype``; the default flagship pairing is fp32
  params + bf16 MXU-native matmuls, and ``OptimConfig.precision:
  bf16_mixed`` lifts BOTH to bf16 with fp32 master weights held by the
  optimizer — train/train_step.resolve_precision, ISSUE 14). The
  fp32-MANDATED islands are hard-coded by design and stay fp32 under every
  policy: LayerNorm (``ln``/``GPTHead``), MoE routing softmax
  (``MoEMLP``), and the CE loss (ops/fused_ce.py). Those scope names are a
  CONTRACT with the graph auditor: analysis/dtypelint.py allowlists
  exactly them (renaming one fails tests/test_numerics.py), and
  analysis/numerics.py asserts the islands' exp/rsqrt lower fp32 in every
  audited program.
- Attention is a pluggable op (dense / Pallas flash / ring); causality lives
  inside the op — no (1,1,T,T) mask tensor threaded through the model
  (cf. `/root/reference/model/GPTModel.py:50-51`).
- Optional per-block rematerialisation (``remat``) to trade FLOPs for HBM.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from dtc_tpu.adapters.lora import apply_lora
from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.ops.attention import causal_attention


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


class OverlapDense(nn.Module):
    """``nn.Dense`` twin whose matmul rides the overlapped-collectives
    ring (ops/overlap_collectives.py, ISSUE 12).

    Same parameter tree, names, shapes, and init as ``nn.Dense`` — so the
    sharding rule table, checkpoints, and LoRA injection see an identical
    layer — but the product is computed by the fused
    all-gather-then-matmul whenever the active rules shard "embed_p"
    (FSDP): each ring step matmuls the parameter shard already on-chip
    while the next shard streams in, and the backward pass streams the
    weight-gradient reduce-scatter through the ring the same way.
    ``shard_axis`` names which KERNEL axis carries "embed_p" under
    FSDP_RULES: 0 for the contraction axis (q/k/v/fc1 — d_model in), 1
    for the output axis (out_proj/fc2 — d_model out); ``tp_logical`` is
    the logical axis of the OTHER kernel dimension ("qkv" / "mlp"), so on
    a DP×FSDP×TP mesh the op goes manual over the Megatron axis too and
    makes its row-parallel psums explicit. Every inapplicable call (no
    FSDP axis in scope, eager init, decode's narrow batches,
    non-divisible tails) falls back to the identical plain dot inside the
    op, so selecting ``collectives: overlapped`` is safe on any config.
    """

    features: int
    shard_axis: int
    tp_logical: str = "qkv"
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from flax.linen import dtypes

        from dtc_tpu.ops.overlap_collectives import overlap_dense_matmul
        from dtc_tpu.parallel.sharding import fsdp_axis_in_scope

        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.param_dtype,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            self.param_dtype,
        )
        x, kernel, bias = dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype
        )
        tp_axis = dict(nn.get_logical_axis_rules()).get(self.tp_logical)
        y = overlap_dense_matmul(
            x, kernel, shard_axis=self.shard_axis,
            axis_name=fsdp_axis_in_scope(),
            tp_axis=tp_axis if isinstance(tp_axis, str) else None,
        )
        return y + bias


def _dense(cfg: ModelConfig, features: int, name: str, shard_axis: int,
           cdtype, pdtype, tp_logical: str = "qkv") -> nn.Module:
    """The dense-layer factory every matmul site shares: ``nn.Dense`` for
    ``collectives: xla`` (byte-identical to every pre-ISSUE-12 program),
    :class:`OverlapDense` for ``overlapped``."""
    if cfg.collectives == "overlapped":
        return OverlapDense(
            features, shard_axis=shard_axis, tp_logical=tp_logical,
            name=name, dtype=cdtype, param_dtype=pdtype,
        )
    return nn.Dense(features, name=name, dtype=cdtype, param_dtype=pdtype)


class CausalSelfAttention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        train: bool,
        decode: bool = False,
        decode_index: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        cdtype = _dtype(cfg.compute_dtype)
        pdtype = _dtype(cfg.param_dtype)

        def dense(name, shard_axis=0):
            # LoRA injection point (dtc_tpu/adapters/): with an active
            # adapter config and a targeted name, the base Dense output
            # gains a low-rank delta from the SEPARATE "lora" collection;
            # at rank 0 apply_lora is an identity passthrough that creates
            # no variables — the rank-0 graph is bitwise the base graph.
            # ``shard_axis`` is the kernel axis FSDP shards (0 = the
            # d_model contraction for q/k/v, 1 = the d_model output for
            # out_proj) — consumed only by the overlapped-collectives
            # flavor (_dense, ISSUE 12).
            layer = _dense(cfg, cfg.d_model, name, shard_axis, cdtype, pdtype)
            return lambda h: apply_lora(
                self, layer, h, cfg=cfg, name=name, train=train
            )

        # named_scope component annotation (ISSUE 8): trace-time-only HLO
        # op_name provenance so XLA fusions roll up to model components in
        # the device-time attribution (obs/devprof.py). attn_qkv /
        # attn_kernel / attn_proj split the attention block into its
        # projection, kernel, and output legs — the same cut PERF.md's
        # hand-read rounds used.
        with jax.named_scope("attn_qkv"):
            q = dense("q_proj")(x).reshape(b, t, cfg.n_heads, cfg.head_dim)
            k = dense("k_proj")(x).reshape(b, t, cfg.n_heads, cfg.head_dim)
            v = dense("v_proj")(x).reshape(b, t, cfg.n_heads, cfg.head_dim)

        if decode:
            # Autoregressive KV-cache path (inference; single device or
            # GSPMD — no flash/ring). The cache holds max_seq_len k/v per
            # layer in the PACKED model-native (B, S, H·D) layout — the
            # raw byte order of the k/v projections, so the write below is
            # a lane-aligned in-place dynamic_update_slice with no
            # relayout, and the fused decode kernel reads it directly.
            # ``decode_index`` is the write frontier, owned by GPT (one
            # scalar per model, not one per layer — the scan body carries
            # it, it never updates inside the loop). CALLER CONTRACT:
            # total decoded length must stay <= max_seq_len — past it,
            # dynamic_update_slice CLAMPS the write start and logits go
            # silently wrong (the index is traced, so this cannot raise
            # here; GPT.__call__ emits the checkify guard under
            # cfg.debug_checks and dtc_tpu.generate.generate enforces the
            # bound at its static API surface).
            from dtc_tpu.ops.attention import decode_attention
            from dtc_tpu.ops import decode_attention as fused

            if decode_index is None:
                # ValueError, not assert: must fire under `python -O` too
                # (same rationale as parallel/pipeline.py's stage check).
                raise ValueError(
                    "decode=True requires the GPT-owned decode_index (apply "
                    "the full GPT model, not a bare stage, for decode)"
                )
            idx = decode_index
            hd = cfg.n_heads * cfg.head_dim
            quant = cfg.kv_quantized
            kv_dt = jnp.int8 if quant else _dtype(cfg.kv_store_dtype)
            ck = self.variable(
                "cache", "k", jnp.zeros, (b, cfg.max_seq_len, hd), kv_dt,
            )
            cv = self.variable(
                "cache", "v", jnp.zeros, (b, cfg.max_seq_len, hd), kv_dt,
            )
            if quant:
                # Per-(position, head) fp32 scales next to the int8
                # payload (ops/decode_attention.quantize_kv) — ~1/(2·D)
                # of the bf16 payload's bytes, accounted as metadata
                # overhead (utils/metrics.decode_step_bytes counts it in
                # the roofline; the paged-pool budget does not).
                cks = self.variable(
                    "cache", "k_scale", jnp.zeros,
                    (b, cfg.max_seq_len, cfg.n_heads), jnp.float32,
                )
                cvs = self.variable(
                    "cache", "v_scale", jnp.zeros,
                    (b, cfg.max_seq_len, cfg.n_heads), jnp.float32,
                )

            # Logical constraints shard the cache over heads under a TP
            # mesh (the packed lane axis IS the head axis × head_dim, so
            # sharding it over "model" is head sharding — and the scale
            # cache's last axis IS the head axis; seq stays unsharded and
            # the dynamic update partitions trivially); decode then runs
            # head-parallel up to out_proj's all-reduce, same as training.
            def cache_write(var, update):
                if idx.ndim == 1:
                    # Per-slot frontiers (the serving runtime's continuous
                    # batching: the cache index is (B,), one write
                    # position per slot). The batched dynamic_update_slice
                    # lowers to a scatter — each row writes at its own
                    # frontier.
                    new = jax.vmap(
                        lambda c, u, i: jax.lax.dynamic_update_slice(
                            c, u, (i, 0)
                        )
                    )(var.value, update, idx)
                else:
                    new = jax.lax.dynamic_update_slice(
                        var.value, update, (0, idx, 0)
                    )
                var.value = nn.with_logical_constraint(
                    new, ("batch", "seq", "heads")
                )

            if quant:
                kq, ksc = fused.quantize_kv(k.reshape(b, t, hd), cfg.n_heads)
                vq, vsc = fused.quantize_kv(v.reshape(b, t, hd), cfg.n_heads)
                cache_write(ck, kq)
                cache_write(cv, vq)
                cache_write(cks, ksc)
                cache_write(cvs, vsc)
            else:
                cache_write(ck, k.reshape(b, t, hd).astype(kv_dt))
                cache_write(cv, v.reshape(b, t, hd).astype(kv_dt))
            if fused.use_fused(cfg, t):
                # The serving fast path: one Pallas launch reads the whole
                # packed cache, masked to the frontier (int8 caches ride
                # their scales in; dequant is in-register). Multi-token
                # calls (prefill — once per sequence) and unsupported
                # cache lengths take the XLA oracle below. fused_layers
                # reaching HERE means a call the megakernel declined
                # (prefill, or an unsupported shape) — the per-layer
                # kernel is its fallback before the oracle.
                with jax.named_scope("attn_kernel"):
                    out = fused.fused_decode_attention(
                        q.reshape(b, 1, hd), ck.value, cv.value, idx,
                        h=cfg.n_heads, d=cfg.head_dim,
                        k_scale=cks.value if quant else None,
                        v_scale=cvs.value if quant else None,
                    ).reshape(b, 1, cfg.n_heads, cfg.head_dim)
            else:
                with jax.named_scope("attn_kernel"):
                    if quant:
                        k_full = fused.dequantize_kv(
                            ck.value, cks.value, cfg.n_heads, cdtype
                        )
                        v_full = fused.dequantize_kv(
                            cv.value, cvs.value, cfg.n_heads, cdtype
                        )
                    else:
                        k_full, v_full = ck.value, cv.value
                    out = decode_attention(
                        q,
                        k_full.reshape(b, cfg.max_seq_len, cfg.n_heads, cfg.head_dim),
                        v_full.reshape(b, cfg.max_seq_len, cfg.n_heads, cfg.head_dim),
                        idx,
                    )
        else:
            # Head axis is the TP-sharded axis: under TP each device holds
            # n_heads / model_parallelism heads and attention is
            # embarrassingly parallel until out_proj's row-parallel
            # all-reduce.
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "head_dim"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "head_dim"))

            with jax.named_scope("attn_kernel"):
                out = causal_attention(
                    q, k, v,
                    impl=cfg.attention,
                    block_q=cfg.attention_block_q,
                    block_kv=cfg.attention_block_kv,
                    block_q_bwd=cfg.attention_block_q_bwd,
                    block_kv_bwd=cfg.attention_block_kv_bwd,
                )
        with jax.named_scope("attn_proj"):
            out = out.reshape(b, t, cfg.d_model)
            out = dense("out_proj", shard_axis=1)(out)
            # Row-parallel output: constraining back to embed-replicated
            # makes XLA insert the TP all-reduce here.
            out = nn.with_logical_constraint(out, ("batch", "seq", "embed"))
        return out


class MLP(nn.Module):
    cfg: ModelConfig
    # Only consulted by the LoRA dropout path (adapters/lora.py); the base
    # MLP has no train-dependent ops, which is why the field can default.
    train: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        cdtype = _dtype(cfg.compute_dtype)
        pdtype = _dtype(cfg.param_dtype)
        with jax.named_scope("mlp"):
            # FSDP shards fc1's d_model CONTRACTION axis and fc2's d_model
            # OUTPUT axis — the shard_axis the overlapped-collectives
            # flavor of _dense keys its ring schedule on (ISSUE 12).
            fc1 = _dense(cfg, cfg.d_ff, "fc1", 0, cdtype, pdtype, "mlp")
            h = apply_lora(self, fc1, x, cfg=cfg, name="fc1", train=self.train)
            h = nn.gelu(h)
            h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))  # column-parallel
            fc2 = _dense(cfg, cfg.d_model, "fc2", 1, cdtype, pdtype, "mlp")
            h = apply_lora(self, fc2, h, cfg=cfg, name="fc2", train=self.train)
            h = nn.with_logical_constraint(h, ("batch", "seq", "embed"))  # row-parallel all-reduce
        return h


def moe_capacity(t: int, cfg: ModelConfig) -> int:
    """Slots per expert per batch row: ceil(t * top_k * capacity_factor / E).
    Shared with tests so the parity reference cannot drift from the model."""
    import math

    return max(
        1, math.ceil(t * cfg.moe_top_k * cfg.moe_capacity_factor / cfg.moe_experts)
    )


class MoEMLP(nn.Module):
    """Mixture-of-Experts FFN with expert parallelism (beyond the reference,
    which is dense-only — `/root/reference/model/MLP.py`).

    GShard/Switch-style top-k routing with STATIC capacity slots; this
    module owns the router and parameters, while the token<->slot
    permutation is a pluggable backend from ``ops/moe_dispatch.py``
    (``cfg.moe_dispatch``): ``einsum`` contracts one-hot ``(B,T,E,cap)``
    dispatch/combine tensors over T (gather-free, MXU-shaped, cost grows
    with E — PERF.md round 5), ``sort`` executes the same permutation as
    an int32 slot map + row gathers (MegaBlocks-style, O(B·T·k·d) data
    movement at any E). Routing — and therefore which tokens reach which
    expert, the capacity drop policy, and the aux loss — is computed once
    and shared, so the switch is a pure execution-strategy A/B.

    Expert tensors carry an "experts" logical axis mapped to the "model"
    mesh axis, so XLA's partitioner emits the expert-parallel collectives
    (tokens to their experts' devices and back) exactly as it emits TP
    collectives — EP is a rule-table entry, not a hand-written comm
    schedule, and holds for both backends (tests/test_collectives_hlo.py).
    Tokens over an expert's capacity are dropped (contribute zero; the
    residual stream carries them — standard Switch semantics). The
    load-balance aux loss (Switch eq. 4-6, coefficient pre-applied) is
    sowed into the "aux_loss" collection; the train step adds it to the
    CE loss.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from dtc_tpu.ops import moe_dispatch as md

        cfg = self.cfg
        e, k = cfg.moe_experts, cfg.moe_top_k
        cdtype = _dtype(cfg.compute_dtype)
        b, t, d = x.shape
        cap = moe_capacity(t, cfg)

        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (e, d, cfg.d_ff),
            _dtype(cfg.param_dtype),
        )
        bi = self.param("bi", nn.initializers.zeros_init(), (e, cfg.d_ff),
                        _dtype(cfg.param_dtype))
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (e, cfg.d_ff, d),
            _dtype(cfg.param_dtype),
        )
        bo = self.param("bo", nn.initializers.zeros_init(), (e, d),
                        _dtype(cfg.param_dtype))

        # Routing in fp32 (softmax numerics), per batch row — shared by
        # both dispatch backends, bitwise.
        logits = nn.Dense(
            e, name="router", use_bias=False,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )(x.astype(jnp.float32))
        routing = md.top_k_routing(jax.nn.softmax(logits, axis=-1), k, cap)
        self.sow(
            "aux_loss", "load_balance",
            md.load_balance_loss(routing, k, cfg.moe_aux_coef),
        )

        if cfg.moe_dispatch == "sort":
            x_e = md.sort_dispatch(x, routing, cap)
        else:
            # Build the one-hot pair ONCE; dispatch and combine each
            # consume their half (the buildup is ~18% of the E=8 step).
            dispatch, combine = md.dispatch_combine_tensors(routing, cap)
            x_e = md.einsum_dispatch(x, dispatch)
        x_e = nn.with_logical_constraint(x_e, ("batch", "experts", None, "embed"))
        y_e = md.expert_ffn(
            x_e, wi.astype(cdtype), bi.astype(cdtype),
            wo.astype(cdtype), bo.astype(cdtype),
        )
        y_e = nn.with_logical_constraint(y_e, ("batch", "experts", None, "embed"))
        if cfg.moe_dispatch == "sort":
            y = md.sort_combine(y_e, routing, cap)
        else:
            y = md.einsum_combine(y_e, combine)
        return nn.with_logical_constraint(y, ("batch", "seq", "embed"))


class Block(nn.Module):
    """Pre-LN transformer block: x + Attn(LN(x)); x + MLP(LN(x)) — the MLP
    is the dense reference FFN or, with ``moe_experts > 0``, the
    expert-parallel :class:`MoEMLP`."""

    cfg: ModelConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        train: bool,
        decode: bool = False,
        decode_index: jax.Array | None = None,
    ) -> jax.Array:
        cfg = self.cfg

        def ln(name):
            # LayerNorm in fp32 for numerical stability.
            return nn.LayerNorm(name=name, dtype=jnp.float32, param_dtype=jnp.float32)

        h = ln("ln_1")(x).astype(_dtype(cfg.compute_dtype))
        x = x + nn.Dropout(cfg.dropout, deterministic=not train)(
            CausalSelfAttention(cfg, name="attn")(
                h, train=train, decode=decode, decode_index=decode_index
            )
        )
        h = ln("ln_2")(x).astype(_dtype(cfg.compute_dtype))
        if cfg.moe_experts > 0:
            moe_cls = MoEMLP
            if cfg.remat_mode == "mlp" and train and not decode:
                # Same selective-remat contract as the dense branch: the
                # (B, E, cap, d_ff) expert intermediates are the memory to
                # trade away.
                moe_cls = nn.remat(MoEMLP, prevent_cse=False)
            ff = moe_cls(cfg, name="moe")(h)
        else:
            mlp_cls = MLP
            if cfg.remat_mode == "mlp" and train and not decode:
                # Selective remat: only the MLP's d_ff-wide intermediates
                # are recomputed in backward; the attention path's
                # flash-kernel residuals (q/k/v/out/lse) stay saved, so the
                # backward scan skips the ~0.7 ms/layer attention recompute
                # the "block" mode pays (measured, PERF.md round 4).
                mlp_cls = nn.remat(MLP, prevent_cse=False)
            ff = mlp_cls(cfg, train=train, name="mlp")(h)
        x = x + nn.Dropout(cfg.dropout, deterministic=not train)(ff)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class _ScanBlock(nn.Module):
    """Carry adapter so Block can run under nn.scan. The carry is
    ``(h, decode_index)`` — the decode write frontier rides along
    UNCHANGED (None outside decode), so the scan body stays one fused
    block per layer with no per-layer index variable or counter update
    (the pre-hoist layout stacked an (L,) index in the cache collection
    and re-incremented it in every layer's program)."""

    cfg: ModelConfig
    train: bool
    decode: bool = False

    @nn.compact
    def __call__(self, carry, _):
        h, idx = carry
        h = Block(self.cfg)(
            h, train=self.train, decode=self.decode, decode_index=idx
        )
        return (h, idx), None


class GPTEmbed(nn.Module):
    """Token + learned-position embedding with dropout (pipeline stage 0 head-end).

    ``lookup="onehot"`` computes the token lookup as one_hot(x) @ table — a
    matmul instead of a gather. The pipeline step uses it because XLA's SPMD
    partitioner cannot partition a sharded gather inside a partially-manual
    (shard_map over "pipe") region, while a matmul partitions fine — and it
    rides the MXU. Both lookups share identical params.
    """

    cfg: ModelConfig
    lookup: str = "gather"

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        train: bool = True,
        pos_offset: int | jax.Array = 0,
        decode: bool = False,
    ) -> jax.Array:
        cfg = self.cfg
        pdtype = _dtype(cfg.param_dtype)
        _, t = x.shape
        # Decode position bookkeeping is GPT's: the single cache "index"
        # counter doubles as the position offset (cache slots and
        # positions advance in lockstep by construction), passed in via
        # ``pos_offset`` — no per-module counters to keep in sync.
        del decode
        wte = nn.Embed(cfg.padded_vocab_size, cfg.d_model, name="wte", param_dtype=pdtype)
        if self.lookup == "onehot":
            onehot = jax.nn.one_hot(x, cfg.padded_vocab_size, dtype=_dtype(cfg.compute_dtype))
            tok = onehot @ wte.embedding.astype(_dtype(cfg.compute_dtype))
        else:
            tok = wte(x)
        # Positions are a contiguous slice of the table, not a gather.
        # ``pos_offset`` (possibly traced, e.g. stage_id * chunk in the
        # pipeline's seq-chunked embed) says where the slice starts.
        wpe = nn.Embed(cfg.max_seq_len, cfg.d_model, name="wpe", param_dtype=pdtype)
        if isinstance(pos_offset, int) and pos_offset == 0:
            pos = wpe.embedding[:t][None, :, :]
        elif getattr(pos_offset, "ndim", 0) == 1:
            # Per-slot offsets (serving decode: each batch row at its own
            # position) — a (B, t) gather instead of one shared slice.
            rows = pos_offset[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
            pos = jnp.take(wpe.embedding, rows, axis=0)
        else:
            pos = jax.lax.dynamic_slice_in_dim(wpe.embedding, pos_offset, t, axis=0)[None]
        h = (tok + pos).astype(_dtype(cfg.compute_dtype))
        h = nn.Dropout(cfg.dropout, deterministic=not train)(h)
        return nn.with_logical_constraint(h, ("batch", "seq", "embed"))


class GPTStage(nn.Module):
    """``n_layers`` stacked blocks — a pipeline stage's layer chunk.

    nn.scan stacks every block param with a leading "layers" axis — the
    layout the TP rule table keys on and the PP (stages, layers/stage, ...)
    reshape relies on (mirrors `/root/reference/model/GPTModel.py:55-67`).
    """

    cfg: ModelConfig
    n_layers: int

    @nn.compact
    def __call__(
        self,
        h: jax.Array,
        *,
        train: bool = True,
        decode: bool = False,
        decode_index: jax.Array | None = None,
    ) -> jax.Array:
        cls = _ScanBlock
        mode = self.cfg.remat_mode
        if mode in ("block", "block_save_flash") and not decode:
            kwargs: dict = {"prevent_cse": False}
            if mode == "block_save_flash":
                # Block remat, but the flash kernel's full residual set
                # (q/k/v/out/lse — tagged with checkpoint_name in the
                # custom-vjp fwd rule) is saved instead of recomputed: the
                # backward scan re-runs the cheap LN/MLP ops but neither
                # the attention kernel nor the qkv projections. ~65 MB/layer
                # of extra HBM at the flagship shape buys back ~4.3 ms/step
                # of recompute at b32 (device-busy 83.1 -> 78.8 ms, PERF.md
                # round 4).
                kwargs["policy"] = jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse", "flash_q", "flash_k", "flash_v"
                )
            cls = nn.remat(cls, **kwargs)
        scanned = nn.scan(
            cls,
            # "lora" rides the scan like every block variable: per-layer
            # adapter factors stack with the leading "layers" axis
            # (training (L, in, r); the serving engine's per-slot gather
            # feeds (L, B, in, r) and each layer sees its (B, in, r) row
            # factors). A lora-free model simply has no such collection.
            variable_axes={"params": 0, "cache": 0, "aux_loss": 0, "lora": 0},
            split_rngs={"params": True, "dropout": True},
            length=self.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(self.cfg, train, decode, name="blocks")
        (h, _), _ = scanned((h, decode_index), None)
        return h


class _DenseParams(nn.Module):
    """Parameter container with nn.Dense's exact tree, names, and init
    (kernel: lecun_normal, bias: zeros) — so GPTHead can hand the raw
    kernel/bias to the fused head+CE op while staying checkpoint- and
    sharding-rule-compatible with the nn.Dense layout it replaced."""

    features: int
    param_dtype: Any

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (in_features, self.features), self.param_dtype,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype
        )
        return kernel, bias


class GPTHead(nn.Module):
    """Final LayerNorm + LM head (pipeline last-stage tail).

    With ``targets`` the head returns the mean next-token CE loss via
    :func:`dtc_tpu.ops.fused_ce.fused_head_ce` (whose backward folds the
    bias gradient into the dW matmul — one logits pass fewer than autodiff,
    PERF.md round 4); without, the padded-and-masked logits as before.
    Both paths share one logits computation (``head_logits``), so train and
    eval/generate numerics cannot drift apart.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, h: jax.Array, targets: jax.Array | None = None) -> jax.Array:
        from dtc_tpu.ops.fused_ce import fused_head_ce, head_logits

        cfg = self.cfg
        h = nn.LayerNorm(name="ln_f", dtype=jnp.float32, param_dtype=jnp.float32)(h)
        kernel, bias = _DenseParams(
            cfg.padded_vocab_size, _dtype(cfg.param_dtype), name="lm_head"
        )(cfg.d_model)
        hc = h.astype(_dtype(cfg.compute_dtype))
        if targets is not None:
            return fused_head_ce(hc, kernel, bias, targets, cfg.vocab_size)
        return head_logits(hc, kernel, bias, cfg.vocab_size)


class GPT(nn.Module):
    """Full decoder-only GPT. Param tree: {"embed": …, "stage": …, "head": …} —
    already the pipeline decomposition, so PP is a leaf reshape away."""

    cfg: ModelConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        *,
        train: bool = True,
        decode: bool = False,
        targets: jax.Array | None = None,
    ) -> jax.Array:
        """Forward pass. Returns logits, or — when ``targets`` is given —
        the mean next-token CE loss via the fused head+CE op (the train
        step's path; one logits pass cheaper in backward, PERF.md round 4).

        ``decode=True``: GPT owns the ONE decode position/write-frontier
        counter (``cache/index``) — updated here, outside the layer scan,
        and threaded down read-only so the scan body is pure per-layer
        compute (the per-layer stacked counters this replaced cost an
        update op per layer per token). CALLER CONTRACT: the cumulative
        decoded length across calls must stay <= ``cfg.max_seq_len``. The
        write index is a traced value, so it cannot be range-checked here;
        past the bound, ``dynamic_update_slice`` clamps the write start
        and logits go silently wrong. ``dtc_tpu.generate.generate``
        enforces this at its static API surface — callers applying the
        model directly must do the same (or discharge the
        ``cfg.debug_checks`` checkify guard below).
        """
        cfg = self.cfg
        idx = None
        pos_offset: int | jax.Array = 0
        if decode:
            # The index is () for generate's whole-batch decode, or (B,)
            # when the caller built a per-slot cache (the serving
            # runtime's continuous batching — dtc_tpu/serve/engine.py
            # init_slot_cache): every decode consumer below branches on
            # its STATIC rank, so both flavors share this one model.
            ci = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            if cfg.debug_checks:
                # The caller contract above, enforced dynamically: callers
                # bypassing generate() can discharge this via
                # checkify.checkify instead of debugging clamped writes.
                from jax.experimental import checkify

                checkify.check(
                    jnp.all(idx + x.shape[1] <= cfg.max_seq_len),
                    "decode cache overflow: write frontier {i} + {n} tokens "
                    "exceeds max_seq_len={m}; dynamic_update_slice would "
                    "clamp and corrupt the cache",
                    i=jnp.max(idx), n=jnp.int32(x.shape[1]),
                    m=jnp.int32(cfg.max_seq_len),
                )
            ci.value = idx + x.shape[1]
            pos_offset = idx
        h = GPTEmbed(cfg, name="embed")(
            x, train=train, decode=decode, pos_offset=pos_offset
        )
        h = GPTStage(cfg, cfg.n_layers, name="stage")(
            h, train=train, decode=decode, decode_index=idx
        )
        return GPTHead(cfg, name="head")(h, targets=targets)


def adapter_param_count(cfg: ModelConfig) -> int:
    """Exact LoRA adapter parameter count from config (0 when disabled).

    Counted SEPARATELY from :func:`param_count` on purpose: the base
    params are frozen and shared across every tenant, while each tenant
    pays only this subtree — the whole point of the multi-tenant design.
    Per targeted site: ``rank * (in + out)`` for the A/B pair, per layer.
    With ``moe_experts > 0`` the dense fc1/fc2 sites do not exist (the
    MoE expert tensors carry no adapters), so only attention targets
    count."""
    a = cfg.adapter
    if a.rank <= 0:
        return 0
    d, f, r = cfg.d_model, cfg.d_ff, a.rank
    dims = {
        "q_proj": (d, d), "k_proj": (d, d), "v_proj": (d, d),
        "out_proj": (d, d),
    }
    if cfg.moe_experts == 0:
        dims["fc1"] = (d, f)
        dims["fc2"] = (f, d)
    per_layer = sum(
        r * (i + o) for t, (i, o) in dims.items() if t in tuple(a.target_modules)
    )
    return cfg.n_layers * per_layer


def param_count(cfg: ModelConfig) -> int:
    """Exact BASE parameter count from config (no tracing needed).
    LoRA adapter params are deliberately excluded — they are per-tenant
    and counted by :func:`adapter_param_count`."""
    if cfg.layer_pattern:
        from dtc_tpu.models.pattern import pattern_param_count

        return pattern_param_count(cfg)
    d, v, L, f, s = cfg.d_model, cfg.padded_vocab_size, cfg.n_layers, cfg.d_ff, cfg.max_seq_len
    embed = v * d + s * d
    if cfg.moe_experts > 0:
        e = cfg.moe_experts
        ffn = d * e + e * (d * f + f + f * d + d)  # router + E experts
    else:
        ffn = (d * f + f) + (f * d + d)            # fc1 + fc2
    per_block = (
        4 * (d * d + d)        # q,k,v,out projections
        + ffn
        + 4 * d                # ln_1, ln_2 scale+bias
    )
    head = 2 * d + (d * v + v)  # ln_f + lm_head
    return embed + L * per_block + head
