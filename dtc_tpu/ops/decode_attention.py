"""Fused single-query decode attention — one Pallas launch per layer.

The decode step's attention used to be a pile of small XLA ops per layer
(score einsum over the full cache, iota mask build, fp32 softmax, value
einsum — each a separate kernel launch inside the token scan), which is
what made decode launch-bound at ~4 ms/token (PERF.md round 5: an
fp32-vs-bf16 weight A/B moved nothing, so the cost is dispatch, not
bandwidth). This kernel folds the whole per-layer attention read into ONE
launch over the model-native packed KV layout:

- **Layout**: the cache is ``(B, S, H·D)`` — exactly the byte layout the
  qkv projections produce and the packed training kernels consume
  (ops/flash_attention.py round 3). Heads group ``g`` per lane block
  (``128 // D`` when that divides the head count; otherwise one block of
  all ``H·D`` lanes — Mosaic pads internally, same as the transpose
  kernels keep head_dim native). The per-head slice happens INSIDE VMEM,
  a register shuffle, never an HBM pass.
- **Masking**: the query is ONE new token at position ``start``; cache
  columns ``col <= start`` are valid (the current token's k/v are written
  at ``start`` before attention — models/gpt.py). ``start`` rides in as
  an SMEM scalar so the mask is an in-register iota compare, and KV
  blocks entirely beyond the frontier are predicated out (their compute
  never runs; at S=512 the whole cache is one tile anyway).
- **Numerics**: fp32 scores/softmax regardless of input dtype, the same
  ``exp(s - max)`` one-pass softmax as the training kernels' single-tile
  path — the XLA oracle (ops/attention.py ``decode_attention``) remains
  the parity reference, asserted token-exact in tests/test_generate.py.

The kernel handles ONLY the single-token step (``T_new == 1``); prefill
(multi-token) goes through the oracle — it runs once per sequence, the
scan body runs per token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with the training kernels.
from dtc_tpu.ops.flash_attention import _interpret, _packed_group

NEG_INF = -1e9  # matches ops/attention.py
_LANES = 128

#: Longest cache held as ONE KV tile per (batch, group) program. The tile
#: is (S, lane_block) in the input dtype — 2 MB bf16 at S=4096/128 lanes,
#: comfortably VMEM — and a single tile needs no online-softmax scratch.
#: Past this the blocked kernel walks the cache in _DECODE_BLOCK_S chunks
#: and skips the compute for blocks beyond the write frontier (Pallas
#: still pipelines every block's copy — the skip saves VPU/MXU work,
#: not HBM reads).
_DECODE_MAX_SINGLE_S = 4096
_DECODE_BLOCK_S = 512


#: Smallest per-head amplitude treated as non-zero by the int8 quantizer:
#: an all-zero head (fresh cache rows, padding) would otherwise divide by
#: zero. round(0 / floor) == 0, so zero vectors round-trip exactly.
KV_SCALE_FLOOR = 1e-8


def quantize_kv(x: jax.Array, n_heads: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(position, head) int8 quantization of a packed
    ``(..., H·D)`` k/v tensor.

    Each head's D-vector gets its own fp32 scale ``max(|x|)/127`` (the
    "per-head-block" granularity: one scale per lane group the decode
    kernels already slice by), so a large-magnitude head cannot crush a
    small one's resolution — the standard KV-quantization failure mode
    KIVI/KVQuant address with finer groups. Returns ``(int8 payload of
    x's shape, fp32 scales (..., H))``. Round-trip error is bounded by
    ``scale/2 = max(|x|)/254`` per element (pinned in
    tests/test_decode_fused.py). The in-kernel quantizers
    (ops/decode_fused.py) replicate these exact fp32 ops so the compiled
    paths cannot drift from this reference."""
    *lead, hd = x.shape
    d = hd // n_heads
    xr = x.reshape(tuple(lead) + (n_heads, d)).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xr), axis=-1)
    scale = jnp.maximum(amax, KV_SCALE_FLOOR) / 127.0
    q = jnp.clip(jnp.round(xr / scale[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8).reshape(x.shape), scale


def dequantize_kv(
    q: jax.Array, scale: jax.Array, n_heads: int, dtype
) -> jax.Array:
    """Inverse of :func:`quantize_kv`: ``(..., H·D)`` int8 payload +
    ``(..., H)`` fp32 scales -> ``dtype`` values (the cache's compute
    view). The XLA-oracle decode path uses this whole-cache dequant as
    the parity reference; the kernels dequantize the same arithmetic
    in-register, per head slice, without materializing this tensor."""
    *lead, hd = q.shape
    d = hd // n_heads
    qr = q.reshape(tuple(lead) + (n_heads, d)).astype(jnp.float32)
    return (qr * scale[..., None]).reshape(q.shape).astype(dtype)


def _group(d: int, h: int) -> tuple[int, int]:
    """(heads per lane block, lane block width).

    128-lane groups per the training kernels' packed grouping rule
    (flash_attention._packed_group, shared so the two paths can't
    diverge); otherwise one block holding all H·D lanes — correct for
    any shape (the tiny CPU-test models), lane-padded by Mosaic."""
    g = _packed_group(d, h)
    return (g, _LANES) if g is not None else (h, h * d)


def supports(s: int) -> bool:
    """Whether the fused kernel handles a cache of length ``s``.

    The single-tile branch additionally clears the shared VMEM planner
    (ops/vmem.py — every ``supports_*`` gate consults it, lint-enforced
    by analysis/kernels.py). At the 14 MiB budget every cache under the
    structural ``_DECODE_MAX_SINGLE_S`` bound fits — pinned in
    tests/test_kernel_audit.py so this consult can never silently
    change routing."""
    from dtc_tpu.ops import vmem

    if s <= _DECODE_MAX_SINGLE_S and vmem.decode_single_tile_fits(s):
        return True
    return s % _DECODE_BLOCK_S == 0


def use_fused(cfg, t_new: int) -> bool:
    """The per-layer kernel's routing predicate (models/gpt.py): knob on
    ``fused`` — or ``fused_layers`` for a call the megakernel declined —
    a single-token call, and a supported cache length. Prefill
    (multi-token) and unsupported lengths take the einsum oracle."""
    return (
        cfg.decode_attention in ("fused", "fused_layers")
        and t_new == 1
        and supports(cfg.max_seq_len)
    )


def _head_kv(kt, vt, ks, vs, gg, d, out_dtype):
    """This lane block's head ``gg`` K/V tiles, dequantized to
    ``out_dtype`` when the cache is int8 (``ks``/``vs`` are the (s, g)
    per-head fp32 scale columns; None = float cache, native slices).
    The dequant is a register-resident multiply — the int8 payload is
    what crossed HBM."""
    sl = slice(gg * d, (gg + 1) * d)
    k_h, v_h = kt[:, sl], vt[:, sl]
    if ks is not None:
        k_h = (k_h.astype(jnp.float32) * ks[:, gg:gg + 1]).astype(out_dtype)
        v_h = (v_h.astype(jnp.float32) * vs[:, gg:gg + 1]).astype(out_dtype)
    elif k_h.dtype != out_dtype:
        # Down-dtyped float cache (kv_cache_dtype: bf16 under fp32
        # compute): promote to q's dtype for the dots, exactly as the
        # XLA oracle's einsum promotion does.
        k_h, v_h = k_h.astype(out_dtype), v_h.astype(out_dtype)
    return k_h, v_h


def _decode_kernel_single(start_ref, q_ref, k_ref, v_ref, *rest,
                          s, g, d, scale, per_row=False, quant=False):
    """Whole-cache-in-one-tile decode step for the g heads of this lane
    block: per head, a (1, S) score row, masked to the frontier, one-pass
    softmax, and a (1, D) output row. No scratch, no rescale passes.
    ``per_row``: the SMEM frontier is (B,) — one write position per batch
    row (the serving slots) — read at this program's batch index.
    ``quant``: the cache is int8 with per-(position, head) fp32 scales
    riding as two extra inputs; dequant happens per head slice in
    registers (the HBM read is the 1-byte payload)."""
    if quant:
        ks_ref, vs_ref, o_ref = rest
        ks, vs = ks_ref[0], vs_ref[0]              # (s, g) fp32
    else:
        (o_ref,) = rest
        ks = vs = None
    start = start_ref[pl.program_id(0)] if per_row else start_ref[0]
    qt = q_ref[0]                                  # (1, g*d)
    kt, vt = k_ref[0], v_ref[0]                    # (s, g*d)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    mask = col <= start
    for gg in range(g):
        sl = slice(gg * d, (gg + 1) * d)
        k_h, v_h = _head_kv(kt, vt, ks, vs, gg, d, qt.dtype)
        sc = jax.lax.dot_general(
            qt[:, sl] * scale, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (1, s) fp32
        sc = jnp.where(mask, sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jax.lax.dot_general(
            p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # (1, d)
        o_ref[0, :, sl] = (acc / l).astype(o_ref.dtype)


def _decode_kernel_blocked(start_ref, q_ref, k_ref, v_ref, *rest,
                           block_s, g, d, scale, per_row=False, quant=False):
    """Online-softmax decode step over KV blocks (caches past the
    single-tile bound). Blocks whose first column is beyond the write
    frontier are predicated out — a 32k-slot cache decoded at position
    600 COMPUTES two blocks, not 64, though the pipeline still copies in
    all 64 (compute skip, not a DMA skip). Scratch rows 0
    hold head gg's running stats in column gg (the packed-kernel
    convention); the output is written once at the last block.
    ``quant`` as in the single-tile kernel: int8 payload + per-head
    scale blocks, dequantized per head slice in registers."""
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
        ks, vs = ks_ref[0], vs_ref[0]              # (block_s, g) fp32
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks = vs = None
    j = pl.program_id(2)
    start = start_ref[pl.program_id(0)] if per_row else start_ref[0]

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_s <= start)
    def _():
        qt = q_ref[0]                              # (1, g*d)
        kt, vt = k_ref[0], v_ref[0]                # (block_s, g*d)
        col = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1
        )
        mask = col <= start
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            cl = slice(gg, gg + 1)
            k_h, v_h = _head_kv(kt, vt, ks, vs, gg, d, qt.dtype)
            sc = jax.lax.dot_general(
                qt[:, sl] * scale, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sc = jnp.where(mask, sc, NEG_INF)
            m_prev = m_scr[:1, cl]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_scr[:1, cl] = alpha * l_scr[:1, cl] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_scr[:1, sl] = acc_scr[:1, sl] * alpha + jax.lax.dot_general(
                p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[:1, cl] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            cl = slice(gg, gg + 1)
            o_ref[0, :, sl] = (acc_scr[:1, sl] / l_scr[:1, cl]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h", "d"))
def fused_decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, start: jax.Array,
    *, h: int, d: int,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-launch decode attention on the packed KV layout.

    ``q`` is ``(B, 1, H·D)`` — the one new token, model-native packed;
    ``k``/``v`` are the FULL cache ``(B, S, H·D)`` with valid columns
    ``<= start`` (the write frontier, the new token's position). ``start``
    is a scalar — one frontier for the whole batch, the ``generate`` path
    — or a ``(B,)`` vector of per-row frontiers (the serving runtime's
    continuous-batching slots; it rides in SMEM either way and each
    (batch, group) program reads its own row's scalar). With an int8
    cache (``kv_cache_dtype: int8``) ``k``/``v`` are the 1-byte payload
    and ``k_scale``/``v_scale`` the ``(B, S, H)`` fp32 per-(position,
    head) scales (:func:`quantize_kv`); dequant runs per head slice in
    registers, so the HBM traffic is the quantized bytes. Returns
    ``(B, 1, H·D)`` in q's dtype. Numerics match
    :func:`dtc_tpu.ops.attention.decode_attention` (fp32 softmax, -1e9
    mask, whole-cache dequant for int8) to fp roundoff; token-level
    decisions are exact in practice and asserted in
    tests/test_generate.py + tests/test_decode_fused.py.
    """
    b, t, hd = q.shape
    s = k.shape[1]
    if t != 1:
        raise ValueError(f"fused decode attention is single-query; got T={t}")
    if hd != h * d:
        raise ValueError(f"packed width {hd} != n_heads*head_dim {h}*{d}")
    if not supports(s):
        raise ValueError(
            f"cache length {s} unsupported (> {_DECODE_MAX_SINGLE_S} and not "
            f"a multiple of {_DECODE_BLOCK_S}); use the xla decode path"
        )
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    g, lb = _group(d, h)
    hg = hd // lb
    scale = float(d ** -0.5)
    start = jnp.asarray(start, jnp.int32)
    per_row = start.ndim == 1 and start.shape[0] == b and b > 1
    if not per_row:
        start = start.reshape((1,))

    qspec = pl.BlockSpec((1, 1, lb), lambda bi, gi, *_: (bi, 0, gi))
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    args = (start, q, k, v) + ((k_scale, v_scale) if quant else ())
    if s <= _DECODE_MAX_SINGLE_S:
        kvspec = pl.BlockSpec((1, s, lb), lambda bi, gi: (bi, 0, gi))
        # Scale blocks mirror the payload blocks one column per head: the
        # lane group [gi·g, gi·g+g) reads scale columns [gi·g, gi·g+g).
        scspec = pl.BlockSpec((1, s, g), lambda bi, gi: (bi, 0, gi))
        return pl.pallas_call(
            functools.partial(
                _decode_kernel_single, s=s, g=g, d=d, scale=scale,
                per_row=per_row, quant=quant,
            ),
            grid=(b, hg),
            in_specs=[
                sspec,
                pl.BlockSpec((1, 1, lb), lambda bi, gi: (bi, 0, gi)),
                kvspec,
                kvspec,
            ] + ([scspec, scspec] if quant else []),
            out_specs=pl.BlockSpec((1, 1, lb), lambda bi, gi: (bi, 0, gi)),
            out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=_interpret(),
        )(*args)

    nkv = s // _DECODE_BLOCK_S
    kvspec = pl.BlockSpec((1, _DECODE_BLOCK_S, lb), lambda bi, gi, j: (bi, j, gi))
    scspec = pl.BlockSpec((1, _DECODE_BLOCK_S, g), lambda bi, gi, j: (bi, j, gi))
    return pl.pallas_call(
        functools.partial(
            _decode_kernel_blocked, block_s=_DECODE_BLOCK_S, g=g, d=d,
            scale=scale, per_row=per_row, quant=quant,
        ),
        grid=(b, hg, nkv),
        in_specs=[sspec, qspec, kvspec, kvspec]
        + ([scspec, scspec] if quant else []),
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((8, _LANES), jnp.float32),  # running max (row 0)
            pltpu.VMEM((8, _LANES), jnp.float32),  # running sum (row 0)
            pltpu.VMEM((8, lb), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*args)
