"""Blockwise causal flash attention — Pallas TPU kernels, custom VJP.

Replaces the reference's O(T²)-memory einsum attention, which materialises
the full ``(B, H, T, T)`` score tensor in fp32
(`/root/reference/model/CausalSelfAttention.py:34-42`). Here scores only
ever exist one ``(block_q, block_kv)`` VMEM tile at a time:

- **Forward**: online softmax (running max ``m``, running sum ``l``) over KV
  blocks; the grid's innermost dimension walks KV blocks sequentially so the
  running statistics live in VMEM scratch across iterations. Emits the
  logsumexp alongside the output for the backward pass.
- **Backward**: flash-attention-2 style two-kernel split — one kernel
  accumulates dQ (grid walks KV innermost), one accumulates dK/dV (grid
  walks Q innermost) — each recomputing ``p = exp(s - lse)`` blockwise from
  the saved logsumexp instead of storing attention weights.
- Causal structure is exploited twice: blocks strictly above the diagonal
  are predicated out entirely (``@pl.when``), and diagonal-straddling blocks
  apply an iota position mask.
- On the packed layout, with the blocks left at their defaults, the tiles
  come from the shape (``ops/vmem.py:flash_plan``) and the kernels follow
  the causal triangle themselves: K and V of a lane group resident, large
  unmasked updates below the diagonal, the diagonal in row strips that
  stop at it. See "causal triangle followed inside the kernel" below.

HBM-layout notes (what made this fast on a v5e):

- head_dim stays NATIVE in HBM (the flagship's 32); tiles are laid out by
  Mosaic with internal lane padding in VMEM only. An earlier version
  zero-padded q/k/v to the 128-lane width in HBM — 4× the memory traffic of
  the whole attention layer, all zeros.
- lse / delta travel as compact ``(B, H, T)`` arrays (block ``(1, 1,
  block_q)``), not lane-broadcast ``(…, 128)`` buffers (128× traffic).
- Scores/statistics are fp32 on the MXU/VPU regardless of input dtype;
  q/k/v tiles stay in their input dtype (bf16 in the mixed-precision path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtc_tpu.ops import vmem

NEG_INF = -1e9  # matches the reference's additive mask value (ops/attention.py)
_LANES = 128  # TPU lane width (kept for stat-scratch shapes)

#: Longest sequence routed to the FUSED packed backward, which accumulates
#: dk/dv in full-T (T, 128) fp32 VMEM scratches — ~8 MB of scratch + output
#: blocks at T=4096 (measured working on a v5e); doubling T again exceeds a
#: core's VMEM. Past this, the packed SPLIT dq/dkv kernels (scratch
#: O(block), 7 tile matmuls vs the fused 5) take over — still packed
#: layout, any T.
_PACKED_MAX_T = 4096


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mask(i, j, block_q, block_kv):
    """Causal mask for the (block_q, block_kv) tile at grid position (i, j):
    True where kv position <= q position (global coordinates)."""
    t = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    s = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    return s <= t


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel_single(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_kv):
    """One-pass forward for nkv == 1 (whole KV in one tile — the flagship's
    T=512 case). Attention at small head_dim is VPU-bound, so this skips the
    online-softmax machinery entirely: no running stats, no rescale pass, no
    scratch broadcasts. q arrives pre-scaled (see flash_causal_attention)."""
    i = pl.program_id(2)
    q = q_ref[0, 0]                          # (block_q, d), pre-scaled
    k = k_ref[0, 0]                          # (block_kv, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = jnp.where(_mask(i, 0, block_q, block_kv), s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, block_q, block_kv):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: the KV block is relevant iff its first position <= the Q
    # block's last position. Blocks strictly above the diagonal are skipped.
    @pl.when(j * block_kv <= i * block_q + block_q - 1)
    def _():
        q = q_ref[0, 0]                     # (block_q, d)
        k = k_ref[0, 0]                     # (block_kv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                    # (block_q, block_kv) fp32; q pre-scaled
        s = jnp.where(_mask(i, j, block_q, block_kv), s, NEG_INF)

        m_prev = m_scr[:, :1]                # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)      # rescale factor for old stats
        p = jnp.exp(s - m_new)               # (block_q, block_kv)

        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # logsumexp per q row; every row has >= 1 unmasked key (its own
        # position) so l > 0 always. Compact (block_q, 1) store.
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l_scr[:, :1])


def _fwd_call(q, k, v, block_q, block_kv):
    b, h, t, d = q.shape
    # Grouped KV heads: q head hi reads KV head hi // grp, chosen by the
    # block index map; K and V are never expanded in HBM.
    grp = h // k.shape[1]
    nq, nkv = t // block_q, t // block_kv
    if nkv == 1:
        # Whole KV fits one tile: one-pass kernel, no online-softmax scratch.
        qspec3 = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0))
        kvspec3 = pl.BlockSpec((1, 1, block_kv, d), lambda bi, hi, i: (bi, hi // grp, 0, 0))
        return pl.pallas_call(
            functools.partial(_fwd_kernel_single, block_q=block_q, block_kv=block_kv),
            grid=(b, h, nq),
            in_specs=[qspec3, kvspec3, kvspec3],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i: (bi, hi, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
            ),
            interpret=_interpret(),
        )(q, k, v)
    grid = (b, h, nq, nkv)
    qspec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0))
    kvspec = pl.BlockSpec((1, 1, block_kv, d), lambda bi, hi, i, j: (bi, hi // grp, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_kv=block_kv),
        grid=grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i, j: (bi, hi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward — fused single-block kernel (nq == nkv == 1)
# ---------------------------------------------------------------------------


def _bwd_kernel_single(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, *, block_q, block_kv):
    """Fused backward for the single-tile case: one program holds the whole
    (T, T) score tile for its (batch, head), so p is recomputed ONCE and all
    three gradients come out of the same pass — the split dq/dkv kernels
    would recompute s/p twice and double the VPU work."""
    q, do = q_ref[0, 0], do_ref[0, 0]
    k, v = k_ref[0, 0], v_ref[0, 0]
    p, ds = _p_ds(q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
                  0, 0, block_q, block_kv)
    dq_ref[0, 0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[0, 0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)
    dv_ref[0, 0] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# backward — dq kernel (grid walks KV innermost, dq accumulates in scratch)
# ---------------------------------------------------------------------------


def _p_ds(q, k, v, do, lse, delta, i, j, block_q, block_kv):
    """Shared backward tile math: recomputed probabilities p and the score
    gradient ds = p * (dp - delta), both (block_q, block_kv) fp32.

    q arrives pre-scaled, so no scale factor appears anywhere: the VJP of the
    outer ``q * scale`` restores dq's factor automatically, and dk's factor
    rides in through the scaled q itself. ``lse``/``delta`` are (block_q, 1)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.exp(s - lse)
    p = jnp.where(_mask(i, j, block_q, block_kv), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, block_q, block_kv):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(j * block_kv <= i * block_q + block_q - 1)
    def _():
        _, ds = _p_ds(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
            lse_ref[0, 0], delta_ref[0, 0],
            i, j, block_q, block_kv,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward — dk/dv kernel (grid walks Q innermost, dk/dv accumulate)
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, block_q, block_kv, nq):
    # kv block j outer; inner: the q heads of this KV head's group, each
    # with its nq q blocks (one head: the inner index is the q block).
    j, inner = pl.program_id(2), pl.program_id(3)
    i = inner % nq

    @pl.when(inner == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(i * block_q + block_q - 1 >= j * block_kv)
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _p_ds(
            q, k_ref[0, 0], v_ref[0, 0], do,
            lse_ref[0, 0], delta_ref[0, 0],
            i, j, block_q, block_kv,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(inner == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, out, lse, do, block_q, block_kv):
    b, h, t, d = q.shape
    hk = k.shape[1]
    grp = h // hk  # q heads on one KV head (see _fwd_call)
    nq, nkv = t // block_q, t // block_kv
    # delta_i = rowsum(dO ⊙ O): tiny elementwise reduce, leave it to XLA.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[..., None]

    if nq == 1 and nkv == 1:
        spec = pl.BlockSpec((1, 1, t, d), lambda bi, hi: (bi, hi, 0, 0))
        kspec = pl.BlockSpec((1, 1, t, d), lambda bi, hi: (bi, hi // grp, 0, 0))
        sspec = pl.BlockSpec((1, 1, t, 1), lambda bi, hi: (bi, hi, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel_single, block_q=t, block_kv=t),
            grid=(b, h),
            in_specs=[spec, kspec, kspec, spec, sspec, sspec],
            out_specs=[spec, spec, spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
                jax.ShapeDtypeStruct((b, h, t, d), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=_interpret(),
        )(q, k, v, do, lse, delta)
        if grp > 1:
            # One tile a head: each q head wrote its own dk / dv; the
            # group's sum is a small XLA reduce.
            dk, dv = (x.reshape(b, hk, grp, t, d).astype(jnp.float32).sum(2).astype(x.dtype)
                      for x in (dk, dv))
        return dq, dk, dv

    qspec = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0))
    kvspec_q_outer = pl.BlockSpec((1, 1, block_kv, d), lambda bi, hi, i, j: (bi, hi // grp, j, 0))
    statspec = pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i, j: (bi, hi, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_kv=block_kv),
        grid=(b, h, nq, nkv),
        in_specs=[qspec, kvspec_q_outer, kvspec_q_outer, qspec, statspec, statspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # dk/dv grid: (b, KV heads, nkv, grp * nq) — the group's q heads and
    # their q blocks innermost, so one KV block's accumulators persist in
    # scratch over every q head it serves: the group's dk / dv are summed
    # in VMEM, not by a reduction over expanded heads.
    qspec_kv_outer = pl.BlockSpec(
        (1, 1, block_q, d), lambda bi, hi, j, i: (bi, hi * grp + i // nq, i % nq, 0))
    kvspec = pl.BlockSpec((1, 1, block_kv, d), lambda bi, hi, j, i: (bi, hi, j, 0))
    statspec_kv = pl.BlockSpec(
        (1, 1, block_q, 1), lambda bi, hi, j, i: (bi, hi * grp + i // nq, i % nq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=block_q, block_kv=block_kv, nq=nq),
        grid=(b, hk, nkv, grp * nq),
        in_specs=[qspec_kv_outer, kvspec, kvspec, qspec_kv_outer, statspec_kv, statspec_kv],
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, t, d), k.dtype),
            jax.ShapeDtypeStruct((b, hk, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP wrapper over (B, H, T, D) tensors
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, block_q, block_kv):
    out, _ = _fwd_call(q, k, v, block_q, block_kv)
    return out


def _flash_fwd(q, k, v, block_q, block_kv):
    out, lse = _fwd_call(q, k, v, block_q, block_kv)
    # Names make the kernel residuals policy-saveable under remat: with
    # jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    # (ModelConfig remat="block_save_flash"), the backward pass recomputes
    # the cheap qkv projections but never re-runs this forward kernel —
    # out/lse are restored from HBM (~17 MB/layer at the flagship shape vs
    # ~0.5 ms/layer of kernel recompute; measured in PERF.md round 4).
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    return out, (q, k, v, out, lse)


def _flash_bwd(block_q, block_kv, res, do):
    q, k, v, out, lse = res
    return _bwd_call(q, k, v, out, lse, do, block_q, block_kv)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Packed (transpose-free) kernels.
#
# The model's natural layout is (B, T, H*D) — the raw output of the qkv
# projections. The original kernels wanted (B, H, T, D), and XLA realised
# that relayout as ~10 HBM copy passes per step (q/k/v/o forward, the same
# again under remat recompute, and do/dq/dk/dv backward): measured ~15 ms of
# a 114 ms flagship b32 step. These variants index the packed layout
# directly — a lane GROUP of g = 128 // D heads per grid slot, so every
# block is 128-lane aligned — and slice heads INSIDE VMEM, where a 32-lane
# static slice is a register shuffle, not an HBM pass. The softmax scale is
# applied to the q tile in VMEM (free) instead of as a separate HBM pass,
# and the backward's delta = rowsum(dO ⊙ O) moves into the kernel (was a
# 2.7 ms layout-hostile XLA reduce fusion).
# ---------------------------------------------------------------------------


def _packed_group(d: int, h: int) -> int | None:
    """Heads per 128-lane group, or None if the packed path can't apply."""
    if d > _LANES or _LANES % d != 0:
        return None
    g = _LANES // d
    return g if h % g == 0 else None


def _causal_block_dispatch(i, j, block_q, block_kv, accumulate):
    """Run ``accumulate(masked)`` for the causally-relevant (i, j) tile.

    One definition of the two correctness-critical predicates shared by
    all four packed multi-tile kernels: a block participates iff its first
    kv position <= the q block's last position, and it needs the (full
    VPU pass) causal select iff it straddles the diagonal — fully-below
    blocks (last kv pos <= first q pos) run unmasked. Blocks strictly
    above the diagonal run neither branch."""
    straddles = j * block_kv + block_kv - 1 > i * block_q

    @pl.when((j * block_kv <= i * block_q + block_q - 1) & straddles)
    def _():
        accumulate(True)

    @pl.when(jnp.logical_not(straddles))
    def _():
        accumulate(False)


def _packed_scores(qt, kt, sl, scale, mask):
    """fp32 score tile for head slice ``sl`` of packed q/k tiles;
    ``mask=None`` skips the causal select (fully-below-diagonal blocks)."""
    s = jax.lax.dot_general(
        qt[:, sl] * scale, kt[:, sl], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _packed_tile_bwd(qt, kt, vt, dot_, ot, lse, mask, sl, scale, delta=None):
    """Shared per-head backward tile math for the packed kernels: recompute
    p from the saved lse, form ds = p*(dp - delta), and return the three
    fp32 gradient contributions (dq, dk, dv) for head slice ``sl``.
    ``delta`` (rowsum(dO ⊙ O), depends only on the q block) may be passed
    in precomputed; None computes it from the tiles."""
    qs = qt[:, sl] * scale
    k = kt[:, sl]
    do = dot_[:, sl]
    s = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.exp(s - lse)
    if mask is not None:  # None = unmasked block (zigzag ring cross-chunks)
        p = jnp.where(mask, p, 0.0)
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * ot[:, sl].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
    dp = jax.lax.dot_general(
        do, vt[:, sl], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    dq_c = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dk_c = jax.lax.dot_general(
        ds.astype(qs.dtype), qs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dv_c = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dq_c, dk_c, dv_c


# --- packed, causal triangle followed inside the kernel ---------------------
#
# Grid (rows, lane groups, q blocks). K and V of a lane group stay in VMEM
# across its q blocks (same block index -> no second DMA). Below the q
# block's first row the kernel loops over KV chunks unmasked, all of the
# block's rows at once; the block's own square on the diagonal it walks in
# row strips of ``diag`` rows, each against the columns it can see: what lies
# left of the strip's diagonal unit unmasked, the unit itself masked, what
# lies right of it never issued — a skipped unit costs nothing, where a
# skipped grid step still costs its ~0.35 us of pipeline bookkeeping. A strip
# is one softmax update however many units wide it is: the chip pays per
# update (a chain of matmul, reduce, exp, reduce, matmul it cannot overlap
# with the next), so the tiles are as large as the triangle allows and only
# the unit of skipping is small. Where the q block is the whole sequence
# every strip sees all its columns at once and nothing is carried.
#
# A head is selected by zeroing the other heads' lanes of the q-side operand
# once per q block, so every matmul contracts or emits whole 128-lane tiles
# (at head size 64 a half-filled MXU pass costs what a full one does) and
# nothing in the loop slices or shifts lanes; m and l of a head are held
# replicated across a lane tile, so their update is whole-register work.


def _head_lanes(g, d):
    """Per head of the lane group, the (1, 128) mask of its own lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return [(lane >= gg * d) & (lane < (gg + 1) * d) for gg in range(g)]


def _per_head(x, lanes):
    """``x`` with every other head's lanes zeroed, one copy per head."""
    if len(lanes) == 1:
        return [x]
    return [jnp.where(m, x, jnp.zeros_like(x)) for m in lanes]


def _merge_heads(xs, lanes):
    """Each head's own lanes of its (n, 128) array, side by side."""
    out = xs[0]
    for m, x in zip(lanes[1:], xs[1:]):
        out = jnp.where(m, x, out)
    return out


def _walk_triangle(i, block_q, chunk, diag, below, strip):
    """The schedule of q block i, shared by forward and backward:
    ``below(cols)`` once per KV chunk wholly under the block's first row,
    then per row strip r ``strip(rows, segs)`` with ``segs`` the column
    ranges it sees inside the block's own square as ``(cols, mask)``:
    the units left of the diagonal unmasked, the diagonal unit masked.
    ``i`` None: the block is the whole sequence, every index is static."""
    col0 = 0
    if i is not None:
        col0 = i * block_q

        def body(j, carry):
            below(pl.ds(pl.multiple_of(j * chunk, chunk), chunk))
            return carry

        jax.lax.fori_loop(0, i * (block_q // chunk), body, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (diag, diag), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (diag, diag), 1)
    mask = col <= row

    def cols(start, size):
        if i is None:
            return pl.ds(start, size)
        return pl.ds(pl.multiple_of(col0 + start, diag), size)

    for r in range(block_q // diag):
        segs = [(cols(0, r * diag), None)] if r else []
        strip(slice(r * diag, (r + 1) * diag), segs + [(cols(r * diag, diag), mask)])


def _widen(x, width):
    """A lane-dense (n, 128) statistic repeated across ``width`` lanes."""
    rep = width // _LANES
    return pltpu.repeat(x, rep, 1) if rep > 1 else x


def _softmax_update(ss, vs, prev):
    """One online-softmax update of one head: its fp32 score segments
    ``ss`` (masked already) against their value tiles ``vs``, from ``prev``
    = (m, l, acc) carried, or None. Returns (m, l, acc), m and l lane-dense
    (n, 128), acc fp32 as wide as a value tile."""
    m_cur = functools.reduce(
        jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in ss]
    )
    if prev is None:
        m_new = jnp.broadcast_to(m_cur, (m_cur.shape[0], _LANES))
    else:
        m_prev, l_prev, acc_prev = prev
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
    l_new = acc = None
    for s, vt in zip(ss, vs):
        p = jnp.exp(s - _widen(m_new, s.shape[1]))
        l_seg = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_new = l_seg if l_new is None else l_new + l_seg
        acc = pv if acc is None else acc + pv
    if prev is None:
        l_new = jnp.broadcast_to(l_new, m_new.shape)
    else:
        l_new = alpha * l_prev + l_new
        acc = _widen(alpha, acc.shape[1]) * acc_prev + acc
    return m_new, l_new, acc


def _fwd_kernel_tri(q_ref, k_ref, v_ref, o_ref, lse_ref, *stats,
                    block_q, chunk, diag, g, d, scale):
    whole = not stats  # the q block is the whole sequence: nothing carried
    lanes = _head_lanes(g, d)
    qh = _per_head(q_ref[0] * scale, lanes)     # scaled once per q block

    def update(rows, segs, prev):
        """One online-softmax update of ``rows`` against the column
        segments, per head: (m, l, acc), m and l lane-dense (n, 128)."""
        kv = [(k_ref[0, cols, :], v_ref[0, cols, :], mask) for cols, mask in segs]
        new = []
        for gg in range(g):
            ss = []
            for kt, _, mask in kv:
                s = jax.lax.dot_general(
                    qh[gg][rows], kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ss.append(s if mask is None else jnp.where(mask, s, NEG_INF))
            # p . [v_0 | v_1 | ...]: this head's lanes of the product are
            # its own p . v, the others are dropped at the end.
            new.append(_softmax_update(
                ss, [vt for _, vt, _ in kv], None if prev is None else prev[gg]
            ))
        return new

    if not whole:
        m_scr, l_scr, acc_scr = stats
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def carried(rows):
        return [(m_scr[gg, rows], l_scr[gg, rows], acc_scr[gg, rows]) for gg in range(g)]

    def below(cols):
        rows = slice(None)
        for gg, (m, l, acc) in enumerate(update(rows, [(cols, None)], carried(rows))):
            m_scr[gg], l_scr[gg], acc_scr[gg] = m, l, acc

    def strip(rows, segs):
        done = update(rows, segs, None if whole else carried(rows))
        out = _merge_heads([acc / l for _, l, acc in done], lanes)
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        for gg, (m, l, _) in enumerate(done):
            lse_ref[0, 0, rows, gg : gg + 1] = (m + jnp.log(l))[:, :1]

    i = None if whole else pl.program_id(2)
    _walk_triangle(i, block_q, chunk, diag, below, strip)


def _bwd_kernel_tri(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *dq_scr,
                    block_q, chunk, diag, g, d, scale):
    """The fused five-matmul backward on the same schedule: p recomputed
    once per segment and head from the saved lse; dq of a q block
    accumulates over its chunks, dk/dv rows over the q blocks that see
    them, written whole after the last."""
    whole = not dq_scr
    i = None if whole else pl.program_id(2)

    def first():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if whole:
        first()
    else:
        pl.when(i == 0)(first)

    lanes = _head_lanes(g, d)
    dot_ = do_ref[0]
    qh = _per_head(q_ref[0] * scale, lanes)
    doh = _per_head(dot_, lanes)
    dod = dot_.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    delta = [jnp.sum(x, axis=-1, keepdims=True) for x in _per_head(dod, lanes)]
    lse = [lse_ref[0, 0, :, gg : gg + 1] for gg in range(g)]

    def grads(rows, segs):
        """dq of ``rows`` from the column segments (heads merged); their
        dk and dv go into the accumulators."""
        dq = [None] * g
        for cols, mask in segs:
            kt, vt = k_ref[0, cols, :], v_ref[0, cols, :]
            dk_c = dv_c = None
            for gg in range(g):
                q_r, do_r = qh[gg][rows], doh[gg][rows]
                s = jax.lax.dot_general(
                    q_r, kt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                p = jnp.exp(s - lse[gg][rows])
                if mask is not None:
                    p = jnp.where(mask, p, 0.0)
                dp = jax.lax.dot_general(
                    do_r, vt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = (p * (dp - delta[gg][rows])).astype(kt.dtype)
                # ds . [k_0 | k_1 | ...]: only this head's lanes are its dq.
                dq_h = jax.lax.dot_general(
                    ds, kt, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                # q and do carry zeros in the other heads' lanes, so these
                # land in this head's lanes alone and the heads simply add.
                dk_h = jax.lax.dot_general(
                    ds, q_r, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dv_h = jax.lax.dot_general(
                    p.astype(dot_.dtype), do_r, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                dq[gg] = dq_h if dq[gg] is None else dq[gg] + dq_h
                dk_c = dk_h if dk_c is None else dk_c + dk_h
                dv_c = dv_h if dv_c is None else dv_c + dv_h
            dk_scr[cols, :] += dk_c
            dv_scr[cols, :] += dv_c
        return _merge_heads(dq, lanes)

    if not whole:
        dq_scr[0][:] = jnp.zeros_like(dq_scr[0])

    def below(cols):
        dq_scr[0][:] += grads(slice(None), [(cols, None)])

    def strip(rows, segs):
        dq = grads(rows, segs)
        if not whole:
            dq = dq + dq_scr[0][rows, :]
        dq_ref[0, rows, :] = (dq * scale).astype(dq_ref.dtype)

    _walk_triangle(i, block_q, chunk, diag, below, strip)

    def last():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if whole:
        last()
    else:
        pl.when(i == pl.num_programs(2) - 1)(last)


def _tri_call(kernel, args, outs, scratch, tri, g, d, scale):
    """Launch a triangle kernel: ``args`` / ``outs`` are (array or
    ShapeDtypeStruct, kind) with kind "q" (a q block), "kv" (resident,
    whole T) or "lse"; ``scratch`` is what the pass needs however many q
    blocks there are, the carried state is added where there are several."""
    block_q, chunk, diag, limit = tri
    b, t, hd = args[0][0].shape
    specs = {
        "q": pl.BlockSpec((1, block_q, _LANES), lambda bi, gi, i: (bi, i, gi)),
        "kv": pl.BlockSpec((1, t, _LANES), lambda bi, gi, i: (bi, 0, gi)),
        "lse": pl.BlockSpec((1, 1, block_q, g), lambda bi, gi, i: (bi, gi, i, 0)),
    }
    return pl.pallas_call(
        functools.partial(
            kernel, block_q=block_q, chunk=chunk, diag=diag, g=g, d=d, scale=scale
        ),
        grid=(b, hd // _LANES, t // block_q),
        in_specs=[specs[kind] for _, kind in args],
        out_specs=[specs[kind] for _, kind in outs],
        out_shape=[x for x, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=limit,
        ),
        interpret=_interpret(),
    )(*(x for x, _ in args))


def _tri_fwd_call(q, k, v, tri, g, d, scale):
    b, t, hd = q.shape
    block_q = tri[0]
    stat = pltpu.VMEM((g, block_q, _LANES), jnp.float32)  # lane-dense, per head
    return _tri_call(
        _fwd_kernel_tri,
        [(q, "q"), (k, "kv"), (v, "kv")],
        [(jax.ShapeDtypeStruct((b, t, hd), q.dtype), "q"),
         (jax.ShapeDtypeStruct((b, hd // _LANES, t, g), jnp.float32), "lse")],
        [] if block_q == t else [stat, stat, stat],      # m, l, p . V
        tri, g, d, scale,
    )


def _tri_bwd_call(q, k, v, do, out, lse, tri, g, d, scale):
    b, t, hd = q.shape
    block_q = tri[0]
    acc = pltpu.VMEM((t, _LANES), jnp.float32)           # dk, dv accumulators
    dq_acc = pltpu.VMEM((block_q, _LANES), jnp.float32)
    sds = lambda x: jax.ShapeDtypeStruct((b, t, hd), x.dtype)  # noqa: E731
    return _tri_call(
        _bwd_kernel_tri,
        [(q, "q"), (k, "kv"), (v, "kv"), (do, "q"), (out, "q"), (lse, "lse")],
        [(sds(q), "q"), (sds(k), "kv"), (sds(v), "kv")],
        [acc, acc] if block_q == t else [acc, acc, dq_acc],
        tri, g, d, scale,
    )


# --- packed, grid-walking: one grid step a tile, for a user's tiling --------


def _fwd_kernel_packed_multi(q_ref, k_ref, v_ref, o_ref, lse_ref,
                             m_scr, l_scr, acc_scr, *,
                             block_q, block_kv, g, d, scale):
    """Online-softmax forward on packed layout, KV blocks walked innermost.
    Blocks strictly above the causal diagonal are predicated out entirely.
    Scratch columns gg hold head gg's running stats; acc uses the same
    lane slot as the head's output slice. Runs a user's tiling; blocks
    left at their defaults take the triangle kernels above."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        qt, kt, vt = q_ref[0], k_ref[0], v_ref[0]
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            cl = slice(gg, gg + 1)
            s = _packed_scores(qt, kt, sl, scale, mask)
            m_prev = m_scr[:, cl]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[:, cl] = alpha * l_scr[:, cl] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:, sl] = acc_scr[:, sl] * alpha + jax.lax.dot_general(
                p.astype(vt.dtype), vt[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[:, cl] = m_new

    # The causal select is a full VPU pass over the fp32 score tile; at
    # T/block = 8 the dispatch skips it on 28 of 36 valid blocks.
    _causal_block_dispatch(i, j, block_q, block_kv, _accumulate)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            cl = slice(gg, gg + 1)
            o_ref[0, :, sl] = (acc_scr[:, sl] / l_scr[:, cl]).astype(o_ref.dtype)
            lse_ref[0, 0, :, cl] = m_scr[:, cl] + jnp.log(l_scr[:, cl])


def _bwd_kernel_packed_multi(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                             dq_ref, dk_ref, dv_ref,
                             dq_scr, dk_scr, dv_scr, delta_scr, *,
                             block_q, block_kv, g, d, scale):
    """Fused backward on packed layout with causal block skipping.

    Grid (b, hg, i, j), row-major: dq for q-block i accumulates in a small
    (block_q, 128) scratch reset at j==0 and written at j==last; dk/dv
    accumulate rows pl.ds(j*block_kv) of full-length (T, 128) scratches —
    their j-blocks only complete at the final i — and are written whole at
    the last grid step. p is recomputed ONCE per valid block and feeds all
    three gradients (the split dq/dkv kernels of the transpose path
    recompute it twice)."""
    i, j = pl.program_id(2), pl.program_id(3)
    nq, nkv = pl.num_programs(2), pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta = rowsum(dO ⊙ O) depends only on the q block: compute it
        # once per i here (j == 0 is always causally valid) instead of
        # per KV block — saves (nkv - 1) redundant VPU reduces per head.
        dot_, ot = do_ref[0], o_ref[0]
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            delta_scr[:, gg : gg + 1] = jnp.sum(
                dot_[:, sl].astype(jnp.float32) * ot[:, sl].astype(jnp.float32),
                axis=-1, keepdims=True,
            )

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        qt, kt, vt = q_ref[0], k_ref[0], v_ref[0]
        dot_, ot = do_ref[0], o_ref[0]
        rows = pl.ds(j * block_kv, block_kv)
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            lse = lse_ref[0, 0, :, gg : gg + 1]
            dq_c, dk_c, dv_c = _packed_tile_bwd(
                qt, kt, vt, dot_, ot, lse, mask, sl, scale,
                delta=delta_scr[:, gg : gg + 1],
            )
            dq_scr[:, sl] += dq_c
            dk_scr[rows, sl] += dk_c
            dv_scr[rows, sl] += dv_c

    _causal_block_dispatch(i, j, block_q, block_kv, _accumulate)

    @pl.when(j == nkv - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when((i == nq - 1) & (j == nkv - 1))
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# --- packed split backward: O(block) scratch, any T -----------------------
#
# The fused multi-tile backward above holds full-length (T, 128) dk/dv
# accumulators in VMEM — past _PACKED_MAX_T those outgrow a core's VMEM.
# These two kernels are the FA2-style split on the packed layout: the dq
# kernel accumulates (block_q, 128) while walking KV blocks, the dk/dv
# kernel accumulates (block_kv, 128) while walking Q blocks. Each
# recomputes p from the saved lse (7 tile matmuls total vs the fused
# kernel's 5), so the fused path stays the default wherever it fits and
# these take over beyond it. delta = rowsum(dO ⊙ O) is precomputed by XLA
# in the lse layout (b, hg, T, g) — one cheap elementwise+reduce pass —
# instead of per-tile, which would redo it nkv (dq) / nq (dkv) times.


def _split_tile_p_ds(refs, lse_ref, delta_ref, mask, sl, gg, scale):
    """Shared split-kernel recompute for head slice ``sl``: returns
    (p, ds, qs) — probabilities from the saved lse, the score gradient
    ds = p * (dp - delta), and the pre-scaled q tile. One definition so
    the dq and dk/dv halves of the gradient cannot drift apart."""
    q_ref, k_ref, v_ref, do_ref = refs
    qs = q_ref[0][:, sl] * scale
    s = jax.lax.dot_general(
        qs, k_ref[0][:, sl], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.exp(s - lse_ref[0, 0, :, gg : gg + 1])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do_ref[0][:, sl], v_ref[0][:, sl], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0, :, gg : gg + 1])
    return p, ds, qs


def _dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, block_q, block_kv, g, d, scale):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            _, ds, _ = _split_tile_p_ds(
                (q_ref, k_ref, v_ref, do_ref), lse_ref, delta_ref,
                mask, sl, gg, scale,
            )
            kk = k_ref[0][:, sl]
            dq_scr[:, sl] += jax.lax.dot_general(
                ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

    _causal_block_dispatch(i, j, block_q, block_kv, _accumulate)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr,
                       *, block_q, block_kv, g, d, scale):
    j, i = pl.program_id(2), pl.program_id(3)  # kv block outer, q inner

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        for gg in range(g):
            sl = slice(gg * d, (gg + 1) * d)
            p, ds, qs = _split_tile_p_ds(
                (q_ref, k_ref, v_ref, do_ref), lse_ref, delta_ref,
                mask, sl, gg, scale,
            )
            dk_scr[:, sl] += jax.lax.dot_general(
                ds.astype(qs.dtype), qs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dot_ = do_ref[0]
            dv_scr[:, sl] += jax.lax.dot_general(
                p.astype(dot_.dtype), dot_[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _causal_block_dispatch(i, j, block_q, block_kv, _accumulate)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _packed_split_bwd_call(q, k, v, do, out, lse, block_q, block_kv, g, d, scale):
    b, t, hd = q.shape
    hg = hd // _LANES
    nq, nkv = t // block_q, t // block_kv
    # delta in the lse layout (b, hg, t, g): rowsum over each head's d slice.
    delta = (
        (do.astype(jnp.float32) * out.astype(jnp.float32))
        .reshape(b, t, hg, g, d)
        .sum(-1)
        .transpose(0, 2, 1, 3)
    )

    qspec = pl.BlockSpec((1, block_q, _LANES), lambda bi, gi, i, j: (bi, i, gi))
    kvspec = pl.BlockSpec((1, block_kv, _LANES), lambda bi, gi, i, j: (bi, j, gi))
    statspec = pl.BlockSpec((1, 1, block_q, g), lambda bi, gi, i, j: (bi, gi, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel_packed,
            block_q=block_q, block_kv=block_kv, g=g, d=d, scale=scale,
        ),
        grid=(b, hg, nq, nkv),
        in_specs=[qspec, kvspec, kvspec, qspec, statspec, statspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    qspec_kv = pl.BlockSpec((1, block_q, _LANES), lambda bi, gi, j, i: (bi, i, gi))
    kvspec_kv = pl.BlockSpec((1, block_kv, _LANES), lambda bi, gi, j, i: (bi, j, gi))
    statspec_kv = pl.BlockSpec((1, 1, block_q, g), lambda bi, gi, j, i: (bi, gi, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel_packed,
            block_q=block_q, block_kv=block_kv, g=g, d=d, scale=scale,
        ),
        grid=(b, hg, nkv, nq),
        in_specs=[qspec_kv, kvspec_kv, kvspec_kv, qspec_kv, statspec_kv, statspec_kv],
        out_specs=[kvspec_kv, kvspec_kv],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), k.dtype),
            jax.ShapeDtypeStruct((b, t, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, _LANES), jnp.float32),
            pltpu.VMEM((block_kv, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_packed(q, k, v, block_q, block_kv, g, d, scale,
                  block_q_bwd, block_kv_bwd, tri_fwd=None, tri_bwd=None):
    """``tri_fwd`` / ``tri_bwd``: (q block, KV chunk, diagonal unit,
    vmem_limit_bytes) of the causal-triangle schedule for that pass, or
    None to walk the grid with the ``block_*`` tiles."""
    out, _ = _packed_fwd_call(q, k, v, block_q, block_kv, g, d, scale, tri_fwd)
    return out


def _packed_fwd_call(q, k, v, block_q, block_kv, g, d, scale, tri=None):
    if tri is not None:
        return _tri_fwd_call(q, k, v, tri, g, d, scale)
    b, t, hd = q.shape
    hg = hd // _LANES
    nq = t // block_q
    nkv = t // block_kv
    qspec = pl.BlockSpec((1, block_q, _LANES), lambda bi, gi, i, j: (bi, i, gi))
    kvspec = pl.BlockSpec((1, block_kv, _LANES), lambda bi, gi, i, j: (bi, j, gi))
    lsespec = pl.BlockSpec((1, 1, block_q, g), lambda bi, gi, i, j: (bi, gi, i, 0))
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel_packed_multi,
            block_q=block_q, block_kv=block_kv, g=g, d=d, scale=scale,
        ),
        grid=(b, hg, nq, nkv),
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec, lsespec],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype),
            jax.ShapeDtypeStruct((b, hg, t, g), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # acc (g head slices)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v)


def _packed_flash_fwd(q, k, v, block_q, block_kv, g, d, scale,
                      block_q_bwd, block_kv_bwd, tri_fwd=None, tri_bwd=None):
    out, lse = _packed_fwd_call(q, k, v, block_q, block_kv, g, d, scale, tri_fwd)
    # Policy-saveable residuals — see _flash_fwd for the rationale.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    return out, (q, k, v, out, lse)


def _packed_flash_bwd(block_q, block_kv, g, d, scale,
                      block_q_bwd, block_kv_bwd, tri_fwd, tri_bwd, res, do):
    q, k, v, out, lse = res
    if tri_bwd is not None:
        return _tri_bwd_call(q, k, v, do, out, lse, tri_bwd, g, d, scale)
    b, t, hd = q.shape
    hg = hd // _LANES
    # The backward's best tiling differs from the forward's (the fused
    # kernel holds dk/dv scratches the forward doesn't; measured on v5e,
    # PERF.md round 5): nonzero overrides retile it independently. The
    # saved lse is blocked afresh by these specs, so any valid tiling of
    # the same arrays works.
    if block_q_bwd:
        block_q = block_q_bwd
    if block_kv_bwd:
        block_kv = block_kv_bwd
    nq = t // block_q
    # A user tiling override that resolves to one whole-T tile at
    # T > _PACKED_MAX_T must not reach the fused kernel — its full-T VMEM
    # scratches would die as an opaque Mosaic compile OOM instead of this
    # error. flash_causal_attention validates the same condition at the
    # API surface; this is the defense for direct _flash_packed callers.
    if t > _PACKED_MAX_T:
        if block_kv == t and nq == 1:
            raise ValueError(
                f"packed flash backward cannot run whole-T tiles past "
                f"T={_PACKED_MAX_T} (full-T VMEM scratches): T={t} with "
                f"block_q={block_q}, block_kv={block_kv}; choose bwd "
                f"blocks < T"
            )
        # Fused kernel's full-T dk/dv VMEM scratches don't fit: split
        # dq / dkv kernels with O(block) scratch take over.
        return _packed_split_bwd_call(
            q, k, v, do, out, lse, block_q, block_kv, g, d, scale
        )
    nkv = t // block_kv
    qspec = pl.BlockSpec((1, block_q, _LANES), lambda bi, gi, i, j: (bi, i, gi))
    kvspec = pl.BlockSpec((1, block_kv, _LANES), lambda bi, gi, i, j: (bi, j, gi))
    lsespec = pl.BlockSpec((1, 1, block_q, g), lambda bi, gi, i, j: (bi, gi, i, 0))
    fullspec = pl.BlockSpec((1, t, _LANES), lambda bi, gi, i, j: (bi, 0, gi))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel_packed_multi,
            block_q=block_q, block_kv=block_kv, g=g, d=d, scale=scale,
        ),
        grid=(b, hg, nq, nkv),
        in_specs=[qspec, kvspec, kvspec, qspec, qspec, lsespec],
        out_specs=[qspec, fullspec, fullspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype),
            jax.ShapeDtypeStruct((b, t, hd), k.dtype),
            jax.ShapeDtypeStruct((b, t, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # dq accumulator
            pltpu.VMEM((t, _LANES), jnp.float32),        # dk accumulator
            pltpu.VMEM((t, _LANES), jnp.float32),        # dv accumulator
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # delta (per q block)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, out, lse)
    return dq, dk, dv


_flash_packed.defvjp(_packed_flash_fwd, _packed_flash_bwd)


def supports(t: int, d: int, block_q: int, block_kv: int) -> bool:
    """Whether the kernel handles this shape (used by the auto dispatcher)."""
    bq, bkv = min(block_q, t), min(block_kv, t)
    return (
        t % bq == 0 and t % bkv == 0
        and bq % 8 == 0 and bkv % _LANES == 0
        and d <= 512  # per-tile head_dim must fit VMEM comfortably
        and (_chosen(block_q, block_kv, 0, 0) or vmem.flash_grid_tile_fits(bq, bkv))
    )


def _chosen(block_q, block_kv, block_q_bwd, block_kv_bwd) -> bool:
    """The blocks were left at the config's defaults: the kernel chooses."""
    return (block_q, block_kv, block_q_bwd, block_kv_bwd) == (
        vmem.FLASH_DEFAULT_BLOCK, vmem.FLASH_DEFAULT_BLOCK, 0, 0
    )


def _triangle(t, d, h, itemsize):
    """(tri_fwd, tri_bwd) for :func:`_flash_packed` from the planner: each
    (q block, KV chunk, diagonal unit, vmem_limit_bytes), or None where K
    and V of a lane group do not fit VMEM whole beside that pass's
    accumulators (the backward never past ``_PACKED_MAX_T``: the split
    kernels take over)."""
    plan = vmem.flash_plan(t, d, h, itemsize)

    def one(name):
        leg = plan[name]
        if not leg["fits"]:
            return None
        return leg["block_q"], leg["kv_chunk"], leg["unit"][0], leg["vmem_limit_bytes"]

    if plan is None:
        return None, None
    return one("fwd"), one("bwd") if t <= _PACKED_MAX_T else None


def schedule(
    t: int, h: int, d: int, itemsize: int = 2,
    block_q: int = vmem.FLASH_DEFAULT_BLOCK, block_kv: int = vmem.FLASH_DEFAULT_BLOCK,
    block_q_bwd: int = 0, block_kv_bwd: int = 0,
) -> dict | None:
    """How :func:`flash_causal_attention` will walk the score square at
    this shape, per pass: ``{"fwd": {...}, "bwd": {...}}``, each with
    ``schedule`` ("triangle": the loop inside the kernel; "grid": one grid
    step a tile), ``block_q``, ``kv_chunk``, the ``unit`` of skipping and
    the units run / masked / skipped and the share of the square covered,
    per (row, lane group).
    Static, from the shape alone (no tracing): the trainer's start-up
    event and the tests read the same function the dispatch does. None
    off the packed layout (the transpose family tiles as configured)."""
    if _packed_group(d, h) is None:
        return None
    tri = (None, None)
    if _chosen(block_q, block_kv, block_q_bwd, block_kv_bwd):
        tri = _triangle(t, d, h, itemsize)
    grid = {
        "fwd": (min(block_q, t), min(block_kv, t)),
        "bwd": (min(block_q_bwd or block_q, t), min(block_kv_bwd or block_kv, t)),
    }
    out = {}
    for name, leg in zip(("fwd", "bwd"), tri):
        tiles = grid[name] if leg is None else leg[:3]
        out[name] = {
            "schedule": "grid" if leg is None else "triangle",
            **vmem.flash_schedule(t, *tiles),
        }
    return out


def flash_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, block_q: int = 512, block_kv: int = 512,
    block_q_bwd: int = 0, block_kv_bwd: int = 0,
) -> jax.Array:
    """Causal flash attention over ``(B, T, H, D)`` tensors (op-layer layout).

    Exact (up to fp32 accumulation order) match of
    ``dense_causal_attention``; O(T) memory instead of O(T²).
    ``block_*_bwd`` retile the packed backward independently of the
    forward (0 = same as forward) — at long context the forward wants
    wide KV blocks while the backward's scratches cap its tile budget.
    """
    b, t, h, d = q.shape
    chosen = _chosen(block_q, block_kv, block_q_bwd, block_kv_bwd)
    block_q, block_kv = min(block_q, t), min(block_kv, t)
    block_q_bwd, block_kv_bwd = min(block_q_bwd, t), min(block_kv_bwd, t)
    if not supports(t, d, block_q, block_kv):
        raise ValueError(
            f"flash attention unsupported for T={t}, D={d}, "
            f"block_q={block_q}, block_kv={block_kv}"
        )
    if (block_q_bwd or block_kv_bwd) and not supports(
        t, d, block_q_bwd or block_q, block_kv_bwd or block_kv
    ):
        raise ValueError(
            f"flash attention backward tiling unsupported for T={t}, "
            f"block_q_bwd={block_q_bwd}, block_kv_bwd={block_kv_bwd}"
        )
    # Past _PACKED_MAX_T no grid-walking kernel can hold a whole-T tile
    # (the forward materializes (T, T) scores; fused AND split backwards
    # hold (T, 128) accumulators) — reject single-tile tilings HERE with the
    # cause named instead of letting pallas_call die in a Mosaic compile
    # OOM (round-5 ADVICE guard-order fix; the bwd-side check in
    # _packed_flash_bwd covers direct kernel callers).
    if t > _PACKED_MAX_T:
        for tag, bq_eff, bkv_eff in (
            ("", block_q, block_kv),
            ("_bwd", block_q_bwd or block_q, block_kv_bwd or block_kv),
        ):
            if bkv_eff == t and bq_eff == t:
                raise ValueError(
                    f"flash attention cannot run whole-T tiles past "
                    f"T={_PACKED_MAX_T}: T={t} with block_q{tag}={bq_eff}, "
                    f"block_kv{tag}={bkv_eff}; use blocks < T (e.g. the "
                    f"512/1024 defaults)"
                )

    # Fewer KV heads than q heads (k, v are (B, T, H_kv, D), H a multiple
    # of H_kv): the transposed-layout kernels pick each q head's KV head in
    # their block index maps; the packed kernels know no groups.
    if h % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"q heads {h} are not a multiple of KV heads {k.shape[2]}")
    g = _packed_group(d, h) if k.shape[2] == h else None
    if (block_q_bwd or block_kv_bwd) and g is None:
        # The transpose-layout fallback has no independent backward tiling;
        # silently running the forward tiling there would make sweep-tuned
        # A/B numbers lie.
        raise ValueError(
            "attention_block_{q,kv}_bwd require the packed flash path "
            f"(128 % head_dim == 0 and heads % group == 0); got D={d}, H={h}"
        )
    if g is not None:
        # Packed transpose-free path: heads group into 128-lane blocks ->
        # operate on the model-native (B, T, H*D) layout directly. reshape
        # is a bitcast; no HBM relayout anywhere. Blocks left at their
        # defaults: the planner picks the tiles from the shape and the
        # kernels follow the causal triangle themselves; a user's tiling
        # walks the grid, one step a tile. Beyond _PACKED_MAX_T the fused
        # backward's full-T dk/dv scratches outgrow VMEM and the split
        # dq/dkv kernels (all scratch O(block)) take over — packed at
        # every T.
        scale = float(d ** -0.5)
        tri = _triangle(t, d, h, q.dtype.itemsize) if chosen else (None, None)
        out = _flash_packed(
            q.reshape(b, t, h * d), k.reshape(b, t, h * d),
            v.reshape(b, t, h * d), block_q, block_kv, g, d, scale,
            block_q_bwd, block_kv_bwd, *tri,
        )
        return out.reshape(b, t, h, d)

    # Fold the softmax scale into q once here — saves a full (bq, bkv)
    # multiply pass per tile in every kernel, and its VJP restores dq's
    # scale factor automatically.
    q = q * q.dtype.type(d ** -0.5)

    # (B, T, H, D) -> (B, H, T, D). head_dim stays native: Mosaic pads the
    # VMEM tiles internally, HBM traffic stays at the true size.
    tk = lambda x: x.transpose(0, 2, 1, 3)
    out = _flash(tk(q), tk(k), tk(v), block_q, block_kv)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Latent attention (multi-head latent attention, models/pattern.LatentAttention)
#
# q and k of a head are [nope | rope]: q_h = [qn_h | qp_h], k_h = [kn_h | kp]
# with ONE rope key kp shared by every head, and v of another width:
#
#     s_h = (qn_h kn_h^T + qp_h kp^T) / sqrt(dn + dr),   o_h = softmax_causal(s_h) v_h
#
# The operands stay as the projections wrote them, (B, T, H * width), and kp
# as (B, T, dr): no head's key is assembled in HBM, kp is never copied to the
# heads and nothing is padded to a lane tile there. A grid step takes
# ``g = 128 // dr`` heads (their rope parts are one lane tile of qp; dn and dv
# are whole lane tiles, so a head's nope and value parts are aligned slices
# of its block); kp's block is read once per KV block for all of them.
#
# The forward walks the causal triangle inside the kernel, as the packed
# family's does (``_walk_triangle``): grid (rows, head groups, q blocks); a q
# block's own square of K / V arrives beside q as blocks and is run first in
# row strips, nothing above the diagonal issued; the KV chunks below the
# block are fetched from HBM by the kernel, two in flight, each one unmasked
# update of all the block's rows. m and l are lane-dense, q scaled once a q
# block. The backward walks one (block_q, block_kv) tile a grid step, as the
# transposed family does, split FA2-style into a dq pass (rows, head groups,
# q blocks, KV blocks) and a dk / dv pass whose innermost axis walks every
# head group's q blocks for one KV block, so kp's gradient, the sum over all
# H heads, accumulates in VMEM and is written once. Tiles above the diagonal
# are predicated out, and their block indices are clamped to the last (first)
# tile that runs, so a skipped step fetches nothing. dr 64 needs 64-lane
# slices of the qp block, which Mosaic takes (the packed grid kernels slice
# heads of 64 the same way).
# ---------------------------------------------------------------------------


def mla_group(h: int, dn: int, dr: int, dv: int) -> int | None:
    """Heads a grid step of the latent kernels takes, or None where the
    widths do not tile: dn and dv whole lane tiles, dr dividing one, the
    heads a multiple of the group."""
    if dn % _LANES or dv % _LANES or dr <= 0 or _LANES % dr:
        return None
    g = _LANES // dr
    return g if h % g == 0 else None


def mla_supports(t: int, h: int, dn: int, dr: int, dv: int, itemsize: int = 2) -> dict | None:
    """The planner's plan (``ops/vmem.mla_flash_plan``) where the latent
    kernels take the shape, else None."""
    if mla_group(h, dn, dr, dv) is None:
        return None
    plan = vmem.mla_flash_plan(t, h, dn, dr, dv, itemsize)
    return plan if plan is not None and plan["fits"] else None


def _mla_scores(qn, qp, kn, kp, mask):
    """fp32 scores of one head from its scaled q parts and the key tile."""
    dims = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(qn, kn, dims, preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qp, kp, dims, preferred_element_type=jnp.float32))
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _mla_heads(g, dn, dr, dv):
    """Per head of a step: its (nope, rope, value) lane slices."""
    return [(slice(h * dn, (h + 1) * dn), slice(h * dr, (h + 1) * dr), slice(h * dv, (h + 1) * dv))
            for h in range(g)]


def _mla_fwd_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, kn_hbm, v_hbm, o_ref, lse_ref,
                    *carried, block_q, chunk, unit, g, dn, dr, dv, scale):
    """The forward of q block i: its own square of K / V (the blocks beside
    q's) in row strips first, then the chunks of nope keys and values below
    it, fetched from HBM two in flight, each one unmasked update of all the
    block's rows. The rope key is held whole for the row: Mosaic takes a
    64-lane window of it neither as a DMA nor as a ref view, only as a
    load."""
    heads = _mla_heads(g, dn, dr, dv)
    qn, qp = qn_ref[0] * scale, qp_ref[0] * scale        # scaled once per q block
    q = [(qn[:, n], qp[:, r]) for n, r, _ in heads]

    def update(rows, segs, prev):
        """One online-softmax update of ``rows`` against the segments
        ``(kn, kp, v, mask)`` (``kn`` / ``v`` give a head's lanes of the
        segment, ``kp`` is its rope key), per head: (m, l, acc)."""
        new = []
        for gg, (n, _, c) in enumerate(heads):
            qn_h, qp_h = q[gg][0][rows], q[gg][1][rows]
            ss = [_mla_scores(qn_h, qp_h, kn(n), kp, mask) for kn, kp, _, mask in segs]
            new.append(_softmax_update(ss, [v(c) for _, _, v, _ in segs],
                                       None if prev is None else prev[gg]))
        return new

    def own(base, segs):
        """The segments of a strip in the block's own square."""
        return [(lambda n, cols=cols: kn_ref[0, cols, n],
                 kp_ref[0, pl.ds(base + cols.start, cols.size), :],
                 lambda c, cols=cols: v_ref[0, cols, c], mask) for cols, mask in segs]

    def emit(rows, done):
        for (_, _, c), (m, l, acc) in zip(heads, done):
            o_ref[0, rows, c] = (acc / _widen(l, dv)).astype(o_ref.dtype)
        for gg, (m, l, _) in enumerate(done):
            lse_ref[0, 0, rows, gg:gg + 1] = (m + jnp.log(l))[:, :1]

    if not carried:  # the q block is the whole sequence
        _walk_triangle(None, block_q, chunk, unit, None,
                       lambda rows, segs: emit(rows, update(rows, own(0, segs), None)))
        return

    m_scr, l_scr, acc_scr, kn_buf, v_buf, sem = carried
    bi, gi, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_below = i * (block_q // chunk)

    def copies(j, slot):
        cols = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        return [pltpu.make_async_copy(src.at[bi, cols, pl.ds(gi * w, w)], buf.at[slot], sem.at[x, slot])
                for x, (src, buf, w) in enumerate(((kn_hbm, kn_buf, g * dn), (v_hbm, v_buf, g * dv)))]

    @pl.when(n_below > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    def strip(rows, segs):
        done = update(rows, own(pl.multiple_of(i * block_q, block_q), segs), None)
        for gg, (m, l, acc) in enumerate(done):
            m_scr[gg, rows], l_scr[gg, rows], acc_scr[gg, rows] = m, l, acc

    _walk_triangle(None, block_q, chunk, unit, None, strip)

    def below(j, carry):
        slot = j % 2
        for cp in copies(j, slot):
            cp.wait()

        @pl.when(j + 1 < n_below)
        def _():
            for cp in copies(j + 1, 1 - slot):
                cp.start()

        seg = (lambda n: kn_buf[slot, :, n],
               kp_ref[0, pl.ds(pl.multiple_of(j * chunk, chunk), chunk), :],
               lambda c: v_buf[slot, :, c], None)
        prev = [(m_scr[gg], l_scr[gg], acc_scr[gg]) for gg in range(g)]
        for gg, (m, l, acc) in enumerate(update(slice(None), [seg], prev)):
            m_scr[gg], l_scr[gg], acc_scr[gg] = m, l, acc
        return carry

    jax.lax.fori_loop(0, n_below, below, 0)
    emit(slice(None), [(m_scr[gg], l_scr[gg], acc_scr[gg]) for gg in range(g)])


def _mla_p_ds(refs, lse, delta, mask, gg, heads, scale):
    """The backward's recomputed probabilities and score gradient of head
    ``gg`` (fp32), with its scaled q parts."""
    qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref = refs
    n, r, c = heads[gg]
    qn, qp = qn_ref[0, :, n] * scale, qp_ref[0, :, r] * scale
    p = jnp.exp(_mla_scores(qn, qp, kn_ref[0, :, n], kp_ref[0], None) - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do_ref[0, :, c], v_ref[0, :, c], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta), qn, qp


def _mla_dq_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dqn_ref, dqp_ref, dqn_scr, dqp_scr, *, block_q, block_kv, g, dn, dr, dv, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    heads = _mla_heads(g, dn, dr, dv)
    refs = (qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref)

    @pl.when(j == 0)
    def _():
        dqn_scr[:] = jnp.zeros_like(dqn_scr)
        dqp_scr[:] = jnp.zeros_like(dqp_scr)

    def accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        for gg, (n, r, _) in enumerate(heads):
            _, ds, _, _ = _mla_p_ds(refs, lse_ref[0, 0, :, gg:gg + 1], delta_ref[0, 0, :, gg:gg + 1],
                                    mask, gg, heads, scale)
            ds = ds.astype(kn_ref.dtype)
            dqn_scr[:, n] += jax.lax.dot_general(ds, kn_ref[0, :, n], (((1,), (0,)), ((), ())),
                                                 preferred_element_type=jnp.float32)
            dqp_scr[:, r] += jax.lax.dot_general(ds, kp_ref[0], (((1,), (0,)), ((), ())),
                                                 preferred_element_type=jnp.float32)

    _causal_block_dispatch(i, j, block_q, block_kv, accumulate)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dqn_ref[0] = (dqn_scr[:] * scale).astype(dqn_ref.dtype)
        dqp_ref[0] = (dqp_scr[:] * scale).astype(dqp_ref.dtype)


def _mla_dkv_kernel(qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dkn_ref, dkp_ref, dv_ref, dkn_scr, dkp_scr, dv_scr,
                    *, block_q, block_kv, g, dn, dr, dv, scale, nq):
    # KV block j outer; inner: every head group's q blocks, group by group.
    j, inner = pl.program_id(1), pl.program_id(2)
    i = inner % nq
    heads = _mla_heads(g, dn, dr, dv)
    refs = (qn_ref, qp_ref, kn_ref, kp_ref, v_ref, do_ref)

    @pl.when(inner == 0)
    def _():
        dkp_scr[:] = jnp.zeros_like(dkp_scr)

    @pl.when(i == 0)
    def _():
        dkn_scr[:] = jnp.zeros_like(dkn_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def accumulate(masked: bool):
        mask = _mask(i, j, block_q, block_kv) if masked else None
        dkp = None
        for gg, (n, _, c) in enumerate(heads):
            p, ds, qn, qp = _mla_p_ds(refs, lse_ref[0, 0, :, gg:gg + 1],
                                      delta_ref[0, 0, :, gg:gg + 1], mask, gg, heads, scale)
            ds = ds.astype(qn.dtype)
            dims = (((0,), (0,)), ((), ()))
            dv_scr[:, c] += jax.lax.dot_general(p.astype(do_ref.dtype), do_ref[0, :, c], dims,
                                                preferred_element_type=jnp.float32)
            dkn_scr[:, n] += jax.lax.dot_general(ds, qn, dims, preferred_element_type=jnp.float32)
            dkp_h = jax.lax.dot_general(ds, qp, dims, preferred_element_type=jnp.float32)
            dkp = dkp_h if dkp is None else dkp + dkp_h
        dkp_scr[:] += dkp

    _causal_block_dispatch(i, j, block_q, block_kv, accumulate)

    @pl.when(i == nq - 1)
    def _():
        dkn_ref[0] = dkn_scr[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(inner == pl.num_programs(2) - 1)
    def _():
        dkp_ref[0] = dkp_scr[:].astype(dkp_ref.dtype)


def _mla_specs(t, g, dn, dr, dv, block_q, block_kv, kv_outer: bool):
    """BlockSpecs of (qn, qp, kn, kp, v, do / o, lse / delta) for the grid
    (rows, head groups, q blocks, KV blocks), or with ``kv_outer`` for
    (rows, KV blocks, head groups x q blocks). A tile above the diagonal
    reads the blocks of the nearest one that runs."""
    nq = t // block_q
    if kv_outer:
        def q_of(bi, j, u):           # the q block, not before the KV block's first
            return bi, jnp.maximum(u % nq, (j * block_kv) // block_q), u // nq

        def kv_of(bi, j, u):
            return bi, j, u // nq

        def stat(bi, j, u):
            b_, i_, h_ = q_of(bi, j, u)
            return b_, h_, i_, 0

        def kp(bi, j, u):
            return bi, j, 0
    else:
        def q_of(bi, hi, i, j):
            return bi, i, hi

        def kv_of(bi, hi, i, j):      # the KV block, not past the q block's last row
            return bi, jnp.minimum(j, (i * block_q + block_q - 1) // block_kv), hi

        def stat(bi, hi, i, j):
            return bi, hi, i, 0

        def kp(bi, hi, i, j):
            return kv_of(bi, hi, i, j)[:2] + (0,)
    return {
        "qn": pl.BlockSpec((1, block_q, g * dn), q_of),
        "qp": pl.BlockSpec((1, block_q, g * dr), q_of),
        "kn": pl.BlockSpec((1, block_kv, g * dn), kv_of),
        "kp": pl.BlockSpec((1, block_kv, dr), kp),
        "v": pl.BlockSpec((1, block_kv, g * dv), kv_of),
        "o": pl.BlockSpec((1, block_q, g * dv), q_of),
        "stat": pl.BlockSpec((1, 1, block_q, g), stat),
    }


def _mla_args(plan, g, dn, dr, dv, scale, **extra):
    return dict(block_q=plan["block_q"], block_kv=plan["block_kv"], g=g, dn=dn, dr=dr, dv=dv,
                scale=scale, **extra)


def _mla_fwd_call(qn, qp, kn, kp, v, plan, g, dn, dr, dv, scale):
    b, t, _ = qn.shape
    h = qn.shape[-1] // dn
    leg = plan["fwd"]
    bq, ck = leg["block_q"], leg["kv_chunk"]

    def q_of(bi, gi, i):
        return bi, i, gi

    carried = [] if bq == t else [
        pltpu.VMEM((g, bq, _LANES), jnp.float32),   # running max, a head's lane-dense
        pltpu.VMEM((g, bq, _LANES), jnp.float32),   # running sum
        pltpu.VMEM((g, bq, dv), jnp.float32),       # p . v
        pltpu.VMEM((2, ck, g * dn), kn.dtype),      # the chunks below, two in flight
        pltpu.VMEM((2, ck, g * dv), v.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_mla_fwd_kernel, block_q=bq, chunk=ck, unit=leg["unit"][0],
                          g=g, dn=dn, dr=dr, dv=dv, scale=scale),
        name="flash_mla_fwd",
        grid=(b, h // g, t // bq),
        in_specs=[pl.BlockSpec((1, bq, g * dn), q_of), pl.BlockSpec((1, bq, g * dr), q_of),
                  # the q block's own square of nope keys, the rope key whole, values
                  pl.BlockSpec((1, bq, g * dn), q_of),
                  pl.BlockSpec((1, t, dr), lambda bi, gi, i: (bi, 0, 0)),
                  pl.BlockSpec((1, bq, g * dv), q_of), hbm, hbm],
        out_specs=[pl.BlockSpec((1, bq, g * dv), q_of),
                   pl.BlockSpec((1, 1, bq, g), lambda bi, gi, i: (bi, gi, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h // g, t, g), jnp.float32)],
        scratch_shapes=carried,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=leg["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(qn, qp, kn, kp, v, kn, v)


def _mla_bwd_call(qn, qp, kn, kp, v, out, lse, do, plan, g, dn, dr, dv, scale):
    b, t, _ = qn.shape
    h = qn.shape[-1] // dn
    bq, bkv = plan["block_q"], plan["block_kv"]
    nq, nkv = t // bq, t // bkv
    # delta = rowsum(dO * O) per head, in the statistics' (B, H/g, T, g) layout
    delta = ((do.astype(jnp.float32) * out.astype(jnp.float32))
             .reshape(b, t, h // g, g, dv).sum(-1).transpose(0, 2, 1, 3))
    s = _mla_specs(t, g, dn, dr, dv, bq, bkv, kv_outer=False)
    dqn, dqp = pl.pallas_call(
        functools.partial(_mla_dq_kernel, **_mla_args(plan, g, dn, dr, dv, scale)),
        name="flash_mla_dq",
        grid=(b, h // g, nq, nkv),
        in_specs=[s["qn"], s["qp"], s["kn"], s["kp"], s["v"], s["o"], s["stat"], s["stat"]],
        out_specs=[s["qn"], s["qp"]],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype), jax.ShapeDtypeStruct(qp.shape, qp.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, g * dn), jnp.float32), pltpu.VMEM((bq, g * dr), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan["dq"]["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(qn, qp, kn, kp, v, do, lse, delta)
    s = _mla_specs(t, g, dn, dr, dv, bq, bkv, kv_outer=True)
    dkn, dkp, dvv = pl.pallas_call(
        functools.partial(_mla_dkv_kernel, **_mla_args(plan, g, dn, dr, dv, scale, nq=nq)),
        name="flash_mla_dkv",
        grid=(b, nkv, (h // g) * nq),
        in_specs=[s["qn"], s["qp"], s["kn"], s["kp"], s["v"], s["o"], s["stat"], s["stat"]],
        out_specs=[s["kn"], s["kp"], s["v"]],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype), jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, g * dn), jnp.float32), pltpu.VMEM((bkv, dr), jnp.float32),
                        pltpu.VMEM((bkv, g * dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # kp's gradient sums over every head group: that axis is walked, not split
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan["dkv"]["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(qn, qp, kn, kp, v, do, lse, delta)
    return dqn, dqp, dkn, dkp, dvv


def _mla_static(qn, qp, kp, v, block_q, block_kv, fwd_tiles):
    """(plan, g, dn, dr, dv, scale) of a call: the planner's tiles, or the
    caller's (tests at small shapes)."""
    b, t, _ = qn.shape
    dr = kp.shape[-1]
    h = qp.shape[-1] // dr
    dn, dv = qn.shape[-1] // h, v.shape[-1] // h
    g = mla_group(h, dn, dr, dv)
    plan = vmem.mla_flash_plan(t, h, dn, dr, dv, qn.dtype.itemsize, block_q, block_kv, fwd_tiles)
    if g is None or plan is None or not plan["fits"]:
        raise ValueError(f"latent flash attention unsupported for T={t}, H={h}, nope {dn}, "
                         f"rope {dr}, v {dv}, blocks {block_q}/{block_kv}, forward {fwd_tiles}")
    return plan, g, dn, dr, dv, float((dn + dr) ** -0.5)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def mla_flash_attention(qn, qp, kn, kp, v, block_q=0, block_kv=0, fwd_tiles=()):
    """Causal latent attention, packed: ``qn`` / ``kn`` (B, T, H dn), ``qp``
    (B, T, H dr) and the one rope key ``kp`` (B, T, dr), both rotated
    already, ``v`` (B, T, H dv); returns (B, T, H dv). Scores at dn + dr,
    scaled by its inverse square root. ``block_*``: the backward's tiles,
    ``fwd_tiles`` the forward's (q block, KV chunk, unit); 0 and (): the
    planner's."""
    plan, *static = _mla_static(qn, qp, kp, v, block_q, block_kv, fwd_tiles)
    return _mla_fwd_call(qn, qp, kn, kp, v, plan, *static)[0]


def _mla_vjp_fwd(qn, qp, kn, kp, v, block_q, block_kv, fwd_tiles):
    plan, *static = _mla_static(qn, qp, kp, v, block_q, block_kv, fwd_tiles)
    out, lse = _mla_fwd_call(qn, qp, kn, kp, v, plan, *static)
    # the names the flash family's residuals go by (remat block_save_flash)
    out, lse = checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")
    qn, qp = checkpoint_name(qn, "flash_q"), checkpoint_name(qp, "flash_q")
    kn, kp = checkpoint_name(kn, "flash_k"), checkpoint_name(kp, "flash_k")
    v = checkpoint_name(v, "flash_v")
    return out, (qn, qp, kn, kp, v, out, lse)


def _mla_vjp_bwd(block_q, block_kv, fwd_tiles, res, do):
    qn, qp, kn, kp, v, out, lse = res
    plan, *static = _mla_static(qn, qp, kp, v, block_q, block_kv, fwd_tiles)
    return _mla_bwd_call(qn, qp, kn, kp, v, out, lse, do, plan, *static)


mla_flash_attention.defvjp(_mla_vjp_fwd, _mla_vjp_bwd)


# ---------------------------------------------------------------------------
# Ring-block kernels: single-tile attention BLOCKS for the zigzag ring
# (ops/ring_attention.py). Same packed (B, Tc, H*D) layout and per-group
# head slicing as the kernels above, but (a) the causal mask is optional —
# zigzag cross-chunk blocks are strictly past and need none — and (b) the
# softmax statistics cross the kernel boundary explicitly: forward RETURNS
# lse so the ring can merge blocks online in jnp; backward TAKES the
# globally-merged lse (and global out for delta), the standard ring-flash
# backward contract.
# ---------------------------------------------------------------------------


def _block_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, tc, g, d, scale, causal):
    qt, kt, vt = q_ref[0], k_ref[0], v_ref[0]
    mask = _mask(0, 0, tc, tc) if causal else None
    for gg in range(g):
        sl = slice(gg * d, (gg + 1) * d)
        s = jax.lax.dot_general(
            qt[:, sl] * scale, kt[:, sl], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jax.lax.dot_general(
            p.astype(vt.dtype), vt[:, sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, :, sl] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, gg : gg + 1] = m + jnp.log(l)


def _block_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, tc, g, d, scale, causal):
    mask = _mask(0, 0, tc, tc) if causal else None
    qt, kt, vt = q_ref[0], k_ref[0], v_ref[0]
    dot_, ot = do_ref[0], o_ref[0]
    for gg in range(g):
        sl = slice(gg * d, (gg + 1) * d)
        lse = lse_ref[0, 0, :, gg : gg + 1]
        dq_c, dk_c, dv_c = _packed_tile_bwd(
            qt, kt, vt, dot_, ot, lse, mask, sl, scale
        )
        dq_ref[0, :, sl] = dq_c.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk_c.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv_c.astype(dv_ref.dtype)


def _block_specs(tc, g):
    dspec = pl.BlockSpec((1, tc, _LANES), lambda bi, gi: (bi, 0, gi))
    lsespec = pl.BlockSpec((1, 1, tc, g), lambda bi, gi: (bi, gi, 0, 0))
    return dspec, lsespec


def block_supported(tc: int, h: int, d: int) -> bool:
    """Can the packed ring-block kernels handle a (B, tc, h*d) chunk?"""
    return (
        _packed_group(d, h) is not None and tc % 8 == 0 and tc <= _PACKED_MAX_T
    )


def _block_call(q, k, v, scale, causal, g, d, do=None, o=None, lse=None):
    """pallas_call wrapper for the ring-block kernels. Forward when
    ``do is None`` -> (out, lse); backward otherwise -> (dq, dk, dv) fp32."""
    b, tc, hd = q.shape
    hg = hd // _LANES
    dspec, lsespec = _block_specs(tc, g)
    if do is None:
        return pl.pallas_call(
            functools.partial(
                _block_fwd_kernel, tc=tc, g=g, d=d, scale=scale, causal=causal
            ),
            grid=(b, hg),
            in_specs=[dspec, dspec, dspec],
            out_specs=[dspec, lsespec],
            out_shape=[
                jax.ShapeDtypeStruct((b, tc, hd), q.dtype),
                jax.ShapeDtypeStruct((b, hg, tc, g), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=_interpret(),
        )(q, k, v)
    return pl.pallas_call(
        functools.partial(
            _block_bwd_kernel, tc=tc, g=g, d=d, scale=scale, causal=causal
        ),
        grid=(b, hg),
        in_specs=[dspec, dspec, dspec, dspec, dspec, lsespec],
        out_specs=[dspec, dspec, dspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, tc, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, tc, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, tc, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=_interpret(),
    )(q, k, v, do, o, lse)
