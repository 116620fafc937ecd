"""Overlapped training collectives — fused all-gather-matmul and streamed
grad reduce-scatter over the FSDP ring (ROADMAP item 2, ISSUE 12).

The problem: XLA serializes FSDP's parameter all-gathers against the
matmuls that consume them and the gradient reduce-scatters against the
matmuls that produce them — PR 8's device-time observatory measures a
comm/compute ``overlap_ratio`` of **0.0** on the b8 reference. This module
implements the decomposition-and-overlap technique of Wang et al.
("Overlap Communication with Dependent Computation via Decomposition",
ASPLOS '23) as explicit ring schedules:

- **all-gather-then-matmul** (forward + the backward re-gather): each ICI
  ring step matmuls the parameter shard the device already holds while
  the next shard streams in — the gather hides entirely under the layer's
  MXU time.
- **streamed reduce-scatter-of-grads** (backward): grad blocks pipeline
  through the ring while the matmuls producing the later blocks are still
  running, partial-sum accumulation riding the permute.

Two interchangeable transports, one schedule:

- ``pallas`` — genuinely fused kernels: ``pltpu.make_async_remote_copy``
  RDMAs the next shard chip-to-chip while ``jnp.dot`` runs on the current
  one (the SNIPPETS [1]/[2] mechanism; same discipline as jax's
  pedagogical ring all-gather: per-chunk receive slots so no buffer is
  ever reused, chained DMA waits, a neighbor barrier on hardware).
  CPU-interpret mode runs the SAME kernels for the parity tests.
- ``decomposed`` — the ring unrolled as ``lax.ppermute`` + per-block
  ``jnp.dot`` at the XLA level. TPU's async collective-permute lets the
  scheduler overlap each permute with the previous block's matmul (the
  paper's "decomposition" without hand-written DMA); this is also the
  backend for shapes the Pallas kernels decline (blocks too small to
  lane-align on hardware, VMEM overflow) and — interpret-mode-only
  limitation — for multi-axis manual meshes off-TPU.

Both run inside a ``shard_map`` manual over the FSDP axis
(``parallel.sharding.fsdp_axis_in_scope`` finds it from the active
logical-axis rules) AND, on DP×FSDP×TP meshes, the Megatron axis — with
the two row-parallel psums explicit in the custom VJP. Full-manual over
every non-trivial axis is load-bearing twice: this jax's SPMD partitioner
rejects collectives in PARTIAL-manual regions (the PP / fsdp+ring
known-env-failure class), and a fully-local region is what lets the
Pallas kernels run under TP at all (a ``pallas_call`` cannot partition
over auto axes).

Numerics: partials accumulate in fp32 (``preferred_element_type``) and
cast to the input dtype once, so bf16 rings match the single-dot XLA
oracle to fp roundoff (asserted in tests/test_overlap_collectives.py).

Auto-fallback ladder (``overlap_dense_matmul``): eager trace / no mesh /
unmapped FSDP axis / ring of 1 / non-divisible shard or batch tails ->
the plain single dot (GSPMD's serialized path); pallas -> decomposed for
blocks too small to lane-align on hardware or VMEM overflow. The ladder
is what lets ``collectives: overlapped`` stay safe on any config — it
only changes programs it can provably take over.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from dtc_tpu.ops import vmem
from dtc_tpu.ops.flash_attention import _interpret  # noqa: F401  (shared gate)
from jax import shard_map

#: VMEM budget for the fused kernels — the ONE shared constant in
#: ops/vmem.py (ISSUE 20 unified this module's copy with
#: decode_fused's): operands + per-chunk receive slots + the f32
#: accumulator must fit, else the decomposed ring runs.
_VMEM_BUDGET_BYTES = vmem.VMEM_BUDGET_BYTES

#: Lane-dim dynamic slices inside the kernels start at ``block * step``;
#: Mosaic wants them 128-aligned on hardware (interpret mode does not
#: care — how the tiny-mesh CPU tests drive the real kernels).
_LANE = vmem.LANE

#: DMA-schedule recording seam (ISSUE 20). When
#: ``analysis/kernels.capture_schedule`` installs a list here, the ring
#: kernels append one dict per schedule event — DMA start/wait, shared-
#: buffer load/store — at kernel TRACE time. Events carry only STATIC
#: metadata (ring step ``s``, buffer name, symbolic slot): under
#: shard_map the kernel body traces once with ``lax.axis_index`` a
#: tracer, so concrete slots are written as ("rel", off) =
#: ``(device_idx + off) % ring`` or ("abs", k), and the auditor
#: instantiates them per device to reconstruct the CONCURRENT schedule
#: interpret-mode execution serializes. Zero overhead when None (every
#: hook is a no-op attribute check).
_SCHED_LOG = None


def _sched(kind: str, **fields) -> None:
    if _SCHED_LOG is not None:
        _SCHED_LOG.append(dict(kind=kind, **fields))


def _backend_override() -> str:
    """DTC_OVERLAP env: '' = auto, 'pallas' | 'decomposed' force a
    transport (pallas off-TPU runs interpret mode — the test hook),
    '0'/'xla' disable the ring entirely (plain serialized dot)."""
    return os.environ.get("DTC_OVERLAP", "")


def _pallas_ok(
    m: int, k_loc: int, n_loc: int, ring: int, shard_axis: int,
    itemsize: int,
) -> bool:
    """Can the fused kernels take this matmul — INCLUDING its backward?
    (Shapes are the LOCAL shard_map-region shapes; ``m`` = flattened
    token rows per device.) One backend decision covers three kernel
    launches (fwd all-gather-matmul, the bwd dx re-gather, the bwd dw
    matmul+reduce-scatter), so the VMEM budget must clear the WORST of
    their working sets — gating on the forward alone would select pallas
    for a shape whose backward then dies in Mosaic instead of taking the
    documented decomposed fallback. The byte accounting is
    :func:`dtc_tpu.ops.vmem.overlap_plan` (the shared planner the
    kernel auditor baselines)."""
    plan = vmem.overlap_plan(m, k_loc, n_loc, ring, shard_axis, itemsize)
    if not _interpret() and not plan["lane_aligned"]:
        return False
    return plan["fits"]


def resolve_backend(
    m: int, k_loc: int, n_loc: int, ring: int, shard_axis: int,
    itemsize: int,
) -> str:
    """'pallas' | 'decomposed' | 'xla' for this (shape, env)."""
    ov = _backend_override()
    if ov in ("0", "xla"):
        return "xla"
    if ov == "decomposed":
        return "decomposed"
    if ov == "pallas":
        return "pallas"
    if jax.default_backend() == "tpu" and _pallas_ok(
        m, k_loc, n_loc, ring, shard_axis, itemsize
    ):
        return "pallas"
    return "decomposed"


def _manual_region(
    shape: dict[str, int], manual: set[str], backend: str
) -> tuple[set[str], str]:
    """The shard_map's manual axes and the transport that can run there.

    Mosaic refuses a kernel inside a PARTIALLY manual region ("Mosaic
    kernels cannot be automatically partitioned" — every mesh axis must
    be manual; found by compiling for a described v5e, no CPU run can
    hit it). Size-1 axes of the trainer's 3-axis mesh therefore join the
    manual set for the pallas transport (a no-op for placement); a
    non-trivial axis left Auto sends it to the decomposed ring. Interpret
    mode has neither the refusal nor support for remote copies under
    more than one manual axis, so the CPU tests keep the narrow region."""
    if backend != "pallas" or _interpret():
        return manual, backend
    rest = set(shape) - manual
    if any(shape[name] > 1 for name in rest):
        return manual, "decomposed"
    return manual | rest, backend


# ---------------------------------------------------------------------------
# shared schedule helpers


def _right_perm(ring: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % ring) for i in range(ring)]


def _neighbor_device_id(mesh, axis_name: str, idx):
    """Remote-copy ``device_id`` for the right ring neighbor.

    Every non-trivial mesh axis is MANUAL here (the op shard_maps over
    the FSDP ring AND any TP axis — see overlap_dense_matmul), so each
    axis coordinate is available in-kernel: the ring axis steps to
    ``idx + 1``, size-1 axes sit at 0, and a manual TP axis keeps its own
    ``lax.axis_index``. Interpret mode supports only scalar LOGICAL ids —
    the row-major linearization of those coordinates; hardware gets the
    MESH coordinate tuple."""
    sizes = {n: int(s) for n, s in zip(mesh.axis_names, mesh.shape.values())}
    ring = sizes[axis_name]
    right = lax.rem(idx + 1, ring)
    coords = tuple(
        right if name == axis_name
        else (0 if sizes[name] == 1 else lax.axis_index(name))
        for name in mesh.axis_names
    )
    if not _interpret():
        return coords, pltpu.DeviceIdType.MESH
    linear = jnp.int32(0)
    for name, coord in zip(mesh.axis_names, coords):
        linear = linear * sizes[name] + coord
    return linear, pltpu.DeviceIdType.LOGICAL


def _neighbor_barrier(mesh, axis_name: str) -> None:
    """Both ring neighbors must be inside the kernel before any RDMA
    lands in their scratch. Hardware only: interpret mode has no barrier
    primitive — and no cross-kernel race either (the emulator sequences
    DMAs deterministically)."""
    if _interpret():
        return
    sizes = {n: int(s) for n, s in zip(mesh.axis_names, mesh.shape.values())}
    idx = lax.axis_index(axis_name)
    ring = sizes[axis_name]

    def coords(pos):
        return tuple(
            pos if name == axis_name
            else (0 if sizes[name] == 1 else lax.axis_index(name))
            for name in mesh.axis_names
        )

    sem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(sem, 1, device_id=coords(lax.rem(idx + 1, ring)))
    pltpu.semaphore_signal(
        sem, 1, device_id=coords(lax.rem(idx - 1 + ring, ring))
    )
    pltpu.semaphore_wait(sem, 2)


def _contract(xs, w_cur, w_t: bool):
    """One ring step's partial matmul, fp32 accumulation. ``w_t`` selects
    which w axis contracts: False -> xs @ w_cur, True -> xs @ w_curᵀ."""
    dims = (((1,), (1,)), ((), ())) if w_t else (((1,), (0,)), ((), ()))
    return lax.dot_general(
        xs, w_cur, dims, preferred_element_type=jnp.float32
    )


def _grad_partial(a, b):
    """aᵀ @ b over the local token rows, fp32 — the per-block grad matmul
    both reduce-scatter transports stream through the ring."""
    return lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# pallas transport — the genuinely fused kernels


def _overlap_ag_matmul_kernel(
    x_ref, w_ref, o_ref, w_slots, send_sem, recv_sem, *,
    ring, axis_name, mesh, slice_x, slice_out, w_t, blk_in, blk_out,
):
    """Fused ring all-gather-matmul: at step s the device matmuls the
    shard it holds (own at s=0, chunk ``(idx - s) % ring`` after) while
    the RDMA forwarding that shard to the right neighbor is in flight.

    Per-chunk receive slots (``w_slots[c]`` holds chunk c, written exactly
    once) + chained ``dma.wait()`` — the jax ring-all-gather discipline —
    so there is no buffer reuse and no flow-control semaphore needed.
    ``dma.wait()`` waits BOTH our send and the symmetric incoming copy, so
    reaching step s guarantees chunk ``(idx - s)`` has landed."""
    idx = lax.axis_index(axis_name)
    _sched("kernel", name="ag_matmul", ring=ring)
    _neighbor_barrier(mesh, axis_name)
    device_id, id_type = _neighbor_device_id(mesh, axis_name, idx)
    dma = None
    for s in range(ring):
        src = lax.rem(idx - s + ring, ring)
        if s > 0:
            _sched("dma_wait", step=s)
            dma.wait()
        if s < ring - 1:
            src_ref = w_ref if s == 0 else w_slots.at[src]
            # The copy lands in the RIGHT neighbor's w_slots at the same
            # chunk index (idx - s), i.e. the slot the neighbor reads at
            # ITS step s+1 — recorded sender-relative; the auditor
            # resolves absolute (device, slot) pairs.
            _sched(
                "dma_start", step=s,
                src_buf=("w_own" if s == 0 else "w_slots"),
                src_slot=(None if s == 0 else ("rel", -s)),
                dst_buf="w_slots", dst_slot=("rel", -s), dst_device=1,
            )
            dma = pltpu.make_async_remote_copy(
                src_ref=src_ref,
                dst_ref=w_slots.at[src],
                send_sem=send_sem,
                recv_sem=recv_sem,
                device_id=device_id,
                device_id_type=id_type,
            )
            dma.start()
        # Compute on the chunk while the forward RDMA is in flight — the
        # overlap the serialized all-gather-then-matmul never gets.
        _sched(
            "read", step=s,
            buf=("w_own" if s == 0 else "w_slots"),
            slot=(None if s == 0 else ("rel", -s)),
        )
        w_cur = w_ref[...] if s == 0 else w_slots[src]
        xs = (
            x_ref[:, pl.ds(src * blk_in, blk_in)] if slice_x else x_ref[...]
        )
        part = _contract(xs, w_cur, w_t)
        _sched("write", step=s, buf="o", slot=None)
        if slice_out:
            o_ref[:, pl.ds(src * blk_out, blk_out)] = part
        elif s == 0:
            o_ref[...] = part
        else:
            o_ref[...] = o_ref[...] + part


def _overlap_rs_matmul_kernel(
    a_ref, b_ref, o_ref, recv_buf, stage, send_sem, recv_sem, *,
    ring, axis_name, mesh, slice_a, blk,
):
    """Fused matmul + streamed ring reduce-scatter of the product.

    Grad block j starts its ring journey at device ``(j + 1) % ring`` and
    travels right, each device adding its local partial — so at step s
    device i computes the partial for block ``(i - s - 1) % ring``, adds
    the accumulator that just arrived, and sends onward WHILE the next
    block's matmul runs. After ``ring`` steps block i is fully reduced at
    device i: the reduce-scatter rode the ring under the grad matmuls.
    Receive slots are per-step (written once — no reuse race); the send
    stage is safe to rewrite because ``dma.wait()`` covers the previous
    send's completion."""
    idx = lax.axis_index(axis_name)
    _sched("kernel", name="rs_matmul", ring=ring)
    _neighbor_barrier(mesh, axis_name)
    device_id, id_type = _neighbor_device_id(mesh, axis_name, idx)
    dma = None
    acc = None
    for s in range(ring):
        j = lax.rem(idx - s - 1 + ring, ring)
        if slice_a:
            part = _grad_partial(a_ref[:, pl.ds(j * blk, blk)], b_ref[...])
        else:
            part = _grad_partial(a_ref[...], b_ref[:, pl.ds(j * blk, blk)])
        if s == 0:
            acc = part
        else:
            _sched("dma_wait", step=s)
            dma.wait()
            _sched("read", step=s, buf="recv", slot=("abs", s - 1))
            acc = recv_buf[s - 1] + part
        if s < ring - 1:
            # The stage rewrite is only safe because the wait above also
            # covered OUR previous send — the exact discipline the
            # auditor's send-rewrite rule checks.
            _sched("write", step=s, buf="stage", slot=None)
            stage[...] = acc
            _sched(
                "dma_start", step=s,
                src_buf="stage", src_slot=None,
                dst_buf="recv", dst_slot=("abs", s), dst_device=1,
            )
            dma = pltpu.make_async_remote_copy(
                src_ref=stage,
                dst_ref=recv_buf.at[s],
                send_sem=send_sem,
                recv_sem=recv_sem,
                device_id=device_id,
                device_id_type=id_type,
            )
            dma.start()
        else:
            _sched("write", step=s, buf="o", slot=None)
            o_ref[...] = acc


def _collective_compiler_params():
    """Kernels holding a barrier semaphore need a collective_id; interpret
    mode takes no compiler params."""
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(collective_id=7)}


def _pallas_ag_matmul(
    xl, wl, *, ring, axis_name, mesh, slice_x, slice_out, w_t,
):
    """shard_map-local fused all-gather-matmul. ``xl`` (m, K) token rows,
    ``wl`` the local shard; returns the full (m, N_out) product in fp32."""
    m = xl.shape[0]
    if w_t:
        n_out = wl.shape[0] * (ring if slice_out else 1)
        blk_out = wl.shape[0]
    else:
        n_out = wl.shape[1] * (ring if slice_out else 1)
        blk_out = wl.shape[1]
    blk_in = wl.shape[1] if w_t else wl.shape[0]
    kernel = functools.partial(
        _overlap_ag_matmul_kernel, ring=ring, axis_name=axis_name, mesh=mesh,
        slice_x=slice_x, slice_out=slice_out, w_t=w_t,
        blk_in=blk_in, blk_out=blk_out,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n_out), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((ring,) + wl.shape, wl.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=_interpret(),
        **_collective_compiler_params(),
    )(xl, wl)


def _pallas_rs_matmul(al, bl, *, ring, axis_name, mesh, slice_a):
    """shard_map-local fused matmul + grad reduce-scatter:
    ``RS_blocks(alᵀ @ bl)`` with the block axis over ``al``'s columns
    (slice_a) or ``bl``'s columns. Returns this device's fp32 block."""
    if slice_a:
        blk = al.shape[1] // ring
        out_shape = (blk, bl.shape[1])
    else:
        blk = bl.shape[1] // ring
        out_shape = (al.shape[1], blk)
    kernel = functools.partial(
        _overlap_rs_matmul_kernel, ring=ring, axis_name=axis_name, mesh=mesh,
        slice_a=slice_a, blk=blk,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((max(ring - 1, 1),) + out_shape, jnp.float32),
            pltpu.VMEM(out_shape, jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=_interpret(),
        **_collective_compiler_params(),
    )(al, bl)


# ---------------------------------------------------------------------------
# decomposed transport — the same schedules as ppermute + dot


def _decomposed_ag_matmul(
    xl, wl, idx, *, ring, axis_name, slice_x, slice_out, w_t,
):
    """ppermute ring with the identical step schedule as the kernel: XLA's
    async collective-permute overlaps each hop with the previous block's
    matmul (the unrolled loop makes every step schedulable — same
    rationale as ring_attention's unrolled ring).

    ``idx`` is the device's ring position, threaded in as a sharded-iota
    operand rather than ``lax.axis_index``: under a PARTIAL-manual region
    (the DP×FSDP×TP mesh, where "model" stays auto) this jax's SPMD
    partitioner rejected axis_index's PartitionId lowering when this was
    written; the iota operand sidesteps that on every backend."""
    perm = _right_perm(ring)
    m = xl.shape[0]
    blk_in = wl.shape[1] if w_t else wl.shape[0]
    blk_out = wl.shape[0] if w_t else wl.shape[1]
    n_out = blk_out * (ring if slice_out else 1)
    out = jnp.zeros((m, n_out), jnp.float32)
    w_cur = wl
    for s in range(ring):
        src = (idx - s) % ring
        xs = (
            lax.dynamic_slice_in_dim(xl, src * blk_in, blk_in, axis=1)
            if slice_x else xl
        )
        part = _contract(xs, w_cur, w_t)
        if slice_out:
            out = lax.dynamic_update_slice(out, part, (0, src * blk_out))
        else:
            out = out + part
        if s < ring - 1:
            w_cur = lax.ppermute(w_cur, axis_name, perm)
    return out


def _decomposed_rs_matmul(al, bl, idx, *, ring, axis_name, slice_a):
    """Streamed grad reduce-scatter at the XLA level: the partial-sum
    accumulator ppermutes right while the next block's matmul runs.
    ``idx``: sharded-iota ring position (see _decomposed_ag_matmul)."""
    perm = _right_perm(ring)
    blk = (al.shape[1] if slice_a else bl.shape[1]) // ring
    acc = None
    for s in range(ring):
        j = (idx - s - 1) % ring
        if slice_a:
            part = _grad_partial(
                lax.dynamic_slice_in_dim(al, j * blk, blk, axis=1), bl
            )
        else:
            part = _grad_partial(
                al, lax.dynamic_slice_in_dim(bl, j * blk, blk, axis=1)
            )
        acc = part if acc is None else acc + part
        if s < ring - 1:
            acc = lax.ppermute(acc, axis_name, perm)
    return acc


# ---------------------------------------------------------------------------
# the custom-vjp op (shard_map-local), one per (ring, mode, backend)


def _make_local_matmul(ring, axis_name, mesh, shard_axis, backend, out_dtype):
    """Build the shard_map-LOCAL fused matmul with its explicit backward:

    forward: all-gather-matmul (contract mode gathers the K shards and
    accumulates partials; out mode writes output column blocks).
    backward: dx re-gathers W through a second ring pass (ZeRO-3
    semantics — params are re-gathered for backward, never stored
    gathered), dw is the streamed matmul+reduce-scatter.

    TP reductions live OUTSIDE this custom VJP, on purpose: the out-mode
    forward's row-parallel psum is applied by the caller (so jax's own
    psum transpose composes with the shard_map boundary), and the
    contract-mode dx psum is shard_map's replicated-input transpose rule
    itself (a spec that omits the TP axis auto-psums its cotangent —
    verified against this jax in tests). Hand-rolling either INSIDE the
    VJP double-counts. The ring schedules never touch the TP axis.

    The local fn takes ``(xl, wl, il)`` with ``il`` the (1,) sharded-iota
    ring position (int32, zero cotangent): the decomposed transport needs
    it in place of ``lax.axis_index`` (see _decomposed_ag_matmul); the
    pallas kernels read their index in-kernel (Mosaic's own device id)."""
    if backend == "pallas":
        def ag(xl, wl, idx, **kw):
            del idx
            return _pallas_ag_matmul(
                xl, wl, ring=ring, axis_name=axis_name, mesh=mesh, **kw
            )

        def rs(al, bl, idx, **kw):
            del idx
            return _pallas_rs_matmul(
                al, bl, ring=ring, axis_name=axis_name, mesh=mesh, **kw
            )
    else:
        ag = functools.partial(
            _decomposed_ag_matmul, ring=ring, axis_name=axis_name
        )
        rs = functools.partial(
            _decomposed_rs_matmul, ring=ring, axis_name=axis_name
        )

    contract = shard_axis == 0

    def _fwd_impl(xl, wl, idx):
        # contract: out = sum_k x[:, blk_k] @ w_k ; out: out[:, blk_k] = x @ w_k
        return ag(
            xl, wl, idx, slice_x=contract, slice_out=not contract, w_t=False
        ).astype(out_dtype)

    @jax.custom_vjp
    def mm(xl, wl, il):
        return _fwd_impl(xl, wl, il[0])

    def mm_fwd(xl, wl, il):
        return _fwd_impl(xl, wl, il[0]), (xl, wl, il)

    def mm_bwd(res, dy):
        import numpy as np

        xl, wl, il = res
        idx = il[0]
        dy = dy.astype(out_dtype)
        if contract:
            # dx[:, blk_k] = dy @ w_kᵀ  (ring re-gather, out-block writes).
            # Under TP this is each rank's PARTIAL over its N/tp output
            # columns — the cross-rank sum is shard_map's own transpose
            # of the replicated-x in_spec (see docstring), not ours.
            dx = ag(dy, wl, idx, slice_x=False, slice_out=True, w_t=True)
            # dw_k = RS over K-blocks of xᵀ @ dy (streamed with its matmuls)
            dw = rs(xl, dy, idx, slice_a=True)
        else:
            # dx = sum_k dy[:, blk_k] @ w_kᵀ
            dx = ag(dy, wl, idx, slice_x=True, slice_out=False, w_t=True)
            # dw_k = RS over N-blocks of xᵀ @ dy
            dw = rs(xl, dy, idx, slice_a=False)
        return (
            dx.astype(xl.dtype), dw.astype(wl.dtype),
            np.zeros(il.shape, jax.dtypes.float0),
        )

    mm.defvjp(mm_fwd, mm_bwd)
    return mm


# ---------------------------------------------------------------------------
# public entry points


def _plain_dot(x, w):
    """The serialized fallback — a single dot, GSPMD inserts whatever
    collectives the shardings demand (the exact path overlapped mode
    replaces when it CAN)."""
    return jnp.matmul(x, w)


def overlap_dense_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    shard_axis: int,
    axis_name: str | None,
    tp_axis: str | None = None,
    mesh=None,
    backend: str | None = None,
) -> jax.Array:
    """``x @ w`` with the FSDP gather/reduce-scatter overlapped.

    ``x``: (..., K) activations (leading axes flattened to token rows —
    the batch axis is expected sharded over ``axis_name``); ``w``: (K, N)
    logical weight whose ``shard_axis`` (0 = contraction, 1 = output) is
    sharded over ``axis_name``. ``tp_axis``: the Megatron axis sharding
    w's OTHER dimension on a DP×FSDP×TP mesh — the region then goes
    manual over both axes (this jax's SPMD partitioner rejects
    partial-manual collectives — the PP/fsdp+ring known-env-failure
    class; full-manual also keeps the Pallas kernels usable under TP)
    with the two row-parallel psums made explicit in the custom VJP.

    Any inapplicable case — eager trace, no mesh/axis, ring of 1,
    non-divisible shard or batch tails — falls back to the plain
    serialized dot, so this is ALWAYS safe to call.
    """
    from jax._src.core import trace_state_clean

    if axis_name is None or trace_state_clean():
        return _plain_dot(x, w)
    if mesh is None:
        from dtc_tpu.parallel.sharding import ambient_mesh

        mesh = ambient_mesh(allow_empty=True)
        if mesh is None:
            return _plain_dot(x, w)
    shape = dict(zip(mesh.axis_names, (int(s) for s in mesh.shape.values())))
    ring = shape.get(axis_name, 1)
    if ring <= 1:
        return _plain_dot(x, w)
    if tp_axis is not None and (
        tp_axis == axis_name or shape.get(tp_axis, 1) <= 1
    ):
        tp_axis = None
    tp = shape.get(tp_axis, 1) if tp_axis is not None else 1
    k, n = int(w.shape[0]), int(w.shape[1])
    b = int(x.shape[0])
    ring_dim, tp_dim = (k, n) if shard_axis == 0 else (n, k)
    if ring_dim % ring != 0 or tp_dim % tp != 0 or b % ring != 0:
        # Non-divisible block tails (or a batch narrower than the ring —
        # generate/serving calls): the ring schedule has no tail handling
        # by design; the serialized dot is the documented fallback.
        return _plain_dot(x, w)

    m_local = 1
    for d in x.shape[:-1]:
        m_local *= int(d)
    m_local //= ring
    # LOCAL operand dims inside the manual region: x's contraction width
    # and the output width this device assembles.
    k_loc = k if shard_axis == 0 else k // tp
    n_loc = n // tp if shard_axis == 0 else n
    if backend is None:
        backend = resolve_backend(
            m_local, k_loc, n_loc, ring, shard_axis, x.dtype.itemsize
        )
    if backend == "xla":
        return _plain_dot(x, w)
    if backend == "pallas" and (
        not _pallas_ok(m_local, k_loc, n_loc, ring, shard_axis,
                       x.dtype.itemsize)
        # Interpret mode cannot emulate remote DMA across a multi-axis
        # manual mesh (LOGICAL ids are single-axis-only there); hardware
        # takes the MESH-coordinate path. CPU tests cover pallas on pure
        # FSDP rings and decomposed on the DP×FSDP×TP mesh.
        or (tp_axis is not None and _interpret())
    ):
        backend = "decomposed"
    manual = {axis_name} | ({tp_axis} if tp_axis is not None else set())
    manual, backend = _manual_region(shape, manual, backend)

    out_dtype = jnp.result_type(x.dtype, w.dtype)
    mm = _make_local_matmul(
        ring, axis_name, mesh, shard_axis, backend, out_dtype
    )

    def local(xl, wl, il):
        rows = xl.reshape(-1, xl.shape[-1])
        out = mm(rows, wl, il)
        if shard_axis == 1 and tp_axis is not None:
            # Row-parallel output (out_proj/fc2 under TP): each TP rank
            # assembled the full-N product from its K/tp contraction
            # slice — the Megatron all-reduce. OUTSIDE the custom VJP so
            # jax's psum transpose composes with the shard_map boundary
            # (hand-rolling it inside mis-scales the cotangent).
            out = lax.psum(out, tp_axis)
        return out.reshape(*xl.shape[:-1], out.shape[-1])

    mids = [None] * (x.ndim - 2)
    if shard_axis == 0:
        x_spec = P(axis_name, *mids, None)
        w_spec = P(axis_name, tp_axis)
        out_spec = P(axis_name, *mids, tp_axis)
    else:
        x_spec = P(axis_name, *mids, tp_axis)
        w_spec = P(tp_axis, axis_name)
        out_spec = P(axis_name, *mids, None)
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, w_spec, P(axis_name)),
        out_specs=out_spec,
        axis_names=manual,
        check_vma=False,
    )(x, w, jnp.arange(ring, dtype=jnp.int32))


def reduce_scatter_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    shard_axis: int,
    axis_name: str,
    mesh=None,
    backend: str | None = None,
) -> jax.Array:
    """Standalone streamed reduce-scatter-of-a-matmul: computes
    ``aᵀ @ b`` summed over the ring's token shards, scattered blockwise
    over ``shard_axis`` of the product (0 = a-columns, 1 = b-columns).
    This is exactly the backward dw op; exposed so the tests (and future
    callers — e.g. a hand-scheduled optimizer) can drive it directly
    against the ``psum_scatter`` oracle."""
    if mesh is None:
        from dtc_tpu.parallel.sharding import ambient_mesh

        mesh = ambient_mesh()
    shape = dict(zip(mesh.axis_names, (int(s) for s in mesh.shape.values())))
    ring = shape.get(axis_name, 1)
    if ring <= 1:
        return _grad_partial(a.reshape(-1, a.shape[-1]),
                             b.reshape(-1, b.shape[-1]))
    # Same fallback ladder as overlap_dense_matmul: env override first
    # ('0'/'xla' means no fused kernel here — the decomposed ring still
    # produces the reduce-scatter, just at the XLA level), then the
    # lane/VMEM gate. Shapes the kernel declines take the decomposed ring
    # instead of dying in Mosaic.
    m_local = 1
    for d in a.shape[:-1]:
        m_local *= int(d)
    m_local //= ring
    k_cols, n_cols = int(a.shape[-1]), int(b.shape[-1])
    blk = (k_cols if shard_axis == 0 else n_cols) // ring
    if blk == 0 or (k_cols if shard_axis == 0 else n_cols) % ring != 0:
        raise ValueError(
            f"reduce_scatter_matmul: scatter dim "
            f"{k_cols if shard_axis == 0 else n_cols} not divisible by "
            f"ring {ring}"
        )
    if backend is None:
        ov = _backend_override()
        if ov in ("0", "xla", "decomposed"):
            backend = "decomposed"
        elif ov == "pallas":
            backend = "pallas"
        else:
            backend = (
                "pallas" if jax.default_backend() == "tpu" else "decomposed"
            )
    if backend == "pallas":
        # Same accounting as overlap_plan's bwd_dw_rs leg — the shared
        # planner's single implementation (was a third inline copy).
        fits = vmem.rs_standalone_bytes(
            m_local, k_cols, n_cols, ring, shard_axis, a.dtype.itemsize
        ) <= _VMEM_BUDGET_BYTES
        if (not _interpret() and blk % _LANE != 0) or not fits:
            backend = "decomposed"
    manual, backend = _manual_region(shape, {axis_name}, backend)

    def local(al, bl, il):
        al = al.reshape(-1, al.shape[-1])
        bl = bl.reshape(-1, bl.shape[-1])
        if backend == "pallas":
            return _pallas_rs_matmul(
                al, bl, ring=ring, axis_name=axis_name, mesh=mesh,
                slice_a=shard_axis == 0,
            )
        return _decomposed_rs_matmul(
            al, bl, il[0], ring=ring, axis_name=axis_name,
            slice_a=shard_axis == 0,
        )

    row_spec = P(axis_name, *([None] * (a.ndim - 1)))
    out_spec = (
        P(axis_name, None) if shard_axis == 0 else P(None, axis_name)
    )
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(row_spec, row_spec, P(axis_name)),
        out_specs=out_spec,
        axis_names=manual,
        check_vma=False,
    )(a, b, jnp.arange(ring, dtype=jnp.int32))
