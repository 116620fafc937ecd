"""Static VMEM/SMEM planner — ONE byte accounting for every Pallas gate.

Before ISSUE 20 the repo carried two hand-rolled 14 MiB estimators
(``_VMEM_BUDGET_BYTES`` in decode_fused.py and overlap_collectives.py)
plus a third inline copy in ``reduce_scatter_matmul`` — three places for
the same arithmetic to drift. This module is the single implementation:

- **exact per-grid-step byte plans** derived from the kernels' own
  BlockSpecs + scratch_shapes (the megakernel's specs are literally BUILT
  from :func:`fused_layers_grid_plan`, so gate and kernel cannot
  disagree about a block shape);
- **the gates** every ``supports_*`` / ``_pallas_ok`` routing predicate
  consults (``dtc_tpu/analysis/kernels.py`` lints that they do);
- **the committed baselines' fingerprints** — ``analysis/kernels.py``
  emits these plans per (kernel, ladder rung) under
  ``analysis/baselines/`` with the report.py drift gate, including the
  double-buffered working set per rung and the ``vmem_limit_bytes`` the
  megakernel states to Mosaic because of it.

Deliberately jax-free: pure integer arithmetic over config dims, cheap
enough for routing predicates on every trace and importable from
anywhere (ops/, analysis/, scripts/) without dependency cycles.

All plans are PIPELINE-RESIDENT accounting: what Mosaic must co-locate
in VMEM for one grid step (input blocks + output blocks + scratch),
with in-register transients (score tiles, softmax rows) reported as a
separate *modeled* term — the 14 MiB budget intentionally sits ~2 MiB
under the ~16 MB/core of a v5e so single-query transients live in the
headroom, exactly the convention the old estimators used. Gates price
only what the old gates priced (weights + cache row, plus the ISSUE-20
spec-window surcharge RELATIVE to the single-query baseline), so
routing decisions are unchanged for every previously-supported shape.
"""

from __future__ import annotations

from typing import Any

#: Per-grid-step VMEM working-set budget shared by every fused-kernel
#: gate (was duplicated as ``_VMEM_BUDGET_BYTES`` in decode_fused.py and
#: overlap_collectives.py). ~16 MB/core on v5e; 14 MiB leaves headroom
#: for in-register activations, Mosaic's own spill, and semaphores.
VMEM_BUDGET_BYTES = 14 * 1024 * 1024

#: Added to a kernel's planned bytes when it states its own
#: ``vmem_limit_bytes``: room for Mosaic's temporaries (dequantized
#: cache slices, matmul staging) that no BlockSpec names.
VMEM_COMPILER_ALLOWANCE_BYTES = 8 * 1024 * 1024

#: Mosaic lane width: lane-dim dynamic slices on hardware must be
#: 128-aligned; interpret mode does not care (how the tiny CPU tests
#: drive the real kernels).
LANE = 128

#: Widest speculative verify window the megakernel serves in one launch
#: (re-exported as ``decode_fused._SPEC_MAX_K``). Tiny by design:
#: speculation past ~8 proposals is acceptance-rate-limited, and a small
#: static bound keeps the (t, S) score tiles inside the single-query
#: VMEM headroom (the gate prices the surcharge — see
#: :func:`fused_layers_plan`).
SPEC_MAX_K = 8

#: Longest cache the megakernel holds as one (S, H·D) tile per (layer,
#: row) grid step (re-exported as ``decode_fused._FUSED_LAYERS_MAX_S``).
FUSED_LAYERS_MAX_S = 4096

#: LoRA dense sites the megakernel threads factors for, with their
#: (in, out) dims as functions of (d_model, H·D, d_ff) — the same
#: canonical order as ``decode_fused._LORA_ATTN_SITES + _LORA_MLP_SITES``.
LORA_SITES = ("q_proj", "k_proj", "v_proj", "out_proj", "fc1", "fc2")

#: The megakernel's 16 per-layer weight blocks — the layer-streamed
#: class whose index maps MUST be b-invariant ("weights re-fetch per
#: layer, not per row"); shared by the byte plan and the kernel lint.
WEIGHT_BLOCK_NAMES = frozenset({
    "ln1_scale", "ln1_bias", "wq", "bq", "wk", "bk", "wv", "bv",
    "wo", "bo", "ln2_scale", "ln2_bias", "w1", "b1", "w2", "b2",
})


def _dtype_bytes(name: str) -> int:
    from dtc_tpu.config.schema import DTYPE_BYTES

    return DTYPE_BYTES.get(name, 4)


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def packed_group(d: int, h: int) -> tuple[int, int]:
    """(heads per lane block, lane block width) — the packed-layout
    grouping rule of ``flash_attention._packed_group`` /
    ``decode_attention._group`` (mirrored here so the planner stays
    jax-free; tests/test_kernel_audit.py pins the two against each
    other). 128-lane groups when head_dim divides the lane width and the
    group divides the head count; otherwise ONE block of all H·D lanes
    (tiny-model shapes, Mosaic pads internally)."""
    if d <= LANE and LANE % d == 0 and h % (LANE // d) == 0:
        return LANE // d, LANE
    return h, h * d


def _lora_dims(cfg) -> dict[str, tuple[int, int]]:
    dm, ff = cfg.d_model, cfg.d_ff
    hd = cfg.n_heads * cfg.head_dim
    return {
        "q_proj": (dm, hd), "k_proj": (dm, hd), "v_proj": (dm, hd),
        "out_proj": (hd, dm), "fc1": (dm, ff), "fc2": (ff, dm),
    }


def lora_sites_for(cfg) -> tuple[str, ...]:
    """The megakernel LoRA sites a config's adapter targets (canonical
    order; empty when adapters are off or the model is MoE — expert MLPs
    carry no fc1/fc2 dense sites)."""
    ad = getattr(cfg, "adapter", None)
    if ad is None or ad.rank <= 0:
        return ()
    targets = set(ad.target_modules)
    sites = [s for s in LORA_SITES if s in targets]
    if cfg.moe_experts > 0:
        sites = [s for s in sites if s not in ("fc1", "fc2")]
    return tuple(sites)


# ---------------------------------------------------------------------------
# decode megakernel (ops/decode_fused.py)
# ---------------------------------------------------------------------------


def fused_layers_grid_plan(
    cfg, t: int = 1, b: int = 1,
    lora_sites: tuple[str, ...] = (), lora_per_row: bool = False,
) -> dict[str, Any]:
    """The megakernel's grid/BlockSpec layout, symbolically.

    This is the SOURCE of ``decode_fused._fused_layers_call``'s specs —
    the kernel wrapper converts these entries into ``pl.BlockSpec``s, so
    the byte plan below and the launched kernel share one definition of
    every block shape and index map. Returns::

        {"grid": (L, b),
         "in_specs":  [(name, block_shape|None, index_map|None,
                        space, dtype_bytes), ...],
         "out_specs": [...same...],
         "scratch":   [(shape, dtype_bytes), ...]}

    ``block_shape is None`` means whole-array (the SMEM frontier).
    Index maps are plain callables of the grid coords ``(l, bb)`` —
    pure, and b-invariant exactly for the weight blocks (the "weights
    re-fetch per layer, not per row" pipelining contract
    ``analysis/kernels.py`` lints)."""
    dm, ff, H = cfg.d_model, cfg.d_ff, cfg.n_heads
    hd = H * cfg.head_dim
    L, S = cfg.n_layers, cfg.max_seq_len
    pb = _dtype_bytes(cfg.param_dtype)
    cb = _dtype_bytes(cfg.compute_dtype)
    quant = cfg.kv_quantized
    kvb = 1 if quant else _dtype_bytes(cfg.kv_store_dtype)

    def wmap(rank):
        return lambda l, bb, _r=rank: (l,) + (0,) * (_r - 1)

    row4 = lambda l, bb: (l, bb, 0, 0)  # noqa: E731
    xmap = lambda l, bb: (bb, 0, 0)     # noqa: E731

    weight_feats = [
        ("ln1_scale", (dm,)), ("ln1_bias", (dm,)),
        ("wq", (dm, hd)), ("bq", (hd,)),
        ("wk", (dm, hd)), ("bk", (hd,)),
        ("wv", (dm, hd)), ("bv", (hd,)),
        ("wo", (hd, dm)), ("bo", (dm,)),
        ("ln2_scale", (dm,)), ("ln2_bias", (dm,)),
        ("w1", (dm, ff)), ("b1", (ff,)),
        ("w2", (ff, dm)), ("b2", (dm,)),
    ]
    in_specs: list[tuple] = [
        ("frontier", None, None, "smem", 4),
        ("x", (1, t, dm), xmap, "vmem", cb),
    ]
    for name, feat in weight_feats:
        # Per-layer vectors (LayerNorm scale/bias, dense biases) ride as
        # (L, 1, feat) arrays blocked (1, 1, feat): Mosaic wants a
        # block's last two dims divisible by (8, 128) or equal to the
        # array's, and a (1, feat) block of an (L, feat) stack is
        # neither. The wrapper reshapes; the bytes are unchanged.
        shape = (1, 1) + feat if len(feat) == 1 else (1,) + feat
        in_specs.append((name, shape, wmap(len(shape)), "vmem", pb))
    in_specs += [
        ("k_row", (1, 1, S, hd), row4, "vmem", kvb),
        ("v_row", (1, 1, S, hd), row4, "vmem", kvb),
    ]
    if quant:
        in_specs += [
            ("k_scale_row", (1, 1, S, H), row4, "vmem", 4),
            ("v_scale_row", (1, 1, S, H), row4, "vmem", 4),
        ]
    rank = getattr(getattr(cfg, "adapter", None), "rank", 0)
    dims = _lora_dims(cfg)
    for site in lora_sites:
        din, dout = dims[site]
        for suffix, shp in (("a", (din, rank)), ("b", (rank, dout))):
            if lora_per_row:
                spec = (f"{site}_{suffix}", (1, 1) + shp, row4, "vmem", 4)
            else:
                full = (1,) + shp
                spec = (f"{site}_{suffix}", full, wmap(len(full)), "vmem", 4)
            in_specs.append(spec)

    out_specs = [
        ("x_out", (1, t, dm), xmap, "vmem", cb),
        ("k_new", (1, 1, t, hd), row4, "vmem", kvb),
        ("v_new", (1, 1, t, hd), row4, "vmem", kvb),
    ]
    if quant:
        out_specs += [
            ("k_scale_new", (1, 1, t, H), row4, "vmem", 4),
            ("v_scale_new", (1, 1, t, H), row4, "vmem", 4),
        ]
    scratch = [((max(b, 8), t, dm), cb)]
    # What the kernel asks Mosaic for (``vmem_limit_bytes``): every
    # blocked operand twice — Mosaic double-buffers blocked inputs and
    # outputs, which is what streams layer l+1's weights under layer l's
    # compute — plus scratch, the modeled in-register tiles and a fixed
    # allowance for the compiler's own temporaries. The chip's default
    # scoped limit (16 MiB on v5e) refuses the flagship's ~24 MiB
    # (tests/test_chip_compile.py); its physical VMEM is 128 MiB, and a
    # plan that passes the single-buffered gate asks for under a third
    # of that.
    blocked = sum(
        _prod(shape) * nbytes
        for _n, shape, _m, space, nbytes in in_specs + out_specs
        if space == "vmem"
    )
    limit = (
        2 * blocked
        + sum(_prod(shape) * nbytes for shape, nbytes in scratch)
        + fused_layers_transient_bytes(t, S)
        + VMEM_COMPILER_ALLOWANCE_BYTES
    )
    return {
        "grid": (L, b),
        "in_specs": in_specs,
        "out_specs": out_specs,
        "scratch": scratch,
        "vmem_limit_bytes": limit,
    }


def fused_layers_transient_bytes(t: int, s: int) -> int:
    """In-register score/softmax tiles of one head iteration, fp32."""
    return 2 * t * s * 4 + 2 * t * t * 4


def fused_layers_plan(cfg, t: int = 1, b: int = 1) -> dict[str, Any]:
    """Exact per-grid-step VMEM/SMEM byte plan for the decode megakernel
    at verify-window width ``t`` (1 = plain decode) and batch ``b``.

    Components (bytes, all per (layer, row) grid step):

    - ``weights`` — one layer's 16 stacked blocks, param dtype. Exact
      per-tensor shapes (the old estimator's ``4·(d² + d)`` assumed
      ``H·D == d_model``; q/k/v/out are really ``(d, H·D)``/``(H·D, d)``).
    - ``cache_row`` — one row's K/V tiles (+ fp32 scales when int8).
    - ``lora`` — the targeted sites' factor blocks (per-row and shared
      layouts stream identical bytes per step: one layer's (in, r) pair
      either way).
    - ``io`` — the x/x_out blocks and the t frontier cache-write blocks
      (+ scale writes) — the per-step t-proportional traffic PR 19 added.
    - ``scratch`` — the residual-carry VMEM scratch, ``(max(b,8), t, dm)``.
    - ``smem`` — the frontier scalars.
    - ``modeled_transients`` — in-register score/softmax tiles
      (``2·t·S·4 + 2·t²·4`` fp32 per head iteration), NOT BlockSpec
      bytes: reported for honesty, lives in the budget's headroom.

    Gate semantics (``gate_bytes``): the historical rule priced
    ``weights + cache_row`` against the budget with single-query io/
    transients absorbed by the 2 MiB headroom. The ISSUE-20 fix keeps
    that calibration and adds the SPEC-WINDOW SURCHARGE — the t-driven
    growth of io + scratch + transients RELATIVE to t=1 (k query/score
    rows, k cache writes per layer) — so a verify window cannot ride a
    gate that only priced one query row. ``fits`` folds in the MoE and
    single-tile-cache structural bounds: it IS ``supports_fused_layers``.

    ``double_buffered_bytes`` is 2× every streamed block (weights,
    cache row, LoRA, io — Mosaic prefetches grid step n+1 while n
    computes) + scratch + smem. The v5e compiler's answer to PR 10's
    open question (tests/test_chip_compile.py): it does double-buffer,
    the flagship needs a ~24 MiB scoped allocation against a 16 MiB
    default, so the kernel states ``vmem_limit_bytes`` (from
    :func:`fused_layers_grid_plan`) and the 14 MiB budget stays what it
    always was — a single-buffered routing gate, not the chip's
    capacity."""
    S = cfg.max_seq_len

    def _transients(tt: int) -> int:
        return fused_layers_transient_bytes(tt, S)

    def _grid(tt: int) -> dict[str, Any]:
        return fused_layers_grid_plan(
            cfg, t=tt, b=b, lora_sites=lora_sites_for(cfg),
            lora_per_row=False,
        )

    def _groups(plan: dict[str, Any]) -> dict[str, int]:
        groups: dict[str, int] = {
            "weights": 0, "cache_row": 0, "lora": 0, "io": 0,
            "scratch": 0, "smem": 0,
        }
        weight_names = {
            "ln1_scale", "ln1_bias", "wq", "bq", "wk", "bk", "wv", "bv",
            "wo", "bo", "ln2_scale", "ln2_bias", "w1", "b1", "w2", "b2",
        }
        for name, shape, _imap, space, nbytes in plan["in_specs"]:
            if space == "smem":
                groups["smem"] += nbytes * max(b, 1)
            elif name in weight_names:
                groups["weights"] += _prod(shape) * nbytes
            elif name.endswith(("_a", "_b")):
                groups["lora"] += _prod(shape) * nbytes
            elif name in ("k_row", "v_row", "k_scale_row", "v_scale_row"):
                groups["cache_row"] += _prod(shape) * nbytes
            else:
                groups["io"] += _prod(shape) * nbytes
        for name, shape, _imap, _space, nbytes in plan["out_specs"]:
            groups["io"] += _prod(shape) * nbytes
        for shape, nbytes in plan["scratch"]:
            groups["scratch"] += _prod(shape) * nbytes
        return groups

    grid_t = _grid(t)
    groups = _groups(grid_t)
    transients = _transients(t)
    base = groups if t == 1 else _groups(_grid(1))
    # The t-driven growth of io + scratch + in-register transients over
    # the single-query baseline — derived from the SAME grid plan the
    # kernel launches with, not a parallel formula.
    surcharge = (
        (groups["io"] - base["io"])
        + (groups["scratch"] - base["scratch"])
        + (transients - _transients(1))
    )
    gate_bytes = groups["weights"] + groups["cache_row"] + surcharge
    per_step = sum(groups.values())
    streamed = (
        groups["weights"] + groups["cache_row"] + groups["lora"]
        + groups["io"]
    )
    db_bytes = 2 * streamed + groups["scratch"] + groups["smem"]
    structural = cfg.moe_experts == 0 and S <= FUSED_LAYERS_MAX_S
    return {
        "kernel": "fused_layers",
        "grid": [cfg.n_layers, b],
        "t": t,
        "bytes": dict(groups),
        "per_step_bytes": per_step,
        "modeled_transient_bytes": transients,
        "spec_surcharge_bytes": surcharge,
        "gate_bytes": gate_bytes,
        "budget_bytes": VMEM_BUDGET_BYTES,
        "fits": structural and gate_bytes <= VMEM_BUDGET_BYTES,
        "double_buffered_bytes": db_bytes,
        "vmem_limit_bytes": grid_t["vmem_limit_bytes"],
    }


# ---------------------------------------------------------------------------
# training flash attention, packed family (ops/flash_attention.py)
# ---------------------------------------------------------------------------

#: ``attention_block_q`` / ``attention_block_kv`` at this value (the
#: schema's default, with both ``*_bwd`` at 0) mean "the kernel chooses":
#: :func:`flash_plan` picks the tiles from the shape. Any other value is
#: a user's tiling and runs as given.
FLASH_DEFAULT_BLOCK = 512

#: What a triangle flash kernel may plan for, blocks and scratch and the
#: modeled transients together: a quarter of a v5e core's 128 MiB. The
#: kernel states its own ``vmem_limit_bytes`` (the chip's default scoped
#: limit is 16 MiB), so this is a choice, not the compiler's default:
#: whole-sequence q blocks measured fastest up to T = 2048 (PERF.md, PR
#: 27); this admits the forward's there and neither pass's at 4096,
#: which was not timed.
FLASH_VMEM_BUDGET_BYTES = 32 * 1024 * 1024


def _flash_candidates(t: int) -> list[tuple[int, int, int]]:
    """(q block, KV chunk, unit) from the largest q block down. The chip
    pays per softmax update, not per element (PERF.md, PR 27: at T = 1024
    one 1024-row block in strips took 0.97 ms a call forward + backward,
    512-row blocks 1.19, 256-row blocks 1.48), so the q block is as large
    as VMEM allows, the whole sequence first: then nothing is carried from
    one update to the next. The unit of skipping is 256 (128 where the
    block is under 512 rows): 128 and 512 both measured slower at T = 512
    and 1024."""
    out = []
    for bq in (t, 1024, 512, 256, 128):
        if t % bq or bq > t or (out and bq >= out[-1][0]):
            continue
        unit = next((u for u in (256, 128) if bq % u == 0 and bq >= 2 * u), bq)
        out.append((bq, min(bq, 512) if bq % 512 == 0 else bq, unit))
    return out


def flash_schedule(t: int, block_q: int, chunk: int, unit: int = 0) -> dict[str, Any]:
    """What a schedule does with the T x T score square of one (row, lane
    group), counted in its units of skipping.

    ``unit`` 0 — the grid-walking kernels: the unit is a ``block_q x
    chunk`` tile, one softmax update each; tiles the diagonal crosses are
    masked, tiles above it predicated out.

    ``unit`` > 0 — the triangle inside the kernel: per q block one update
    of all its rows per KV chunk below its first row, then one per row
    strip of ``unit`` rows against the columns it sees in the block's own
    square: left of its diagonal unit unmasked, that unit masked, the
    rest never issued."""
    nq, nkv = t // block_q, t // chunk
    if unit == 0:
        rows, cols = block_q, chunk
        run = masked = 0
        for i in range(nq):
            for j in range(nkv):
                if j * chunk > (i + 1) * block_q - 1:
                    continue  # above the diagonal: not issued
                run += 1
                masked += (j + 1) * chunk - 1 > i * block_q
        updates, total = run, nq * nkv
    else:
        rows = cols = unit
        n = block_q // unit                       # strips per q block
        below = sum(i * (block_q // chunk) for i in range(nq))
        run = below * n * (chunk // unit) + nq * n * (n + 1) // 2
        masked = nq * n
        updates, total = below + nq * n, (t // unit) ** 2
    return {
        "block_q": block_q,
        "kv_chunk": chunk,
        "unit": [rows, cols],
        "updates": updates,
        "chunks_run": run,
        "chunks_masked": masked,
        "chunks_skipped": total - run,
        "covered_share": run * rows * cols / float(t * t),
    }


def _flash_leg(name, t, g, itemsize, bq, ck, unit) -> dict[str, Any]:
    """One pass's schedule and VMEM bytes at the given tiles. The
    transient term is calibrated against what the v5e compiler accepts
    (the least ``vmem_limit_bytes`` it took, searched for ten shapes, PR
    27): it gives every update of a q block its own fp32 score tile per
    head — the strips are unrolled and their stack slots are not shared —
    and came out 0.4 to 1 times the model's, never above it by more than
    the compiler allowance."""
    kv = 2 * t * LANE * itemsize                  # K and V, one lane group
    qblk = bq * LANE * itemsize
    lse = bq * LANE * 4                           # (block_q, g) pads to a lane tile
    carried = bq != t                             # several q blocks
    n = bq // unit
    scores = (unit * unit * n * (n + 1) // 2 + (bq * ck if carried else 0)) * 4
    if name == "fwd":
        blocks = kv + 2 * qblk + lse              # K, V, q, o, lse
        scratch = 3 * g * bq * LANE * 4 if carried else 0   # m, l, p.V
        transient = g * (scores + qblk)           # + q per head
    else:
        # dK/dV go out as (T, 128) blocks beside fp32 accumulators.
        blocks = 2 * kv + 4 * qblk + lse
        scratch = 2 * t * LANE * 4 + (bq * LANE * 4 if carried else 0)
        # + q and do per head, do * o in fp32
        transient = g * (scores + 2 * qblk) + bq * LANE * 4
    total = 2 * blocks + scratch                  # blocks double-buffered
    return {
        **flash_schedule(t, bq, ck, unit),
        "bytes": total,
        "modeled_transient_bytes": transient,
        "fits": total + transient <= FLASH_VMEM_BUDGET_BYTES,
        "vmem_limit_bytes": total + transient + VMEM_COMPILER_ALLOWANCE_BYTES,
    }


def flash_plan(t: int, d: int, h: int, itemsize: int = 2) -> dict[str, Any] | None:
    """The packed flash kernels' causal-triangle plan for ``(T, head_dim,
    heads, dtype bytes)``: per pass the tiles — the largest q block whose
    working set fits, K and V of a lane group held whole — the schedule's
    counters, the VMEM bytes and the ``vmem_limit_bytes`` the kernel
    states. None where the packed layout does not apply. ``fits`` False
    on a pass: nothing fits, the grid-walking kernels run."""
    g, lb = packed_group(d, h)
    if lb != LANE or t % LANE:
        return None
    plan: dict[str, Any] = {
        "kernel": "flash_packed", "t": t, "head_dim": d, "group": g,
        "lane_groups": h // g, "budget_bytes": FLASH_VMEM_BUDGET_BYTES,
    }
    for name in ("fwd", "bwd"):
        for tiles in _flash_candidates(t):
            plan[name] = _flash_leg(name, t, g, itemsize, *tiles)
            if plan[name]["fits"]:
                break
    return plan


def flash_grid_tile_fits(block_q: int, block_kv: int, itemsize: int = 4) -> bool:
    """The grid-walking flash kernels' working set for a user's tiling:
    one head's fp32 score and probability tiles plus the double-buffered
    q/k/v/o lane-group blocks and the statistics."""
    blocks = 2 * (2 * block_q + 2 * block_kv) * LANE * itemsize
    scratch = 3 * block_q * LANE * 4
    return 2 * block_q * block_kv * 4 + blocks + scratch <= VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# gated deltanet: chunk-local part and carry, one kernel a pass (ops/gated_delta.py)
# ---------------------------------------------------------------------------

#: Sublane count of a float32 tile: the chunk's positions lie along it.
SUBLANE = 8

#: (chunk, head) tiles one grid step of the Gated DeltaNet kernels takes
#: where the value heads allow it: enough that a step's fixed cost (~0.35 us)
#: and the latency of a tile's dependent 64-wide products are shared, few
#: enough that the unrolled body stays small.
GDN_TILES_PER_STEP = 8


def gdn_chunk_plan(
    chunk: int, dk: int, dv: int, hv: int, hk: int, itemsize: int = 2,
) -> dict[str, Any] | None:
    """The Gated DeltaNet kernels' plan (``ops/gated_delta.py``) for a chunk
    of ``chunk`` positions, ``hk`` key heads of ``dk`` serving ``hv`` value
    heads of ``dv``, q / k / v in ``itemsize`` bytes: the value heads one
    grid step takes, per pass the bytes of its blocks (as the BlockSpecs
    state them: q and k lane blocks of the step's key heads, v a block a
    value head, the output or its cotangent the same in float32 with the
    step's heads along the sublanes of its ``(C, heads, dv)`` block, the
    chunk's decay and beta rows in float32, a chunk's incoming ``(dk, dv)``
    state a value head — written by the forward that a backward follows,
    read by the backward), the float32 scratch that carries the state (or
    its cotangent) across a row's chunks, the float32 (C, C), (C, d) and
    (dk, dv) temporaries of the tiles in flight, and the
    ``vmem_limit_bytes`` the kernel states.

    None where a tile is not legal: ``dk`` and ``dv`` multiples of the
    lane width, the chunk a multiple of the sublane count and no wider
    than a lane tile, the key heads dividing the value heads. ``fits``
    False where no legal group of heads is inside the budget."""
    if dk % LANE or dv % LANE or chunk % SUBLANE or chunk > LANE or hk < 1 or hv % hk:
        return None
    rep = hv // hk
    # a step takes whole key heads, and its heads lie along the sublanes of
    # the output's block: a multiple of the sublane count, or all of them —
    # the most such value heads up to 8, else the fewest there are
    legal = [g for g in range(rep, hv + 1, rep) if hv % g == 0 and (g % SUBLANE == 0 or g == hv)]
    tiles = max((g for g in legal if g <= GDN_TILES_PER_STEP), default=legal[0])
    cc = chunk * max(chunk, LANE)                 # a (C, C) block pads to the lane width
    cd = chunk * max(dk, dv)
    state = tiles * dk * dv * 4
    rows = 2 * tiles * SUBLANE * LANE * 4         # a head's row a float32 tile of its own
    inputs = (2 * (tiles // rep) * chunk * dk + tiles * chunk * dv) * itemsize + rows
    out = tiles * chunk * dv * 4                  # the output, or its cotangent
    legs = {
        # the chunk-local part's dozen (C, C) and (C, d) arrays a tile, then
        # the carry's: v', the output, the state in dtype and its update
        "fwd": (inputs + out + state, tiles * (12 * (cc + cd) + 2 * cd + 3 * dk * dv) * 4),
        # + the carry's forward again and its pullback: five cotangents, the
        # output's, the state and its cotangent in dtype, the new cotangent
        "bwd": (2 * inputs + out + state, tiles * (12 * (cc + cd) + 8 * cd + cc + 5 * dk * dv) * 4),
    }
    plan: dict[str, Any] = {
        "kernel": "gdn_chunks", "chunk": chunk, "key_dim": dk, "value_dim": dv,
        "tiles": tiles, "key_heads_per_step": tiles // rep, "budget_bytes": VMEM_BUDGET_BYTES,
    }
    for name, (blocks, transient) in legs.items():
        total = 2 * blocks + state                # blocks double-buffered, the scratch not
        plan[name] = {
            "bytes": total,
            "scratch_bytes": state,
            "modeled_transient_bytes": transient,
            "fits": total <= VMEM_BUDGET_BYTES,
            "vmem_limit_bytes": total + transient + VMEM_COMPILER_ALLOWANCE_BYTES,
        }
    plan["fits"] = plan["fwd"]["fits"] and plan["bwd"]["fits"]
    return plan


# ---------------------------------------------------------------------------
# rotary positions in the packed layout (ops/rotary.py)
# ---------------------------------------------------------------------------


def rotary_plan(t: int, heads: int, d: int, itemsize: int = 2) -> dict[str, Any] | None:
    """The packed rotary kernel's plan (``ops/rotary.py``) for q and k of
    ``(B, T, heads * d)`` in ``itemsize`` bytes: the rows of a grid step —
    the most that divide ``T`` in whole sublane tiles of the dtype and keep
    the step's blocks, double-buffered, inside the budget (an elementwise
    pass is paid by the bytes: the larger the block, the fewer steps) — the
    bytes of those blocks (q and k in, q and k out, the ``(rows, d)``
    float32 cos and sin), the float32 temporaries of the one head in flight
    and the ``vmem_limit_bytes`` the kernel states.

    None where a head is not one lane tile (the swap of a head's halves is
    a roll of its 128 lanes by 64, which stays inside the head) or no such
    row count divides ``T``. ``fits`` False where even the least rows are
    over the budget."""
    tile = SUBLANE * max(1, 4 // itemsize)        # rows of a packed sublane tile
    if d != LANE or t % tile:
        return None

    def blocks(rows: int) -> int:
        return 4 * rows * heads * d * itemsize + 2 * rows * d * 4

    legal = [r for r in range(tile, t + 1, tile) if t % r == 0]
    rows = max((r for r in legal if 2 * blocks(r) <= VMEM_BUDGET_BYTES), default=legal[0])
    total = 2 * blocks(rows)                      # blocks double-buffered, no scratch
    transient = 6 * rows * d * 4                  # x, its roll, two products, cos, sin
    return {
        "kernel": "rotary_packed", "t": t, "heads": heads, "head_dim": d, "rows": rows,
        "bytes": total, "modeled_transient_bytes": transient,
        "budget_bytes": VMEM_BUDGET_BYTES, "fits": total <= VMEM_BUDGET_BYTES,
        "vmem_limit_bytes": total + transient + VMEM_COMPILER_ALLOWANCE_BYTES,
    }


# ---------------------------------------------------------------------------
# per-layer decode kernels (ops/decode_attention.py)
# ---------------------------------------------------------------------------


def decode_single_plan(cfg, s: int | None = None) -> dict[str, Any]:
    """Per-grid-step bytes of the single-tile decode kernel: grid
    ``(B, H·D/lane_block)``, one program holds q (1,1,lb), the full
    (1,s,lb) K and V tiles (+ (1,s,g) fp32 scale columns when int8) and
    the (1,1,lb) output. No scratch."""
    if s is None:
        s = cfg.max_seq_len
    g, lb = packed_group(cfg.head_dim, cfg.n_heads)
    cb = _dtype_bytes(cfg.compute_dtype)
    quant = cfg.kv_quantized
    kvb = 1 if quant else _dtype_bytes(cfg.kv_store_dtype)
    kv = 2 * s * lb * kvb
    scales = 2 * s * g * 4 if quant else 0
    io = 2 * lb * cb  # q block + output block
    total = kv + scales + io
    return {
        "kernel": "decode_single", "s": s, "lane_block": lb, "group": g,
        "bytes": {"kv_tiles": kv, "scales": scales, "io": io, "scratch": 0},
        "per_step_bytes": total,
        "budget_bytes": VMEM_BUDGET_BYTES,
        "fits": total <= VMEM_BUDGET_BYTES,
    }


def decode_blocked_plan(
    cfg, s: int | None = None, block_s: int = 512,
) -> dict[str, Any]:
    """Per-grid-step bytes of the blocked (online-softmax) decode
    kernel: KV walks in ``block_s`` chunks; scratch carries the running
    max/sum (two (8, 128) fp32 rows) and the (8, lane_block) fp32 output
    accumulator."""
    if s is None:
        s = cfg.max_seq_len
    g, lb = packed_group(cfg.head_dim, cfg.n_heads)
    cb = _dtype_bytes(cfg.compute_dtype)
    quant = cfg.kv_quantized
    kvb = 1 if quant else _dtype_bytes(cfg.kv_store_dtype)
    kv = 2 * block_s * lb * kvb
    scales = 2 * block_s * g * 4 if quant else 0
    io = 2 * lb * cb
    scratch = 2 * 8 * LANE * 4 + 8 * lb * 4
    total = kv + scales + io + scratch
    return {
        "kernel": "decode_blocked", "s": s, "block_s": block_s,
        "lane_block": lb, "group": g,
        "bytes": {"kv_tiles": kv, "scales": scales, "io": io,
                  "scratch": scratch},
        "per_step_bytes": total,
        "budget_bytes": VMEM_BUDGET_BYTES,
        "fits": total <= VMEM_BUDGET_BYTES,
    }


def decode_single_tile_fits(s: int, lanes: int = LANE) -> bool:
    """Worst-case (fp32 payload, 128-lane block) single-tile fit for a
    cache of length ``s`` — the VMEM leg of ``decode_attention.supports``
    (the structural ``s <= _DECODE_MAX_SINGLE_S`` bound remains the
    caller's; at the 14 MiB budget every single-tile-bounded cache fits,
    pinned in tests so the gate refactor cannot change routing)."""
    return 2 * s * lanes * 4 + 2 * s * 4 <= VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# ring collective kernels (ops/overlap_collectives.py)
# ---------------------------------------------------------------------------


def overlap_plan(
    m: int, k_loc: int, n_loc: int, ring: int, shard_axis: int,
    itemsize: int,
) -> dict[str, Any]:
    """Per-launch VMEM byte plan for the fused ring kernels, all three
    launches one backend decision covers (the PR 11 worst-of-three rule:
    fwd all-gather-matmul, bwd dx re-gather, bwd dw matmul+reduce-
    scatter). Shapes are the LOCAL shard_map-region shapes; ``m`` =
    flattened token rows per device.

    - fwd ag: x (m, k_loc) + fp32 out (m, n_loc) + the (ring receive
      slots + own shard) weight scratch.
    - bwd dx ag: dy (m, n_loc) + fp32 dx (m, k_loc) + the same slot set.
    - bwd dw rs: both operands + fp32 (recv slots + stage + out) of dw
      (:func:`rs_standalone_bytes` — also ``reduce_scatter_matmul``'s
      own gate)."""
    blk = (k_loc if shard_axis == 0 else n_loc) // ring
    wshard = (
        (k_loc // ring) * n_loc if shard_axis == 0
        else k_loc * (n_loc // ring)
    )
    slots = (ring + 1) * wshard
    legs = {
        "fwd_ag": m * k_loc * itemsize + m * n_loc * 4 + slots * itemsize,
        "bwd_dx_ag": m * n_loc * itemsize + m * k_loc * 4 + slots * itemsize,
        "bwd_dw_rs": rs_standalone_bytes(
            m, k_loc, n_loc, ring, shard_axis, itemsize
        ),
    }
    worst = max(legs.values())
    return {
        "kernel": "overlap_ring",
        "m": m, "k_loc": k_loc, "n_loc": n_loc, "ring": ring,
        "shard_axis": shard_axis, "itemsize": itemsize,
        "block": blk,
        "lane_aligned": blk % LANE == 0,
        "wshard_bytes": wshard * itemsize,
        "legs": legs,
        "worst_bytes": worst,
        "budget_bytes": VMEM_BUDGET_BYTES,
        "fits": worst <= VMEM_BUDGET_BYTES,
    }


def rs_standalone_bytes(
    m: int, k_cols: int, n_cols: int, ring: int, shard_axis: int,
    itemsize: int,
) -> int:
    """The streamed matmul+reduce-scatter launch working set: both
    operands + fp32 (ring-1 recv slots + stage + out) ≈ (ring+1) blocks
    of the scattered product."""
    blk = (k_cols if shard_axis == 0 else n_cols) // ring
    wshard = blk * (n_cols if shard_axis == 0 else k_cols)
    return m * (k_cols + n_cols) * itemsize + (ring + 1) * wshard * 4
