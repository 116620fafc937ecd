"""Layer-fused decode megakernel — ONE Pallas launch per token.

PERF.md round 7 pinned the b8 decode step at 8.1% of its bandwidth
roofline and attributed the gap to LAUNCH COUNT: the per-layer fused
kernel (ops/decode_attention.py) still dispatches one attention kernel
plus a handful of XLA fusions per layer per token — ~110 launches for the
12-layer flagship, each costing dispatch overhead that dwarfs the actual
byte traffic at decode shapes. This module folds the WHOLE per-layer
decode block into one resident kernel that scans the layer axis inside
its grid:

    per layer: LN1 -> q/k/v projection (+ LoRA deltas) -> int8/float
    cache write at the frontier -> single-query attention over the packed
    cache (dequant-in-register for int8) -> output projection (+ LoRA) ->
    residual -> LN2 -> MLP (+ LoRA) -> residual

so one decoded token costs O(1) launches (embed + megakernel + head +
the stacked cache scatter) instead of O(layers)·O(ops). The enabling
seams are prior refactors, not new model surgery:

- **Stacked layer params** (``nn.scan`` since the seed): every block
  weight already carries a leading ``(L,)`` axis, so a grid dimension
  over L block-indexes each layer's weights — the Pallas pipeline streams
  layer l+1's weights while layer l computes, which is exactly the
  scan-over-layers structure XLA runs, minus the per-layer dispatch.
- **The GPT-level single cache/index** (PR 4) and the **static-rank
  scalar/vector frontier branch** (PR 6): one SMEM frontier (scalar for
  ``generate``, ``(B,)`` for the serving engine's continuous-batching
  slots) drives every layer's masking and write position.
- **The stacked LoRA collection** (PR 9): per-site factors ride in as
  ``(L, in, r)`` (one shared adapter) or ``(L, B, in, r)`` (the engine's
  ``gather_slot_lora`` per-slot stack) and the low-rank deltas run
  in-kernel, so multi-tenant decode keeps the O(1)-launch property.

**Grid and memory**: grid ``(L, B)``, both dimensions sequential; a VMEM
scratch carries each row's residual stream across the L axis. Per grid
step the kernel holds one layer's weights + ONE batch row's cache tile
(weights re-fetch only when l advances — the index map is b-invariant).
:func:`supports_fused_layers` gates on an estimated per-step VMEM
working set (see ``_VMEM_BUDGET_BYTES``) and on ``max_seq_len <=
_FUSED_LAYERS_MAX_S`` — the whole-cache-row-in-one-tile regime of the
per-layer single kernel. Longer caches, prefill (multi-token) calls, and
MoE models fall back automatically to the per-layer path (which has the
blocked online-softmax flavor), so ``decode_attention: fused_layers`` is
always safe to set.

**Numerics**: fp32 LayerNorm stats (flax's fast-variance formula,
clipped at zero), fp32 scores/softmax, matmuls in compute dtype — the
same op-for-op recipe as the flax modules, asserted token-exact against
the ``xla`` einsum oracle (greedy, sampled, serving vector-index, and
stacked-LoRA paths) in tests/test_decode_fused.py. The current token's
k/v never round-trips through HBM: attention reads cache columns
``< frontier`` plus the in-register current k/v — after quantization,
so an int8 cache sees bit-identical values to the oracle's
write-then-read.

**Sharding caveat**: the megakernel is a single-device program (the
serving engine's deployment shape). Under a TP mesh the per-layer
``fused``/``xla`` paths shard over heads; ``fused_layers`` does not —
XLA cannot partition a ``pallas_call`` — so TP decode should keep the
per-layer backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtc_tpu.ops import vmem
from dtc_tpu.ops.decode_attention import KV_SCALE_FLOOR, NEG_INF, _interpret

_DTYPES = {
    "float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16,
}

#: Longest cache the megakernel holds as one (S, H·D) tile per (layer,
#: row) grid step — the same single-tile bound as the per-layer kernel.
#: Owned by the shared planner (ops/vmem.py) since ISSUE 20.
_FUSED_LAYERS_MAX_S = vmem.FUSED_LAYERS_MAX_S

#: Widest speculative verify window the megakernel serves as one launch
#: (t query positions against the frontier, causal among themselves
#: in-register). See ops/vmem.SPEC_MAX_K; spec/core.py imports this
#: alias.
_SPEC_MAX_K = vmem.SPEC_MAX_K

#: Per-grid-step VMEM working-set budget — the ONE shared constant in
#: ops/vmem.py (ISSUE 20 unified this module's copy with
#: overlap_collectives'). The flagship (12.6 MB fp32 weights + 1.05 MB
#: bf16 row) passes it single-buffered. Mosaic double-buffers the
#: streamed blocks, which the chip's default scoped limit refuses, so
#: the call states the planner's ``vmem_limit_bytes``.
_VMEM_BUDGET_BYTES = vmem.VMEM_BUDGET_BYTES

#: LoRA site order the kernel threads factors in (a subset, filtered by
#: presence in the model's "lora" collection).
_LORA_ATTN_SITES = ("q_proj", "k_proj", "v_proj", "out_proj")
_LORA_MLP_SITES = ("fc1", "fc2")

_LN_EPS = 1e-6  # flax.linen.LayerNorm default, the model's setting


def supports_fused_layers(cfg, t: int = 1) -> bool:
    """Whether the megakernel can serve ``cfg``'s decode at verify-window
    width ``t`` (1 = plain single-token decode).

    MoE blocks (expert dispatch inside a kernel is future work), caches
    past the single-tile bound, and per-step working sets over the VMEM
    budget all decline — callers fall back to the per-layer path. The
    byte accounting is :func:`dtc_tpu.ops.vmem.fused_layers_plan` —
    derived from the SAME grid plan :func:`_fused_layers_call` builds
    its BlockSpecs from, and t-aware since ISSUE 20: a speculative
    verify window's k query/score rows, k cache writes per layer, and
    k-wide residual scratch are priced as a surcharge over the
    single-query baseline instead of riding a gate that only priced one
    row."""
    return vmem.fused_layers_plan(cfg, t=t)["fits"]


def use_fused_layers(cfg, t_new: int, verify: bool = False) -> bool:
    """The decode_step routing predicate: knob on, single-token call (or
    a ``verify`` call of up to ``_SPEC_MAX_K`` query positions — the
    speculative k-token verify, ISSUE 19), supported shape AT THIS
    WIDTH (the planner prices the verify window's working set, not just
    a single query row). Prefill (multi-token WITHOUT ``verify``) keeps
    falling back to the per-layer path: a prompt pass is compute-bound
    and belongs to XLA's fusions, while a verify window is the same
    frontier-append regime as decode."""
    ok_t = t_new == 1 or (verify and 2 <= t_new <= _SPEC_MAX_K)
    return (
        getattr(cfg, "decode_attention", None) == "fused_layers"
        and ok_t
        and supports_fused_layers(cfg, t=t_new)
    )


def decode_backend(cfg, t_new: int = 1, verify: bool = False) -> str:
    """Which backend one decode call of ``t_new`` tokens takes — the
    ladder fused_layers -> fused -> xla read off the SAME predicates the
    program routes by (:func:`use_fused_layers` in
    ``generate.decode_step``, ``decode_attention.use_fused`` in the
    attention module). For callers that must know what ran rather than
    what was configured (``chip_smoke.py``)."""
    from dtc_tpu.ops import decode_attention

    if use_fused_layers(cfg, t_new, verify=verify):
        return "fused_layers"
    if decode_attention.use_fused(cfg, t_new):
        return "fused"
    return "xla"


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _fused_layers_kernel(
    *refs,
    h, d, s, t, dm, quant, per_row, lora_sites, lora_per_row, lora_scale,
    cdtype, kv_dtype,
):
    """One (layer, batch-row) grid step of the fused decode block.

    ``t`` is the number of in-register query positions: 1 for plain
    decode, or the speculative verify window (ISSUE 19) — the ``t``
    tokens all sit at the frontier (positions ``start .. start+t-1``),
    attend to cache columns ``< start`` plus each other causally
    in-register, and their k/v land in the ``(.., t, ..)`` frontier
    updates the caller scatters in one slice.

    ``refs`` order (inputs, then outputs, then scratch — the pallas_call
    contract): frontier (SMEM), x, 16 weight blocks (ln1 s/b, q/k/v/out
    kernel+bias, ln2 s/b, fc1/fc2 kernel+bias), K cache row, V cache row,
    [k/v scale rows], LoRA a/b pairs per site; x_out, k_new, v_new,
    [k/v scale_new]; x carry scratch."""
    it = iter(refs)
    idx_ref, x_ref = next(it), next(it)
    (ln1s, ln1b, wq, bq, wk, bk, wv, bv, wo, bo,
     ln2s, ln2b, w1, b1, w2, b2) = (next(it) for _ in range(16))
    k_ref, v_ref = next(it), next(it)
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = next(it), next(it)
    lora_refs = {site: (next(it), next(it)) for site in lora_sites}
    x_out = next(it)
    k_out, v_out = next(it), next(it)
    ks_out = vs_out = None
    if quant:
        ks_out, vs_out = next(it), next(it)
    x_scr = next(it)

    l = pl.program_id(0)
    b = pl.program_id(1)
    start = idx_ref[b] if per_row else idx_ref[0]
    att_scale = float(d) ** -0.5

    @pl.when(l == 0)
    def _():
        x_scr[pl.ds(b, 1)] = x_ref[0][None]

    x = x_scr[pl.ds(b, 1)][0]                       # (t, dm) residual

    def ln(xx, s_ref, b_ref):
        # flax LayerNorm, op-for-op: fp32 fast-variance stats clipped at
        # zero, (x - mean) * (rsqrt(var + eps) * scale) + bias, fp32 out.
        xf = xx.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.maximum(
            0.0, jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean
        )
        mul = jax.lax.rsqrt(var + _LN_EPS) * s_ref[0]
        return (xf - mean) * mul + b_ref[0]

    def _dot(a, w):
        # Mosaic's matmul accumulates in 32 bits and refuses any other
        # result type; XLA's compute-dtype dot accumulates in fp32 and
        # rounds once too, so fp32-then-cast is the same arithmetic.
        return jax.lax.dot_general(
            a, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(cdtype)

    def dense(xx, w_ref, bias_ref):
        # nn.Dense: inputs/kernel/bias promoted to compute dtype, plain
        # dot_general (output dtype = compute dtype), bias added after.
        y = _dot(xx.astype(cdtype), w_ref[0].astype(cdtype))
        return y + bias_ref[0].astype(cdtype)

    def lora(site, xx, y):
        # adapters/lora.apply_lora: y + scale * ((x @ A) @ B), factors
        # cast to compute dtype; per-row factors index this row's block.
        if site not in lora_refs:
            return y
        a_ref, b_ref = lora_refs[site]
        av = (a_ref[0, 0] if lora_per_row else a_ref[0]).astype(cdtype)
        bv = (b_ref[0, 0] if lora_per_row else b_ref[0]).astype(cdtype)
        delta = _dot(_dot(xx.astype(cdtype), av), bv)
        return y + (lora_scale * delta).astype(y.dtype)

    # ---- attention leg ----
    h_ln = ln(x, ln1s, ln1b).astype(cdtype)
    q_vec = lora("q_proj", h_ln, dense(h_ln, wq, bq))       # (t, hd)
    k_vec = lora("k_proj", h_ln, dense(h_ln, wk, bk))
    v_vec = lora("v_proj", h_ln, dense(h_ln, wv, bv))

    kt, vt = k_ref[0, 0], v_ref[0, 0]                        # (s, hd)
    ks = ks_ref[0, 0] if quant else None                     # (s, h) fp32
    vs = vs_ref[0, 0] if quant else None
    col = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    mask = col < start  # strictly: the current tokens ride in-register
    # Causal mask AMONG the t in-register positions: row j (cache slot
    # start+j) sees in-register columns 0..j — together with the strict
    # cache mask this is exactly the oracle's ``col <= start + row``.
    rowq = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    colq = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    nmask = colq <= rowq
    if not quant:
        k_out[0, 0] = k_vec.astype(kv_dtype)
        v_out[0, 0] = v_vec.astype(kv_dtype)

    outs = []
    for gg in range(h):
        sl = slice(gg * d, (gg + 1) * d)
        # The current tokens' k/v, exactly as a reader would see them
        # AFTER the cache write: quantize (per-(position, head) fp32
        # scale, the quantize_kv reference arithmetic) then dequantize
        # in-register — int8 attention is bit-identical to the oracle's
        # write-then-dequant, and the raw values never touch HBM.
        if quant:
            kf = k_vec[:, sl].astype(jnp.float32)
            vf = v_vec[:, sl].astype(jnp.float32)
            k_sc = jnp.maximum(
                jnp.max(jnp.abs(kf), axis=-1, keepdims=True), KV_SCALE_FLOOR
            ) / 127.0                                # (t, 1)
            v_sc = jnp.maximum(
                jnp.max(jnp.abs(vf), axis=-1, keepdims=True), KV_SCALE_FLOOR
            ) / 127.0
            kq = jnp.clip(jnp.round(kf / k_sc), -127.0, 127.0)
            vq = jnp.clip(jnp.round(vf / v_sc), -127.0, 127.0)
            k_out[0, 0, :, sl] = kq.astype(kv_dtype)
            v_out[0, 0, :, sl] = vq.astype(kv_dtype)
            ks_out[0, 0, :, gg:gg + 1] = k_sc
            vs_out[0, 0, :, gg:gg + 1] = v_sc
            k_new = (kq * k_sc).astype(cdtype)
            v_new = (vq * v_sc).astype(cdtype)
            k_h = (kt[:, sl].astype(jnp.float32) * ks[:, gg:gg + 1]).astype(cdtype)
            v_h = (vt[:, sl].astype(jnp.float32) * vs[:, gg:gg + 1]).astype(cdtype)
        else:
            k_new = k_vec[:, sl].astype(kv_dtype).astype(cdtype)
            v_new = v_vec[:, sl].astype(kv_dtype).astype(cdtype)
            k_h, v_h = kt[:, sl], vt[:, sl]
            if k_h.dtype != cdtype:
                k_h, v_h = k_h.astype(cdtype), v_h.astype(cdtype)
        q_h = q_vec[:, sl] * att_scale
        sc = jax.lax.dot_general(
            q_h, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (t, s) fp32
        sc = jnp.where(mask, sc, NEG_INF)
        sc_new = jax.lax.dot_general(
            q_h, k_new, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (t, t) fp32
        sc_new = jnp.where(nmask, sc_new, NEG_INF)
        m = jnp.maximum(
            jnp.max(sc, axis=-1, keepdims=True),
            jnp.max(sc_new, axis=-1, keepdims=True),
        )                                            # (t, 1); row 0's own
        # diagonal score is always live, so m is finite even at start==0
        p = jnp.exp(sc - m)
        p_new = jnp.exp(sc_new - m)                  # masked cols -> 0
        lsum = (
            jnp.sum(p, axis=-1, keepdims=True)
            + jnp.sum(p_new, axis=-1, keepdims=True)
        )
        acc = jax.lax.dot_general(
            p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            p_new, v_new.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (t, d) fp32
        outs.append((acc / lsum).astype(cdtype))
    attn = jnp.concatenate(outs, axis=1)             # (t, hd)
    o = lora("out_proj", attn, dense(attn, wo, bo))
    x = x + o.astype(x.dtype)

    # ---- MLP leg ----
    h2 = ln(x, ln2s, ln2b).astype(cdtype)
    m1 = lora("fc1", h2, dense(h2, w1, b1))
    g = jax.nn.gelu(m1, approximate=True)            # flax nn.gelu default
    m2 = lora("fc2", g, dense(g, w2, b2))
    x = x + m2.astype(x.dtype)

    x_scr[pl.ds(b, 1)] = x[None]
    x_out[0] = x  # last write (l == L-1) wins; earlier flushes are dead


# ---------------------------------------------------------------------------
# host-side wrapper + the decode-step orchestration
# ---------------------------------------------------------------------------


def _lora_inputs(lora_tree, cfg):
    """Flatten the "lora" subtree into the kernel's (sites, arrays,
    per_row) in canonical site order; absent sites simply don't appear
    (un-targeted modules, MoE's missing fc1/fc2)."""
    if lora_tree is None:
        return (), [], False
    sites, arrays = [], []
    per_row = False
    groups = (
        ("attn", _LORA_ATTN_SITES),
        ("mlp", _LORA_MLP_SITES),
    )
    for mod, names in groups:
        sub = lora_tree.get(mod, {}) if isinstance(lora_tree, dict) else {}
        for site in names:
            a = sub.get(f"{site}_a")
            if a is None:
                continue
            sites.append(site)
            arrays.extend([a, sub[f"{site}_b"]])
            per_row = a.ndim == 4
    return tuple(sites), arrays, per_row


def _fused_layers_call(x, blocks_p, blocks_c, idx, lora_tree, cfg):
    """Invoke the megakernel: ``x`` (B, t, d_model) post-embed residual
    (t == 1 for plain decode, t <= ``_SPEC_MAX_K`` for a speculative
    verify window), ``blocks_p`` the stacked block params, ``blocks_c``
    the attn cache subtree, ``idx`` the scalar or (B,) frontier. Returns
    ``(x_out, writes)`` where ``writes`` maps cache leaf name -> the
    (L, B, t, ...) frontier updates the caller scatters in."""
    b, t = x.shape[0], x.shape[1]
    dm, H, D = cfg.d_model, cfg.n_heads, cfg.head_dim
    hd, L, S = H * D, cfg.n_layers, cfg.max_seq_len
    cdtype = _DTYPES[cfg.compute_dtype]
    quant = cfg.kv_quantized
    kv_dtype = jnp.int8 if quant else _DTYPES[cfg.kv_store_dtype]

    idx = jnp.asarray(idx, jnp.int32)
    per_row = idx.ndim == 1
    idx_arr = idx if per_row else idx.reshape((1,))

    attn_p, mlp_p = blocks_p["attn"], blocks_p["mlp"]
    weights = [
        blocks_p["ln_1"]["scale"], blocks_p["ln_1"]["bias"],
        attn_p["q_proj"]["kernel"], attn_p["q_proj"]["bias"],
        attn_p["k_proj"]["kernel"], attn_p["k_proj"]["bias"],
        attn_p["v_proj"]["kernel"], attn_p["v_proj"]["bias"],
        attn_p["out_proj"]["kernel"], attn_p["out_proj"]["bias"],
        blocks_p["ln_2"]["scale"], blocks_p["ln_2"]["bias"],
        mlp_p["fc1"]["kernel"], mlp_p["fc1"]["bias"],
        mlp_p["fc2"]["kernel"], mlp_p["fc2"]["bias"],
    ]
    # Per-layer vectors go in as (L, 1, feat): see the block-shape note
    # in ops/vmem.fused_layers_grid_plan.
    weights = [w[:, None] if w.ndim == 2 else w for w in weights]
    lora_sites, lora_arrays, lora_per_row = _lora_inputs(lora_tree, cfg)

    # Block shapes and index maps come from the shared static planner —
    # the SAME grid plan ops/vmem.fused_layers_plan prices and the
    # kernel auditor (analysis/kernels.py) lints, so the VMEM gate, the
    # committed baselines, and the launched kernel cannot drift apart.
    plan = vmem.fused_layers_grid_plan(
        cfg, t=t, b=b, lora_sites=lora_sites, lora_per_row=lora_per_row,
    )

    def _spec(entry):
        _name, shape, imap, space, _nbytes = entry
        if space == "smem":
            return pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.BlockSpec(shape, imap)

    in_specs = [_spec(e) for e in plan["in_specs"]]
    args = [idx_arr, x, *weights, blocks_c["k"], blocks_c["v"]]
    if quant:
        args += [blocks_c["k_scale"], blocks_c["v_scale"]]
    args += lora_arrays

    out_specs = [_spec(e) for e in plan["out_specs"]]
    out_shapes = [
        jax.ShapeDtypeStruct((b, t, dm), cdtype),                  # x_out
        jax.ShapeDtypeStruct((L, b, t, hd), kv_dtype),             # k_new
        jax.ShapeDtypeStruct((L, b, t, hd), kv_dtype),             # v_new
    ]
    if quant:
        out_shapes += [jax.ShapeDtypeStruct((L, b, t, H), jnp.float32)] * 2

    res = pl.pallas_call(
        functools.partial(
            _fused_layers_kernel,
            h=H, d=D, s=S, t=t, dm=dm, quant=quant, per_row=per_row,
            lora_sites=lora_sites, lora_per_row=lora_per_row,
            lora_scale=float(cfg.adapter.scale), cdtype=cdtype,
            kv_dtype=kv_dtype,
        ),
        grid=(L, b),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM(shape, cdtype) for shape, _nb in plan["scratch"]
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan["vmem_limit_bytes"],
        ),
        interpret=_interpret(),
    )(*args)

    writes = {"k": res[1], "v": res[2]}
    if quant:
        writes["k_scale"], writes["v_scale"] = res[3], res[4]
    return res[0], writes


def _scatter_frontier(cache_leaf, update, idx):
    """Write the (L, B, t, X) frontier updates into the (L, B, S, X)
    stacked cache at the scalar — or per-row (B,) — frontier: ONE
    dynamic update per leaf for the whole layer stack (the O(1)-launch
    property the megakernel exists for). ``t`` rows land contiguously at
    ``idx .. idx+t-1`` — the verify window's k positions in one slice."""
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache_leaf, update, (0, 0, idx, 0)
        )
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (0, i, 0)),
        in_axes=(1, 1, 0), out_axes=1,
    )(cache_leaf, update, idx)


def _block_subtree(tree):
    """Descend a "stage"/"blocks" collection subtree to the per-block
    module level. The scanned ``_ScanBlock`` wraps ``Block`` as one
    auto-named child (``Block_0``), so the module dict ("attn"/"ln_1"/…)
    sits one level below "blocks" — tolerate either nesting so a future
    pinned-name refactor cannot silently break this path."""
    sub = tree["stage"]["blocks"]
    if "attn" not in sub and len(sub) == 1:
        sub = next(iter(sub.values()))
    return sub


def fused_decode_step(model, params, cache, tok, lora=None):
    """The ``decode_attention: fused_layers`` step —
    :func:`dtc_tpu.generate.decode_step`'s fast path, shared verbatim by
    the greedy scan and the serving engine. ``tok`` is (B, 1) for plain
    decode or (B, k) for a speculative verify window (ISSUE 19): the k
    logits rows come back in ONE launch, the k cache writes land in one
    stacked scatter, and rollback after partial acceptance is a frontier
    decrement by the caller (positions past the frontier are invisible —
    every read masks ``col < frontier`` — and are rewritten by whichever
    later step advances over them, so no cache surgery ever happens).

    Embed and head apply the REAL flax modules on their param subtrees
    (identical ops to the per-layer path — parity by construction); the
    layer stack runs through the megakernel; the cache write is one
    stacked scatter per K/V (+scale) leaf; the GPT-level index advances
    by one. The returned cache has the exact pytree structure
    ``model.apply(..., mutable=["cache"])`` produces, so the engine's
    traced-slot surgery and checksum table consume it unchanged.

    CALLER CONTRACT (same as GPT.__call__): cumulative decoded length
    must stay <= ``cfg.max_seq_len`` — this path hosts no checkify guard
    (``generate`` enforces the bound statically; the engine's page
    accounting enforces it per slot)."""
    from dtc_tpu.models.gpt import GPTEmbed, GPTHead

    cfg = model.cfg
    t = tok.shape[1]
    idx = jnp.asarray(cache["index"], jnp.int32)
    h = GPTEmbed(cfg).apply(
        {"params": params["embed"]}, tok, train=False,
        pos_offset=idx, decode=True,
    )
    lora_tree = None if lora is None else _block_subtree(lora)
    attn_c = _block_subtree(cache)["attn"]
    h, writes = _fused_layers_call(
        h, _block_subtree(params), attn_c, idx, lora_tree, cfg,
    )
    logits = GPTHead(cfg).apply({"params": params["head"]}, h)
    new_attn = {
        name: _scatter_frontier(attn_c[name], upd, idx)
        for name, upd in writes.items()
    }
    # Rebuild the cache with the EXACT pytree structure model.apply
    # produces (including the scanned block's auto-name level), so the
    # engine's generic tree surgery and the greedy scan's carry both see
    # an unchanged treedef.
    blocks = dict(cache["stage"]["blocks"])
    if "attn" in blocks:
        blocks["attn"] = new_attn
    else:
        inner_name = next(iter(blocks))
        blocks[inner_name] = dict(blocks[inner_name], attn=new_attn)
    return {"index": idx + t, "stage": {"blocks": blocks}}, logits
