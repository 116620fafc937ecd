"""Flash-attention parity vs the dense reference implementation.

Runs the Pallas kernels in interpreter mode on CPU (conftest forces the CPU
platform); the same code compiles via Mosaic on TPU. Parity target:
``dense_causal_attention`` (ops/attention.py), which itself reproduces the
reference semantics (`/root/reference/model/CausalSelfAttention.py:34-42`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.ops.attention import causal_attention, dense_causal_attention
from dtc_tpu.ops.flash_attention import flash_causal_attention, supports

# Interpret-mode kernel suite: minutes on a 1-core host. `pytest -m quick`
# skips it; tier-1 (`-m 'not slow'`) still runs it.
pytestmark = pytest.mark.kernels


def _qkv(key, b, t, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


# (T, D, block_q, block_kv): flagship-like padded head_dim, lane-sized head
# dim, and multi-block tilings exercising the online-softmax accumulation.
SHAPES = [
    (256, 32, 256, 256),    # single block, padded head_dim (flagship-like)
    (256, 128, 128, 128),   # 2x2 blocks, lane-width head_dim
    (512, 32, 128, 128),    # 4x4 blocks, padded head_dim (flagship tiling)
    (512, 64, 256, 128),    # rectangular blocks
]


# The schedule the kernel chooses (blocks left at their defaults): the causal
# triangle followed inside the kernel, at the benchmark cells' head layouts
# (16 and 20 heads of 64: two a lane group) and the flagship's (16 of 32:
# four a lane group). (T, D, H, dtype); one row so interpret mode stays fast.
CHOSEN = [
    (t, d, h, dtype)
    for t in (256, 512, 1024)
    for d, h in ((64, 16), (64, 20), (32, 16))
    for dtype in (jnp.float32, jnp.bfloat16)
]
_chosen_id = lambda c: f"chosen-T{c[0]}-d{c[1]}-h{c[2]}-{c[3].__name__}"  # noqa: E731

# A case is (T, D, H, blocks, dtype): explicit tilings on three heads (the
# transpose family) or the chosen schedule.
FWD_CASES = [
    pytest.param((t, d, 3, dict(block_q=bq, block_kv=bkv), jnp.float32),
                 id=f"{t}-{d}-{bq}-{bkv}")
    for t, d, bq, bkv in SHAPES
] + [pytest.param((t, d, h, {}, dtype), id=_chosen_id((t, d, h, dtype)))
     for t, d, h, dtype in CHOSEN]


def _tol(dtype, fp32):
    # bf16 has ~3 decimal digits; compare in fp32 with a loose tolerance.
    return fp32 if dtype == jnp.float32 else 0.05


@pytest.mark.parametrize("case", FWD_CASES)
def test_forward_parity(case):
    t, d, h, blocks, dtype = case
    q, k, v = _qkv(jax.random.PRNGKey(0), 1 if not blocks else 2, t, h, d, dtype)
    ref = dense_causal_attention(q, k, v)
    got = flash_causal_attention(q, k, v, **blocks)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert err < _tol(dtype, 2e-5)


@pytest.mark.parametrize("case", FWD_CASES)
def test_grad_parity(case):
    t, d, h, blocks, dtype = case
    h = 2 if blocks else h
    q, k, v = _qkv(jax.random.PRNGKey(1), 1 if not blocks else 2, t, h, d, dtype)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss(dense_causal_attention), argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(
        loss(lambda q, k, v: flash_causal_attention(q, k, v, **blocks)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_got):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-8)
        assert err < _tol(dtype, 2e-4), f"d{name} relative error {err}"


def test_chosen_plan_follows_the_causal_triangle():
    """What the code chooses for the cells' shape (T 1024, head size 64):
    at most 0.625 of the score square, a mask on the diagonal's units
    only, and no unit above the diagonal — read from the same static
    function the dispatch and the trainer's ``flash_plan`` event read."""
    from dtc_tpu.ops import vmem
    from dtc_tpu.ops.flash_attention import schedule

    for h in (16, 20):
        plan = schedule(1024, h, 64)
        for leg in (plan["fwd"], plan["bwd"]):
            assert leg["schedule"] == "triangle"
            assert leg["covered_share"] <= 0.625
            rows, cols = leg["unit"]
            assert rows == cols <= 256
            n = 1024 // rows
            assert leg["chunks_masked"] == n                 # the diagonal's units, no other
            assert leg["chunks_run"] == n * (n + 1) // 2     # every unit on or below it
            assert leg["chunks_skipped"] == n * (n - 1) // 2 > 0  # none above it
            assert leg["updates"] <= leg["chunks_run"]
    # the parent's tiling, for scale: 2 x 2 tiles, three run, two masked
    assert vmem.flash_schedule(1024, 512, 512)["covered_share"] == 0.75
    # off the packed layout there is no plan: tiles are as configured
    assert schedule(1024, 3, 64) is None


@pytest.mark.parametrize("t,bq,ck,unit", [
    (1024, 1024, 1024, 256), (1024, 512, 512, 256), (1024, 512, 256, 128), (512, 256, 256, 128),
])
def test_kernel_walks_exactly_the_planned_units(monkeypatch, t, bq, ck, unit):
    """The kernels' own walker against the plan: every (row unit, column
    unit) that ``_walk_triangle`` hands to a kernel, masked or not."""
    import dtc_tpu.ops.flash_attention as fa
    from dtc_tpu.ops import vmem

    # a Python loop stands in for the kernel's fori_loop
    monkeypatch.setattr(
        jax.lax, "fori_loop",
        lambda lo, hi, body, c: [body(j, c) for j in range(lo, hi)] and c,
    )
    seen, updates = [], 0  # (row unit, column unit, masked), global indices

    def units(row0, nrows, cols, masked):
        for r in range(row0 // unit, (row0 + nrows) // unit):
            for c in range(int(cols.start) // unit, (int(cols.start) + cols.size) // unit):
                seen.append((r, c, masked))

    for i in range(t // bq):
        def below(cols, i=i):
            nonlocal updates
            updates += 1
            units(i * bq, bq, cols, False)

        def strip(rows, segs, i=i):
            nonlocal updates
            updates += 1
            for cols, mask in segs:
                units(i * bq + rows.start, rows.stop - rows.start, cols, mask is not None)

        fa._walk_triangle(None if bq == t else i, bq, ck, unit, below, strip)
    want = vmem.flash_schedule(t, bq, ck, unit)
    assert len(seen) == len({(r, c) for r, c, _ in seen}) == want["chunks_run"]
    assert sum(m for *_, m in seen) == want["chunks_masked"]
    assert updates == want["updates"]
    for r, c, masked in seen:
        assert c <= r              # never above the diagonal
        assert masked == (c == r)  # a mask on the diagonal's units alone


def test_explicit_blocks_are_still_honoured(monkeypatch):
    """Only blocks left at the config's defaults mean "the kernel
    chooses": a tiling a user sets runs as given, on the grid-walking
    kernels, and the triangle kernels are not launched."""
    import dtc_tpu.ops.flash_attention as fa

    launched = []
    real = fa.pl.pallas_call

    def spy(kernel, *a, **kw):
        launched.append((kernel.func.__name__, kw["grid"]))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    q, k, v = _qkv(jax.random.PRNGKey(11), 1, 512, 4, 64)
    ref = dense_causal_attention(q, k, v)
    got = flash_causal_attention(q, k, v, block_q=128, block_kv=256)
    assert launched == [("_fwd_kernel_packed_multi", (1, 2, 4, 2))]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    assert fa.schedule(512, 4, 64, 4, 128, 256)["fwd"] == {
        "schedule": "grid", "block_q": 128, "kv_chunk": 256, "unit": [128, 256],
        "updates": 6, "chunks_run": 6, "chunks_masked": 4, "chunks_skipped": 2,
        "covered_share": 0.75,
    }
    # a backward override alone also takes the choice away from the kernel
    assert fa.schedule(512, 4, 64, 4, block_kv_bwd=256)["fwd"]["schedule"] == "grid"

    launched.clear()
    got = flash_causal_attention(q, k, v)  # defaults: the kernel chooses
    assert launched == [("_fwd_kernel_tri", (1, 2, 1))]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_default_blocks_are_the_schema_defaults():
    """"Left at the defaults" is read off the values: the op's sentinel
    and ``ModelConfig``'s defaults must be the same numbers."""
    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.ops import vmem

    fields = ModelConfig.__dataclass_fields__
    assert fields["attention_block_q"].default == vmem.FLASH_DEFAULT_BLOCK
    assert fields["attention_block_kv"].default == vmem.FLASH_DEFAULT_BLOCK
    assert fields["attention_block_q_bwd"].default == 0
    assert fields["attention_block_kv_bwd"].default == 0


def test_flash_plan_event_fields():
    """The trainer's one start-up event: present where attention resolves
    to flash, absent where it does not."""
    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.ops.attention import flash_plan_event

    base = dict(vocab_size=128, d_model=1024, n_layers=2, n_heads=16, d_ff=2048,
                max_seq_len=1024, compute_dtype="bfloat16")
    ev = flash_plan_event(ModelConfig(**base, attention="flash"))
    assert (ev["seq_len"], ev["head_dim"], ev["heads"]) == (1024, 64, 16)
    assert ev["fwd"]["covered_share"] <= 0.625 and ev["bwd"]["covered_share"] <= 0.625
    assert flash_plan_event(ModelConfig(**base, attention="dense")) is None
    assert flash_plan_event(ModelConfig(**base, attention="auto")) is None  # CPU
    longctx = ModelConfig(**{**base, "max_seq_len": 4096}, attention="flash",
                          attention_block_kv=1024, attention_block_q_bwd=512,
                          attention_block_kv_bwd=512)
    ev = flash_plan_event(longctx)
    assert ev["fwd"]["schedule"] == ev["bwd"]["schedule"] == "grid"
    assert (ev["fwd"]["kv_chunk"], ev["bwd"]["kv_chunk"]) == (1024, 512)


def test_bf16_forward():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 256, 2, 32, jnp.bfloat16)
    ref = dense_causal_attention(q, k, v)
    got = flash_causal_attention(q, k, v, block_q=128, block_kv=128)
    assert got.dtype == jnp.bfloat16
    assert jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))) < 0.05


def test_supports_flagship():
    # The flagship (head_dim=32, T=512) must qualify — VERDICT round 1 flagged
    # the old d % 128 == 0 heuristic as unreachable for it.
    assert supports(512, 32, 512, 512)
    assert supports(512, 32, 128, 128)
    assert not supports(100, 32, 128, 128)  # T not tileable


def test_dispatch_unknown_impl():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 32, 2, 16)
    with pytest.raises(ValueError):
        causal_attention(q, k, v, impl="nope")


# ---- packed transpose-free path (single tile, heads grouped into lanes) ----

PACKED_CASES = [
    # (t, d, h): g = 128//d heads per lane group; h % g == 0 engages packing
    (256, 32, 8),
    (512, 32, 16),   # the flagship shape exactly
    (256, 64, 4),
    (256, 128, 2),   # g=1: packed degenerates to per-head lane blocks
]


@pytest.mark.parametrize("t,d,h", PACKED_CASES)
def test_packed_forward_parity(t, d, h):
    from dtc_tpu.ops.flash_attention import _packed_group

    assert _packed_group(d, h) is not None  # the case actually packs
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, t, h, d)
    got = flash_causal_attention(q, k, v, block_q=t, block_kv=t)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("t,d,h", [(256, 32, 8), (256, 64, 4)])
def test_packed_grad_parity(t, d, h):
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, t, h, d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_causal_attention(q, k, v, block_q=t, block_kv=t) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_causal_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_dense, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4,
                                   err_msg=f"d{name}")


def test_packed_group_predicate():
    """The dispatcher packs exactly when 128 % head_dim == 0 and the group
    divides the head count."""
    from dtc_tpu.ops.flash_attention import _packed_group

    assert _packed_group(32, 8) == 4
    assert _packed_group(32, 3) is None
    assert _packed_group(64, 4) == 2
    assert _packed_group(128, 2) == 1
    assert _packed_group(256, 4) is None  # head_dim wider than the lane block


def test_packed_single_matches_packed_multi():
    """Same shape through both packed kernels: block_q = t engages the
    one-pass single-tile path, block_q = t // 2 the online-softmax
    causal-block-skipping path. Outputs agree to fp32 accumulation noise."""
    q, k, v = _qkv(jax.random.PRNGKey(5), 2, 256, 8, 32)
    single = flash_causal_attention(q, k, v, block_q=256, block_kv=256)
    multi = flash_causal_attention(q, k, v, block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(single), np.asarray(multi), atol=2e-5)


@pytest.mark.parametrize("bq,bkv", [(128, 128), (256, 256), (128, 256)])
def test_packed_multi_tile_parity(bq, bkv):
    """Packed multi-tile (online softmax + causal block skip) vs dense."""
    t, d, h = 512, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(6), 2, t, h, d)
    got = flash_causal_attention(q, k, v, block_q=bq, block_kv=bkv)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_packed_multi_tile_grad_parity():
    t, d, h = 256, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, t, h, d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_causal_attention(q, k, v, block_q=128, block_kv=128) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_causal_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_dense, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4,
                                   err_msg=f"d{name}")


def test_bwd_tiling_override_is_semantically_invisible():
    """attention_block_{q,kv}_bwd retile the backward only — gradients
    must match the default tiling to fp32 accumulation noise, and the
    knob must refuse the non-packed fallback loudly (it would silently
    run the forward tiling there)."""
    t, d, h = 256, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(9), 2, t, h, d)

    def loss(bqb, bkvb):
        return jax.grad(
            lambda q, k, v: jnp.sum(flash_causal_attention(
                q, k, v, block_q=64, block_kv=128,
                block_q_bwd=bqb, block_kv_bwd=bkvb,
            ) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)

    g_default = loss(0, 0)
    g_retiled = loss(128, 256)
    for name, a, b in zip("qkv", g_default, g_retiled):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5,
                                   err_msg=f"d{name}")

    # Non-packed fallback (head_dim 48: 128 % 48 != 0) must reject the knob.
    q3, k3, v3 = _qkv(jax.random.PRNGKey(10), 1, 256, 2, 48)
    with pytest.raises(ValueError, match="packed flash path"):
        flash_causal_attention(q3, k3, v3, block_q=128, block_kv=128,
                               block_kv_bwd=256)


def test_packed_split_bwd_grad_parity(monkeypatch):
    """The long-context backward (T > _PACKED_MAX_T routes to the split
    dq/dkv kernels with O(block) scratch). Shrink the threshold so the
    split path runs at a CPU-interpretable shape, and pin it against
    dense autodiff AND the fused packed backward."""
    import dtc_tpu.ops.flash_attention as fa

    t, d, h = 256, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(8), 2, t, h, d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_causal_attention(q, k, v, block_q=64, block_kv=128) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_causal_attention(q, k, v) ** 2)

    g_fused = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "_PACKED_MAX_T", 128)  # force the split backward
    g_split = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, ref, got in zip("qkv", g_dense, g_split):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4,
                                   err_msg=f"d{name} split vs dense")
    for name, a, b in zip("qkv", g_fused, g_split):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5,
                                   err_msg=f"d{name} split vs fused")


def test_split_bwd_kernels_route_through_causal_block_dispatch(monkeypatch):
    """Round-5 VERDICT #3: the causal block skip (above-diagonal tiles
    predicated out entirely, diagonal-straddling tiles the only ones
    paying the VPU mask pass) landed via ``_causal_block_dispatch`` in
    the fused packed kernels — assert the SPLIT dq/dkv pair routes
    through the same dispatcher, so the T=8192 path gets the same 25%+
    compute skip the ceiling analysis (PERF.md round 7) credits it with.
    The spy records at kernel-trace time: a rewrite of either split
    kernel that drops the dispatcher (reverting to an always-on mask, or
    no predication at all) goes red here; the NUMERICS of the skip are
    pinned by test_packed_split_bwd_grad_parity above."""
    import dtc_tpu.ops.flash_attention as fa

    seen = []
    orig = fa._causal_block_dispatch

    def spy(i, j, block_q, block_kv, accumulate):
        seen.append(accumulate.__qualname__)
        return orig(i, j, block_q, block_kv, accumulate)

    monkeypatch.setattr(fa, "_causal_block_dispatch", spy)
    t, d, h = 256, 32, 8
    g = fa._packed_group(d, h)
    b, hd = 1, h * d
    q = jnp.zeros((b, t, hd), jnp.float32)
    do = out = q
    lse = jnp.zeros((b, hd // 128, t, g), jnp.float32)
    # Tracing the split backward traces both kernel bodies (no execution
    # needed — make_jaxpr is enough for the spy to see the call sites).
    jax.make_jaxpr(
        lambda q, k, v, do, out, lse: fa._packed_split_bwd_call(
            q, k, v, do, out, lse, 64, 128, g, d, 1.0
        )
    )(q, q, q, do, out, lse)
    owners = {name.split(".")[0] for name in seen}
    assert "_dq_kernel_packed" in owners, seen
    assert "_dkv_kernel_packed" in owners, seen


def test_whole_t_tiles_past_packed_max_t_raise(monkeypatch):
    """Guard-order regression (round-5 ADVICE): a tiling override that
    resolves to one whole-T tile past _PACKED_MAX_T must be a clear
    ValueError at the API surface — previously the single-tile fast path
    was checked FIRST, so the fused kernel's full-T VMEM scratches hit an
    opaque Mosaic compile OOM on TPU. Threshold shrunk so the guard fires
    at a CPU-testable shape."""
    import functools

    import dtc_tpu.ops.flash_attention as fa

    t, d, h = 256, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(9), 1, t, h, d)
    monkeypatch.setattr(fa, "_PACKED_MAX_T", 128)

    # Forward tiling resolves to one whole-T tile.
    with pytest.raises(ValueError, match="whole-T"):
        flash_causal_attention(q, k, v, block_q=t, block_kv=t)
    # Forward tiled fine, but the BACKWARD override is whole-T.
    with pytest.raises(ValueError, match="whole-T"):
        flash_causal_attention(q, k, v, block_q=128, block_kv=128,
                               block_q_bwd=t, block_kv_bwd=t)
    # Defense inside the vjp rule itself (direct _flash_packed callers
    # bypass the API validation): same clear error, not a kernel launch.
    g = fa._packed_group(d, h)
    pk = lambda x: x.reshape(1, t, h * d)
    lse = jnp.zeros((1, h * d // fa._LANES, t, g), jnp.float32)
    with pytest.raises(ValueError, match="whole-T"):
        fa._packed_flash_bwd(
            t, t, g, d, float(d ** -0.5), 0, 0, None, None,
            (pk(q), pk(k), pk(v), pk(q), lse), pk(q),
        )
    # Multi-tile tilings still route to the split backward and train.
    out = flash_causal_attention(q, k, v, block_q=128, block_kv=128)
    assert out.shape == q.shape


# ---- the kernel per shard on a multi-device mesh (ISSUE 23) ---------------


@pytest.mark.parametrize("shape", [(1, 4, 2), (1, 8, 1), (2, 2, 2)],
                         ids=["data4_model2", "data8", "pipe2_data2_model2"])
def test_flash_per_shard_matches_dense_on_a_mesh(shape):
    """On a mesh of several devices ``causal_attention(impl="flash")`` runs
    the kernel per (batch, heads) shard inside a fully manual shard_map
    (the TPU lowering refuses a bare Mosaic kernel under GSPMD —
    tests/test_chip_compile.py holds that line); values and gradients must
    still equal the dense reference, whichever axes carry batch and heads."""
    from flax import linen as nn

    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    mesh = build_mesh(shape)
    q, k, v = _qkv(jax.random.PRNGKey(3), 8, 256, 8, 32)

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(causal_attention(q, k, v, impl=impl, block_q=128, block_kv=128) ** 2)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        got, got_grads = loss("flash")(q, k, v)
        want, want_grads = loss("dense")(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(got_grads, want_grads):
        assert jnp.max(jnp.abs(a - b)) < 2e-4


def test_flash_per_shard_refuses_a_sharded_sequence():
    """A sequence axis split over devices is ring / Ulysses territory: the
    per-shard kernel would silently attend within each chunk only."""
    from flax import linen as nn
    from jax.sharding import PartitionSpec as P

    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    q, k, v = _qkv(jax.random.PRNGKey(4), 2, 256, 4, 32)
    with build_mesh((1, 4, 2)), nn.logical_axis_rules(DEFAULT_RULES):
        with pytest.raises(ValueError, match="whole sequence"):
            jax.jit(lambda q, k, v: causal_attention(
                q, k, v, impl="flash", spec=P("data", "model", None, None)
            ))(q, k, v)
