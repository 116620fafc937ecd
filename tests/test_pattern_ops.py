"""The operators under a layer-pattern model, each against the plain reference
or a dense form: the Gated DeltaNet scan and its fused kernels, the
held experts' loop over tiles, and grouped KV heads in the flash kernels.
Sizes and tolerances: ``tests/pattern_helpers.py``; what only the fused
kernels have (the carried state, their three variants) is in
``tests/test_pattern_scan_kernels.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.models import pattern
from dtc_tpu.ops.gated_delta import gated_delta_chunked
from tests.pattern_helpers import (  # noqa: F401  (cfg is a fixture)
    LOOSE, TIGHT, as_model, cfg, close, layer_of, normed_input, ref, weights,
)
from tests.pattern_helpers import out_and_grads as _out_and_grads, scan_inputs as _scan_inputs


# ---------------------------------------------------------------------------
# the scan: chunked against the token recurrence


@pytest.mark.parametrize("dtype,tol", [("float32", TIGHT), ("bfloat16", LOOSE)])
@pytest.mark.parametrize("chunk", [16, 32])  # 4 and 2 chunks of T = 64
def test_gdn_chunked_equals_recurrence(chunk, dtype, tol):
    args, co = _scan_inputs(2, 64, 4, 4, 16, 16, seed=chunk)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(ref.delta_rule, args, co)
    got = _out_and_grads(lambda *a: gated_delta_chunked(*a, chunk=chunk, dtype=jnp.dtype(dtype)), args, co)
    for a, b_ in zip(got, want):
        close(a, b_, tol)


@pytest.mark.parametrize("dtype,tol", [("float32", TIGHT), ("bfloat16", LOOSE)])
@pytest.mark.parametrize("key_heads", [1, 2])  # a key head serving two value heads, and one each
def test_gdn_chunk_kernels_equal_recurrence_and_xla_form(monkeypatch, key_heads, dtype, tol):
    """The fused kernels (interpret mode, at a shape the gate takes, two
    chunks so the carry counts): output and all five gradients against the token recurrence at
    ``highest`` and against the ``jax.numpy`` form from the same inputs."""
    from dtc_tpu.ops import gated_delta as gd

    args, co = _scan_inputs(1, 128, key_heads, 2, 128, 128)
    rep = lambda a: jnp.repeat(a, 2 // key_heads, axis=2)  # noqa: E731
    recurrence = lambda q, k, *a: ref.delta_rule(rep(q), rep(k), *a)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(recurrence, args, co)

    def scan():  # a new function each time: a trace is cached by the function
        return lambda *a: gated_delta_chunked(*a, chunk=64, dtype=jnp.dtype(dtype))

    assert "pallas_call" in str(jax.make_jaxpr(scan())(*args))
    got = _out_and_grads(scan(), args, co)
    monkeypatch.setattr(gd, "supports_chunk_kernel", lambda *a: None)
    assert "pallas_call" not in str(jax.make_jaxpr(scan())(*args))
    xla = _out_and_grads(scan(), args, co)
    for a, b_, c in zip(got, want, xla):
        close(a, b_, tol)
        close(a, c, tol)


def test_gdn_chunk_kernels_fast_forgetting_head_is_finite():
    """g of about -30 a token: the chunk's decay underflows to 0, and every
    ``exp`` in the kernels is still of a difference that is never positive."""
    (q, k, v, g, beta), co = _scan_inputs(1, 128, 1, 2, 128, 128, seed=1)
    g = g.at[..., 0].set(-30.0 + 0.1 * g[..., 0])
    fn = lambda *a: gated_delta_chunked(*a, chunk=64, dtype=jnp.float32)  # noqa: E731
    assert "pallas_call" in str(jax.make_jaxpr(fn)(q, k, v, g, beta))
    got = _out_and_grads(fn, (q, k, v, g, beta), co)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(ref.delta_rule, (jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v, g, beta), co)
    close(got[0], want[0], TIGHT)
    close(got[3], want[3], TIGHT)


def _aligned_keys(shape, seed=5):
    """Unit keys that share a direction: (k_i . k_j) ~ 0.8 for every pair."""
    noise = jax.random.normal(jax.random.PRNGKey(seed), shape)
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), shape[-1:]) + 0.5 * noise
    return k / jnp.linalg.norm(k, axis=-1, keepdims=True)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("c", [12, 64])
def test_unit_lower_inverse_where_keys_align(c, dtype, tol):
    """A chunk whose keys align and hardly decay: rows of ``|A|`` sum to 8
    (c 12) and 46 (c 64) while no entry of the inverse passes 1. The blocked
    inverse rounds nothing larger than the answer; the squaring product it
    replaced read 2e11 off here in bfloat16 (3e6 in float32), and the
    benchmark's cell went NaN on such chunks."""
    from dtc_tpu.ops.gated_delta import unit_lower_inverse

    k = _aligned_keys((c, 128))
    a = jnp.tril(0.9 * (k @ k.T), -1)
    want = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
    assert np.abs(a).sum(-1).max() > 0.6 * c and np.abs(want).max() <= 1.0
    got = np.asarray(unit_lower_inverse(a, jnp.dtype(dtype)), np.float64)
    assert np.max(np.abs(got - want)) <= tol


@pytest.mark.parametrize("form", ["mosaic", "xla"])
def test_gdn_aligned_keys_slow_decay_stay_with_the_recurrence(monkeypatch, form):
    """The same chunks through the whole scan in bfloat16, kernel pair and
    ``jax.numpy`` form: output and gradients finite and with the token
    recurrence."""
    from dtc_tpu.ops import gated_delta as gd

    (q, _, v, g, beta), co = _scan_inputs(1, 128, 1, 2, 128, 128, seed=2)
    args = (q, _aligned_keys((1, 128, 1, 128)), v, 0.01 * g, 0.5 + 0.5 * beta)
    if form == "xla":
        monkeypatch.setattr(gd, "supports_chunk_kernel", lambda *a: None)
    fn = lambda *a: gated_delta_chunked(*a, chunk=64, dtype=jnp.bfloat16)  # noqa: E731
    assert ("pallas_call" in str(jax.make_jaxpr(fn)(*args))) == (form == "mosaic")
    got = _out_and_grads(fn, args, co)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(lambda q, k, *a: ref.delta_rule(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), *a), args, co)
    for a, b_ in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        close(a, b_, LOOSE)


@pytest.mark.parametrize("chunk,dk,dv,hv,hk,takes", [
    (64, 128, 128, 32, 16, True),    # the benchmark's cell
    (64, 128, 128, 2, 1, True),
    (64, 16, 16, 4, 2, False),       # toy widths: not a lane tile
    (64, 128, 64, 4, 2, False),
    (12, 128, 128, 4, 2, False),     # a chunk off the sublane count
    (64, 128, 128, 12, 8, False),    # key heads that do not divide the value heads
    (64, 128, 128, 100, 2, False),   # no 8 heads a step, and all 100 are over the budget
    (64, 256, 128, 8, 4, True),      # a (256, 128) state a head: 1 MiB of scratch
    (64, 128, 128, 24, 8, True),     # key heads serve three: no eight are whole key heads, so all 24 a step
    (64, 512, 512, 8, 8, False),     # 8 MiB of state a step, as scratch and as a block: over the budget
])
def test_gdn_chunk_kernel_gate(chunk, dk, dv, hv, hk, takes):
    """The gate asks the planner; where it refuses, the ``jax.numpy`` form
    runs and gives the recurrence's values."""
    from dtc_tpu.ops import vmem
    from dtc_tpu.ops.gated_delta import supports_chunk_kernel

    plan = supports_chunk_kernel(chunk, dk, dv, hv, hk)
    assert (plan is not None) == takes
    if takes:
        assert plan == vmem.gdn_chunk_plan(chunk, dk, dv, hv, hk) and hv % plan["tiles"] == 0
        # whole key heads, and along the sublanes of the output's block a multiple of 8 or every head
        assert plan["tiles"] % (hv // hk) == 0 and (plan["tiles"] % 8 == 0 or plan["tiles"] == hv)
        state = plan["tiles"] * dk * dv * 4       # float32, carried in scratch across a row's chunks
        for leg in ("fwd", "bwd"):
            # the scratch once, the saved-state block double-buffered
            assert plan[leg]["scratch_bytes"] == state and 3 * state < plan[leg]["bytes"] <= vmem.VMEM_BUDGET_BYTES
            assert plan[leg]["vmem_limit_bytes"] > plan[leg]["bytes"] + plan[leg]["modeled_transient_bytes"]
    elif hv <= 4:
        args, _ = _scan_inputs(1, 2 * chunk, hk, hv, dk, dv)
        fn = lambda *a: gated_delta_chunked(*a, chunk=chunk, dtype=jnp.float32)  # noqa: E731
        assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
        with jax.default_matmul_precision("highest"):
            want = ref.delta_rule(*(jnp.repeat(a, hv // hk, 2) for a in args[:2]), *args[2:])
        close(fn(*args), want, TIGHT)


# ---------------------------------------------------------------------------
# the held experts: no drops, the loop over tiles
def test_no_assignment_is_dropped_when_one_expert_takes_every_token(cfg):
    """Planted router weights send every token's first choice to expert 1:
    its load is the token count, nothing is dropped, and the layer still
    equals the reference."""
    w = weights(cfg)
    tree, flat = layer_of(w, 0)
    x = jnp.abs(normed_input(cfg)) + 0.1                  # positive, so a positive column wins
    router = np.array(flat["moe.router.w"])
    router[:, 1] = 1.0
    flat = {**flat, "moe.router.w": jnp.asarray(router)}
    p = {**tree["moe"], "router": {"kernel": flat["moe.router.w"]}}
    y, mut = pattern.FFNS["moe_shared"][0](cfg).apply({"params": p}, x, mutable=["counters"])
    assigned, load_max, load_mean, dropped, flushes = np.asarray(mut["counters"]["moe"][0])
    tokens = x.shape[0] * x.shape[1]
    # 1.25 x the 256 of even routing, in tiles, and a tile: one flush holds them
    assert load_max == tokens and dropped == 0 and flushes == 1
    assert tokens <= assigned <= 2 * tokens and load_mean == assigned / 4
    with jax.default_matmul_precision("highest"):
        close(y, ref.moe_layer(flat, x, as_model(cfg)), TIGHT)


def _flushes(loads, tile, staged):
    """How many groups the loop makes of these experts' loads: a group ends
    where the next tile's rows, at their place in the sorted list, would end
    past the staging."""
    starts = [lo + i * tile for lo, n in zip(np.cumsum([0, *loads[:-1]]), loads)
              for i in range(-(-int(n) // tile))]
    groups, base = 0, None
    for start in starts:
        if base is None or start + tile - base > staged:
            groups, base = groups + 1, start
    return groups


# staging with room for the whole layer; of two tiles (the dropless overflow
# path: many flushes); the program's own sizing at one or two tiles an expert
@pytest.mark.parametrize("tile,staged", [(8, 1024), (8, 16), (48, None)])
def test_expert_tiles_loop_equals_reference(cfg, monkeypatch, tile, staged):
    """The held assignments run a tile of one expert's rows at a time, as
    many tiles as the routing fills, their rows staged packed and scattered
    whenever the staging is full: with tiles far smaller than an expert's
    load, and a staging far smaller than the layer, the layer and its
    gradients still equal the reference's loop over experts, nothing is
    dropped, and the counter says how many scatters it took."""
    from dtc_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "HELD_TILE_ROWS", tile)
    if staged is None:
        staged = moe_dispatch.held_staging_rows(2 * 128 * 2, 4, 8, tile)
        assert staged == 384  # 1.25 x 256 in tiles of 48, and a tile
    else:
        monkeypatch.setattr(moe_dispatch, "held_staging_rows", lambda *a: staged)
    w = weights(cfg, seed=11)
    tree, flat = layer_of(w, 0)
    x = normed_input(cfg, seed=4)
    co = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    module = pattern.FFNS["moe_shared"][0](cfg)
    program = lambda p, x: jnp.sum(module.apply({"params": p}, x) * co)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: jnp.sum(ref.moe_layer(p, x, as_model(cfg)) * co),
                        argnums=(0, 1))(flat, x)
        y, mut = module.apply({"params": tree["moe"]}, x, mutable=["counters"])
        close(y, ref.moe_layer(flat, x, as_model(cfg)), TIGHT)
    assigned, load_max, _, dropped, flushes = np.asarray(mut["counters"]["moe"][0])
    _, idx = moe_dispatch.top_k_gates(
        jax.nn.softmax(x.reshape(-1, x.shape[-1]) @ flat["moe.router.w"], axis=-1), cfg.moe_top_k)
    loads = np.bincount(np.asarray(idx).reshape(-1), minlength=8)[:4]
    assert (assigned, load_max, dropped) == (loads.sum(), loads.max(), 0)
    assert flushes == _flushes(loads, tile, staged)
    assert flushes == 1 if staged > 16 else flushes > assigned / 16
    got_p, got_x = jax.grad(program, argnums=(0, 1))(tree["moe"], x)
    close(got_x, want[1], TIGHT)
    for leaf, name in (("w_gate", "gate"), ("w_up", "up"), ("w_down", "down")):
        close(got_p[leaf], want[0][f"moe.{name}.w"], TIGHT)
    close(got_p["router"]["kernel"], want[0]["moe.router.w"], TIGHT)


# every tile in one group; a group's end right after a part-filled tile
@pytest.mark.parametrize("stale", ["as allocated", "nan"])
@pytest.mark.parametrize("staged,flushes", [(32, 1), (16, 3)])
def test_packed_staging_overwrites_a_part_filled_tile_s_tail(monkeypatch, staged, flushes, stale):
    """Planted choices: expert 0 holds 11 rows (a full tile of 8 and one of
    3), expert 1 holds 5, expert 2 holds 9; expert 3 is held elsewhere. Packed, the
    second tile's five unfilled rows lie where expert 1's tile then writes,
    and so on: output, counters and all five gradients against a dense form.
    ``nan``: the staging is never cleared, so whatever it holds where no tile
    has written (on the chip: what the allocation held) must reach no result."""
    from dtc_tpu.ops import moe_dispatch as md

    monkeypatch.setattr(md, "HELD_TILE_ROWS", 8)
    if stale == "nan":
        monkeypatch.setattr(md, "_staging", lambda *shape: jnp.full(shape, jnp.nan, jnp.float32))
    monkeypatch.setattr(md, "held_staging_rows", lambda *a: staged)
    n, d, f, held = 16, 8, 4, 3
    first = np.repeat([0, 1, 2, 3], [11, 5, 0, 0])          # every token's first choice
    second = np.repeat([2, 3], [9, 7])                       # and its second, another expert
    idx = jnp.asarray(np.stack([first, second], 1), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (n, d))
    gates = jax.nn.softmax(jax.random.normal(ks[1], (n, 2)), axis=-1)
    wg, wu = (jax.random.normal(key, (held, d, f)) / np.sqrt(d) for key in ks[2:4])
    wd = jax.random.normal(ks[4], (held, f, d)) / np.sqrt(f)
    co = jax.random.normal(ks[5], (n, d))

    def dense(x, gates, wg, wu, wd):
        parts = [(jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
                 * jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1, keepdims=True) for e in range(held)]
        return sum(parts)

    def program(*a):
        return md.held_experts(*a[:2], idx, *a[2:], first=0, published=4)

    args = (x, gates, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        y, counters = program(*args)
        close(y, dense(*args), TIGHT)
        np.testing.assert_allclose(np.asarray(counters), [25, 11, 25 / 3, 0, flushes], rtol=1e-6)
        got = jax.grad(lambda *a: jnp.sum(program(*a)[0] * co), argnums=range(5))(*args)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * co), argnums=range(5))(*args)
    for a, b_ in zip(got, want):
        close(a, b_, TIGHT)


@pytest.mark.parametrize("loads", [(5, 0, 17, 8), (0, 0, 0, 0), (0, 40, 0, 1), (16, 16, 16, 16)])
def test_tiles_cover_each_assignment_once_and_nothing_else(loads):
    """The loop's plan: every expert's span of the sorted list is cut into
    tiles of 8, an empty expert gets none, and no tile runs past the count."""
    from dtc_tpu.ops.moe_dispatch import _plan_tiles

    ends = jnp.cumsum(jnp.asarray(loads, jnp.int32))
    tiles = jax.device_get(_plan_tiles(ends, 64, 8))
    assert tiles.count == sum(-(-n // 8) for n in loads)
    seen = []
    for t in range(int(tiles.count)):
        lo, hi = int(tiles.start[t]), min(int(tiles.start[t]) + 8, int(tiles.stop[t]))
        assert lo < hi and int(ends[tiles.expert[t]]) - loads[int(tiles.expert[t])] <= lo
        seen += range(lo, hi)
    assert seen == list(range(sum(loads)))


def test_a_layer_the_routers_have_left_runs_no_tile(cfg):
    """Planted router weights send both choices of every token to experts
    this process does not hold: nothing is assigned here, the loop runs no
    tile, and the layer is the shared expert's part alone."""
    w = weights(cfg)
    tree, flat = layer_of(w, 0)
    x = jnp.abs(normed_input(cfg)) + 0.1
    router = np.array(flat["moe.router.w"])
    router[:, 4:6] = 1.0                                  # held here: experts 0-3
    flat = {**flat, "moe.router.w": jnp.asarray(router)}
    p = {**tree["moe"], "router": {"kernel": flat["moe.router.w"]}}
    y, mut = pattern.FFNS["moe_shared"][0](cfg).apply({"params": p}, x, mutable=["counters"])
    assert np.asarray(mut["counters"]["moe"][0]).tolist() == [0, 0, 0, 0, 0]
    with jax.default_matmul_precision("highest"):
        close(y, ref.moe_layer(flat, x, as_model(cfg)), TIGHT)


# ---------------------------------------------------------------------------
# grouped KV heads in the flash kernels (interpret mode)


# the last: LFM2's groups, 4 query heads a KV head at head size 64 (the packed
# kernels know no groups, so the transposed layout runs)
@pytest.mark.parametrize("shape", [(1, 256, 4, 1, 256, 128), (2, 256, 4, 2, 32, 128),
                                   (1, 256, 8, 2, 64, 128)])
def test_flash_grouped_kv_heads_equal_dense(shape):
    from dtc_tpu.ops.attention import dense_causal_attention
    from dtc_tpu.ops.flash_attention import flash_causal_attention

    b, t, h, hk, d, block = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, t, h, d))
    k, v = (jax.random.normal(key, (b, t, hk, d)) for key in ks[1:3])
    co = jax.random.normal(ks[3], q.shape)
    flash = lambda *a: jnp.sum(flash_causal_attention(*a, block_q=block, block_kv=block) * co)  # noqa: E731
    dense = lambda *a: jnp.sum(dense_causal_attention(*a) * co)  # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), rtol=1e-4)
    for got, want in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v),
                         jax.grad(dense, argnums=(0, 1, 2))(q, k, v)):
        close(got, want, TIGHT)
