"""Layer-pattern models (``models/pattern.py``) against the plain reference.

Everything is compared with ``benchmark/reference_qwen3_next.py`` (float32
``jax.numpy``, Gated DeltaNet as the token recurrence, dense attention,
experts as a loop), loaded by path — there is no second copy — on weights
from its own ``make_weights``, at a toy size: d 64, 2 key / 4 value heads
of 16, 2 query heads on 1 KV head of 32 with 8 rotary dims, 8 experts
top-2 of width 32, vocabulary 256.

Tolerances. float32 ``tight``: 2e-4 of the largest element — the two sides
differ in summation order only (chunked matmuls against a recurrence, a
sorted buffer against a loop; measured 1e-6 to 3e-5). bfloat16 ``loose``:
4e-2 of the largest element, an 8-bit mantissa through a few matmuls
(measured up to 1.5e-2); the routed sum is compared in float32 only, since
a top-k choice that flips on a bf16 near-tie moves a whole expert's output.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtc_tpu.config.loader import load_config, load_yaml_dataclass
from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models import pattern
from dtc_tpu.ops.gated_delta import gated_delta_chunked
from tests.conftest import make_train_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY_YAML = os.path.join(REPO, "configs", "model_config_pattern_dev.yaml")
TIGHT, LOOSE = 2e-4, 4e-2


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(REPO, "benchmark", "reference_qwen3_next.py"), "reference_qwen3_next")
with open(os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as f:
    LEAF_NAMES = json.load(f)["leaf_names"]


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return load_yaml_dataclass(TOY_YAML, ModelConfig)


def as_model(cfg: ModelConfig) -> dict:
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in dataclasses.asdict(cfg).items()}


def weights(cfg: ModelConfig, seed: int = 3) -> dict:
    with jax.default_matmul_precision("highest"):
        return ref.make_weights(as_model(cfg), jnp.asarray(ref.seed_words(seed)))


def program_params(w: dict) -> dict:
    """The reference's leaves laid out as the program's parameter tree."""
    tree: dict = {}
    for path, name in LEAF_NAMES.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = w[name]
    return tree


def layer_of(w: dict, position: int) -> tuple[dict, dict]:
    """(program subtree, reference dict) of one layer, periods axis taken off."""
    tree = program_params(w)["stage"]["periods"][f"layer_{position}"]
    return (jax.tree.map(lambda a: a[0], tree),
            {k: v[0] for k, v in ref.layer_params(w, position).items()})


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), (
        np.max(np.abs(got - want)) / np.max(np.abs(want)))


def normed_input(cfg, seed=0, rows=2):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.max_seq_len, cfg.d_model))
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True))


# ---------------------------------------------------------------------------
# the scan: chunked against the token recurrence


def _scan_inputs(b, t, hk, h, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, t, h, dv))


def _out_and_grads(fn, args, co):
    return (fn(*args), *jax.grad(lambda *a: jnp.sum(fn(*a) * co), argnums=range(5))(*args))


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
    """The chunk-local kernel pair (interpret mode, at a shape the gate
    takes): output and all five gradients against the token recurrence at
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
        for leg in ("fwd", "bwd"):
            assert plan[leg]["bytes"] <= vmem.VMEM_BUDGET_BYTES
            assert plan[leg]["vmem_limit_bytes"] > plan[leg]["bytes"] + plan[leg]["modeled_transient_bytes"]
    elif hv <= 4:
        args, _ = _scan_inputs(1, 2 * chunk, hk, hv, dk, dv)
        fn = lambda *a: gated_delta_chunked(*a, chunk=chunk, dtype=jnp.float32)  # noqa: E731
        assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
        with jax.default_matmul_precision("highest"):
            want = ref.delta_rule(*(jnp.repeat(a, hv // hk, 2) for a in args[:2]), *args[2:])
        close(fn(*args), want, TIGHT)


def test_layer_plan_names_the_chunk_local_implementation(cfg):
    """``layer_plan``'s ``gdn`` entry: the kernel pair with its tile and
    ``vmem_limit_bytes`` at the benchmark cell's widths, the ``jax.numpy``
    form at the toy's."""
    with open(os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as f:
        cell = ModelConfig(**json.load(f)["model"])
    gdn = pattern.layer_plan(cell)["gdn"]
    assert gdn["kernel"] == "mosaic" and gdn["tile"] == [8, cell.gdn_chunk]
    assert set(gdn["vmem_limit_bytes"]) == {"fwd", "bwd"}
    assert all(16 * 2**20 <= v < 32 * 2**20 for v in gdn["vmem_limit_bytes"].values())
    toy = pattern.layer_plan(cfg)["gdn"]
    assert toy["kernel"] == "xla" and "tile" not in toy and "vmem_limit_bytes" not in toy


# ---------------------------------------------------------------------------
# each layer kind, forward and gradients


def _program_layer(kind, cfg):
    return {"gdn": pattern.GatedDeltaNet, "gated_attn": pattern.GatedAttention,
            "moe_shared": pattern.SharedExpertMoE}[kind](cfg)


_REF_LAYER = {"gdn": ("gdn", 0, ref.gdn_layer), "gated_attn": ("attn_full", 3, ref.attn_layer),
              "moe_shared": ("moe", 0, ref.moe_layer)}


@pytest.mark.parametrize("kind,dtype,tol", [
    ("gdn", "float32", TIGHT), ("gdn", "bfloat16", LOOSE),
    ("gated_attn", "float32", TIGHT), ("gated_attn", "bfloat16", LOOSE),
    ("moe_shared", "float32", TIGHT),
])
def test_layer_kind_matches_reference(cfg, kind, dtype, tol):
    cfg = dataclasses.replace(cfg, compute_dtype=dtype)
    scope, position, ref_fn = _REF_LAYER[kind]
    w = weights(cfg)
    tree, flat = layer_of(w, position)
    x = normed_input(cfg)
    co = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    module = _program_layer(kind, cfg)

    def program(p, x):
        return module.apply({"params": p}, x.astype(jnp.dtype(dtype))).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = ref_fn(flat, x, as_model(cfg))
        want_gp, want_gx = jax.grad(
            lambda p, x: jnp.sum(ref_fn(p, x, as_model(cfg)) * co), argnums=(0, 1))(flat, x)
    close(program(tree[scope], x), want, tol)
    got_gp, got_gx = jax.grad(lambda p, x: jnp.sum(program(p, x) * co), argnums=(0, 1))(tree[scope], x)
    close(got_gx, want_gx, tol)
    got_flat = {LEAF_NAMES[f"stage/periods/layer_{position}/{scope}/" + "/".join(
        str(getattr(k, "key", k)) for k in path)].split(".", 2)[2]: leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(got_gp)}
    for name, g in got_flat.items():
        close(g, want_gp[name], tol * (3 if dtype == "bfloat16" else 1))


# ---------------------------------------------------------------------------
# the share of the experts, and no drops


def test_shares_add_up_to_the_uncut_layer(cfg):
    """The routed parts of both shares (2 x 4 experts) plus the shared
    expert once equal the reference layer that holds all 8."""
    whole = dataclasses.replace(cfg, moe_experts_held=0)
    w = weights(whole)
    _, flat = layer_of(w, 0)
    x = normed_input(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.moe_layer(flat, x, as_model(whole))
    total = jnp.zeros_like(want)
    for rank in range(2):
        share = dataclasses.replace(cfg, moe_experts_held=4, moe_expert_rank=rank)
        tree, _ = layer_of(w, 0)
        p = dict(tree["moe"])
        for leaf in ("w_gate", "w_up", "w_down"):
            p[leaf] = p[leaf][rank * 4: rank * 4 + 4]
        y = pattern.SharedExpertMoE(share).apply({"params": p}, x)
        if rank:  # what every chip computes alike counts once
            zero_gate = {**flat, "moe.shared_gate.w": flat["moe.shared_gate.w"]}
            with jax.default_matmul_precision("highest"):
                shared = (ref.moe_layer(zero_gate, x, as_model(whole))
                          - ref.moe_layer(zero_gate, x, as_model(whole), shared=False))
            y = y - shared
        total = total + y
    close(total, want, TIGHT)


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
    y, mut = pattern.SharedExpertMoE(cfg).apply({"params": p}, x, mutable=["counters"])
    assigned, load_max, load_mean, dropped = np.asarray(mut["counters"]["moe"][0])
    tokens = x.shape[0] * x.shape[1]
    assert load_max == tokens and dropped == 0
    assert tokens <= assigned <= 2 * tokens and load_mean == assigned / 4
    with jax.default_matmul_precision("highest"):
        close(y, ref.moe_layer(flat, x, as_model(cfg)), TIGHT)


# several tiles an expert in two groups; in many groups; one tile an expert, part filled
@pytest.mark.parametrize("tile,group", [(8, 16), (8, 3), (48, 16)])
def test_expert_tiles_loop_equals_reference(cfg, monkeypatch, tile, group):
    """The held assignments run a tile of one expert's rows at a time, as
    many tiles as the routing fills, their rows scattered a group of tiles
    at a time: with tiles far smaller than an expert's load, and a last
    group part filled, the layer and its gradients still equal the
    reference's loop over experts."""
    from dtc_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "HELD_TILE_ROWS", tile)
    monkeypatch.setattr(moe_dispatch, "HELD_GROUP_TILES", group)
    w = weights(cfg, seed=11)
    tree, flat = layer_of(w, 0)
    x = normed_input(cfg, seed=4)
    co = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    module = pattern.SharedExpertMoE(cfg)
    program = lambda p, x: jnp.sum(module.apply({"params": p}, x) * co)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: jnp.sum(ref.moe_layer(p, x, as_model(cfg)) * co),
                        argnums=(0, 1))(flat, x)
        close(module.apply({"params": tree["moe"]}, x), ref.moe_layer(flat, x, as_model(cfg)), TIGHT)
    got_p, got_x = jax.grad(program, argnums=(0, 1))(tree["moe"], x)
    close(got_x, want[1], TIGHT)
    for leaf, name in (("w_gate", "gate"), ("w_up", "up"), ("w_down", "down")):
        close(got_p[leaf], want[0][f"moe.{name}.w"], TIGHT)
    close(got_p["router"]["kernel"], want[0]["moe.router.w"], TIGHT)


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
    y, mut = pattern.SharedExpertMoE(cfg).apply({"params": p}, x, mutable=["counters"])
    assert np.asarray(mut["counters"]["moe"][0]).tolist() == [0, 0, 0, 0]
    with jax.default_matmul_precision("highest"):
        close(y, ref.moe_layer(flat, x, as_model(cfg)), TIGHT)


# ---------------------------------------------------------------------------
# the whole model through the program's step


def _one_device_steps(cfg, opt_cfg, batches, w=None):
    """The program's own state and compiled step on a mesh of one device,
    over ``batches`` ((rows, T + 1) arrays); with ``w`` the reference's
    weights replace the program's draw. Returns the last step's outputs
    and every loss."""
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES
    from dtc_tpu.train.train_step import Batch, create_train_step
    from dtc_tpu.train.trainer import init_state
    from flax import linen as nn

    mesh = build_mesh((1, 1, 1), devices=jax.devices()[:1])
    model = pattern.build_model(cfg)
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        state = init_state(model, cfg, make_train_cfg("dp", batch=batches[0].shape[0]), opt_cfg, mesh)
        if w is not None:
            state = state.replace(params=jax.tree.map(
                lambda a, b: jnp.asarray(b, a.dtype), state.params, program_params(w)))
        step = create_train_step(mesh, model=model, state=state)
        losses = []
        for batch in batches:
            batch = jnp.asarray(batch)
            state, loss, counters = step(state, Batch(x=batch[:, :-1], y=batch[:, 1:]), jax.random.PRNGKey(0))
            losses.append(float(loss))
    return state, losses, counters


def test_whole_model_loss_and_clipped_gradients(cfg, opt_cfg):
    """One step through ``create_train_step``: the loss, and the clipped
    gradient read back from AdamW's first moment (mu / (1 - b1)), as the
    benchmark's comparison reads it."""
    w = weights(cfg, seed=5)
    batch = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, cfg.max_seq_len + 1), dtype=np.int32)
    state, (loss,), counters = _one_device_steps(cfg, opt_cfg, [batch], w)
    assert counters.shape == (4, 4) and float(counters[:, 3].sum()) == 0.0
    optim = {"lr": opt_cfg.lr, "weight_decay": opt_cfg.weight_decay, "grad_clip": opt_cfg.grad_clip}
    out = ref.run_steps(as_model(cfg), optim, 5, [batch])
    assert abs(float(loss) - out["losses"][0]) <= 1e-4 * out["losses"][0]
    mu = state.opt_state[1][0].mu if hasattr(state.opt_state[1][0], "mu") else None
    if mu is None:
        mu = next(s.mu for s in jax.tree.leaves(state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                  if hasattr(s, "mu"))
    flat = {LEAF_NAMES["/".join(str(getattr(k, "key", k)) for k in path)]: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(mu)}
    got = jax.device_get(ref.leaf_norms(flat))
    for name, want in out["grad1"].items():
        np.testing.assert_allclose(np.asarray(got[name]) / (1 - ref.B1), want, rtol=2e-3, atol=1e-7,
                                   err_msg=name)


def test_trainer_runs_three_steps_from_yaml_files(tmp_path, cfg):
    """``main.py``'s path: YAML files through ``load_config`` into
    ``trainer.train``; the events hold the two plans and the counters."""
    import yaml

    from dtc_tpu.train.trainer import train

    with open(os.path.join(REPO, "configs", "train_config_dp.yaml")) as f:
        train_yaml = yaml.safe_load(f)
    train_yaml.update(output_dir=str(tmp_path / "run"), steps=3, log_every=3, batch=8,
                      dataset="synthetic", warmup_steps=1, overwrite=True)
    path = tmp_path / "train.yaml"
    path.write_text(yaml.safe_dump(train_yaml))
    train_cfg, model_cfg, opt_cfg = load_config(
        str(path), TOY_YAML, os.path.join(REPO, "configs", "optim_config.yaml"))
    result = train(train_cfg, model_cfg, opt_cfg)
    assert len(result.losses) == 3 and all(np.isfinite(result.losses))
    with open(tmp_path / "run" / "obs" / "events.r0.jsonl") as f:
        events = [json.loads(line) for line in f if line.strip()]
    by_type = {e["etype"]: e for e in events}
    assert by_type["layer_plan"]["pattern"] == list(cfg.layer_pattern)
    assert by_type["layer_plan"]["gdn"]["chunks"] == 2 and by_type["layer_plan"]["gdn"]["chunk"] == 64
    assert by_type["layer_plan"]["gdn"]["kernel"] == "xla"  # key / value width 16: no lane tile
    assert by_type["moe_plan"]["experts_held"] == 4 and by_type["moe_plan"]["experts_published"] == 8
    counted = [e for e in events if e["etype"] == "moe_counters"]
    assert [e["step"] for e in counted] == [1, 2, 3]
    assert all(e["moe_dropped"] == 0 and len(e["moe_assigned_held"]) == 4 for e in counted)
    assert not [e for e in events if e["etype"] == "recompile"]


@pytest.mark.parametrize("parallel", ["dp", "fsdp"])
def test_eight_devices_equal_one(cfg, opt_cfg, parallel):
    """The trainer on the virtual 8-device mesh (each device routes its own
    row's tokens into its own buffer) against the same state and step on
    one device, fed the same rows."""
    from dtc_tpu.train.trainer import train

    rng = np.random.default_rng(1)
    batches = [rng.integers(0, cfg.vocab_size, (8, cfg.max_seq_len + 1), dtype=np.int32)
               for _ in range(3)]
    _, one, _ = _one_device_steps(cfg, opt_cfg, batches)
    many = train(make_train_cfg(parallel, steps=3, log_every=3), cfg, opt_cfg,
                 host_iterator=iter(batches))
    np.testing.assert_allclose(many.losses, one, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# what a pattern model refuses


def test_generate_refuses(cfg):
    from dtc_tpu.generate import generate

    with pytest.raises(NotImplementedError, match="no cache for recurrent state"):
        generate(pattern.build_model(cfg), {}, jnp.zeros((1, 4), jnp.int32), 4)


def test_serving_engine_refuses(cfg):
    from dtc_tpu.serve.engine import ServingEngine

    with pytest.raises(NotImplementedError, match="no cache for recurrent state"):
        ServingEngine(pattern.build_model(cfg), {}, None)


@pytest.mark.parametrize("shape", [(2, 1, 1), (1, 1, 2)])  # pipe > 1; an axis over experts
def test_pipeline_and_expert_axes_refuse(cfg, shape):
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.train.train_step import create_train_step

    mesh = build_mesh(shape, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dp and fsdp only"):
        create_train_step(mesh, model=pattern.build_model(cfg))


@pytest.mark.parametrize("change,message", [
    ({"layer_pattern": ("gdn+mlp",)}, "layer_pattern entry"),
    ({"n_layers": 6}, "whole number of periods"),
    ({"moe_experts_held": 3}, "must divide"),
    ({"max_seq_len": 72}, "multiple of the scan's chunk"),
])
def test_config_rules(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(cfg, **change)


def test_the_schema_s_kinds_are_the_modules_kinds():
    """The schema lists the kinds' names (it knows no module); the model
    file maps each of them, and nothing else, to a module."""
    from dtc_tpu.config.schema import PATTERN_FFNS, PATTERN_MIXERS

    assert set(pattern.MIXERS) == set(PATTERN_MIXERS) and set(pattern.FFNS) == set(PATTERN_FFNS)


# ---------------------------------------------------------------------------
# the counts


def _published_cfg() -> tuple[ModelConfig, dict]:
    with open(os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as f:
        model = json.load(f)["model"]
    return ModelConfig(**model), model


def test_parameter_count_is_the_issue_s_sum():
    from dtc_tpu.models.gpt import param_count

    cfg, model = _published_cfg()
    padded = 2 * (cfg.padded_vocab_size - cfg.vocab_size) * cfg.d_model
    assert param_count(cfg) - padded == 625_667_136
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(model).values()) == param_count(cfg)


def test_the_two_operation_counts_are_equal():
    from dtc_tpu.utils.metrics import gdn_scan_flops, pattern_step_flops

    flops = _load(os.path.join(REPO, "benchmark", "flops_qwen3_next.py"), "flops_qwen3_next")
    cfg, model = _published_cfg()
    for rows, seq in ((2, 8192), (8, 1024)):
        assert flops.train_step_flops(model, rows, seq) == pattern_step_flops(cfg, rows, seq)
    assert flops.gdn_step_flops(model, 2, 8192) == 3 * gdn_scan_flops(cfg, 2 * 8192)
    assert 22.5e12 < pattern_step_flops(cfg, 2, 8192) < 22.9e12


@pytest.mark.parametrize("held,steps", [([10240.0] * 4, None), ([9193.0, 9056.0, 22440.0, 3.0], None),
                                        ([0.0] * 4, [2])])
def test_the_benchmark_counts_the_assignments_a_run_computed(held, steps):
    """``mfu.train.qwen3-next`` and ``moe_experts_roofline.train`` take the
    experts' work from the run's ``moe_counters`` events: the expected count
    is what even routing gives, and a layer the routers left adds nothing."""
    flops = _load(os.path.join(REPO, "benchmark", "flops_qwen3_next.py"), "flops_qwen3_next")
    _, model = _published_cfg()
    run = {"events": [{"etype": "moe_counters", "step": 1, "moe_assigned_held": [1.0] * 4},
                      {"etype": "moe_counters", "step": 2, "moe_assigned_held": held},
                      {"etype": "step", "step": 2}]}
    counted = flops.counted_assignments(run, steps or [2, 3])
    assert counted == sum(held)
    assert flops.counted_assignments({"events": []}) is None
    per = 3 * 2.0 * 2048 * 512
    assert flops.moe_experts_step_flops(model, 2, 8192, counted) == 3 * per * sum(held)
    expected = flops.moe_experts_step_flops(model, 2, 8192)
    assert expected == 3 * per * 4 * 10240
    step = flops.train_step_flops(model, 2, 8192, counted)
    np.testing.assert_allclose(step - flops.train_step_flops(model, 2, 8192),
                               3 * per * (sum(held) - 4 * 10240), rtol=1e-9, atol=1e6)
    if sum(held) == 4 * 10240:
        assert step == flops.train_step_flops(model, 2, 8192)


def test_sharding_table_covers_every_leaf(cfg):
    from dtc_tpu.parallel.sharding import FSDP_RULES, param_specs

    w = weights(cfg)
    specs = param_specs(program_params(w), FSDP_RULES)
    layer = specs["stage"]["periods"]["layer_0"]
    assert tuple(layer["moe"]["w_gate"]) == (None, "model", "data", None)
    assert tuple(layer["gdn"]["in_proj_qkvz"]["kernel"]) == (None, "data", "model")


# ---------------------------------------------------------------------------
# grouped KV heads in the flash kernels (interpret mode)


@pytest.mark.parametrize("shape", [(1, 256, 4, 1, 256, 128), (2, 256, 4, 2, 32, 128)])
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
