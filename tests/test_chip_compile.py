"""Every Pallas kernel of the main path, compiled for a DESCRIBED v5e.

The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached (``topologies.get_topology_desc``). Interpret
mode cannot refuse a block shape Mosaic rejects or a working set the
chip's fast memory cannot hold; this compile can, at no chip time. It
held the decode megakernel's first two refusals (ISSUE 23: per-layer
``(1, d)`` blocks of an ``(L, d)`` stack; a 24 MiB scoped allocation
against the 16 MiB default) and keeps every later PR from reintroducing
one. Nothing runs, so nothing here says a result or a time.

Rules this file lives by (on-chip-measurement guide, section 2):

- the topology is described inside a module-scoped, non-autouse fixture,
  never while a module is imported — only the xdist worker that is given
  this file loads the TPU library;
- everything stays in this ONE file and in the test's own process;
- the program's off-TPU predicates (``_interpret``, ``_on_tpu``) are
  steered here with monkeypatch, not through an option of the program;
- the persistent compile cache is off around these compiles (an entry
  written for a described chip cannot be read back without one).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from dtc_tpu.config.loader import load_config
from dtc_tpu.models.gpt import GPT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip, loudly
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The fsdp leg's mesh (pipe, data, model) = (1, 4, 1) over the four
    described chips."""
    return Mesh(np.array(topo.devices).reshape(1, 4, 1), ("pipe", "data", "model"))


@pytest.fixture(scope="module")
def shipped():
    """(train, model, optim) configs as ``main.py`` loads them."""
    return load_config(
        os.path.join(REPO, "configs/train_config_dp.yaml"),
        os.path.join(REPO, "configs/model_config.yaml"),
        os.path.join(REPO, "configs/optim_config.yaml"),
    )


@pytest.fixture(scope="module")
def flagship(shipped):
    return shipped[1]


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """Take the on-TPU branch of every predicate the kernels consult, and
    keep these compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    from dtc_tpu.ops import (
        attention, decode_attention, decode_fused, flash_attention, gated_delta,
        overlap_collectives, rotary,
    )

    for mod in (flash_attention, decode_attention, decode_fused, overlap_collectives, gated_delta,
                rotary):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Lower + compile for the described chip; the program must hold at
    least one Mosaic kernel (else the steer failed and the case would
    pass vacuously)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# training attention


#: Per-chip attention shapes (B, T, H, D) of the main training paths: the
#: flagship, and the benchmark's two cells (gpt2-medium on one chip,
#: gpt2-large under FSDP: 8 rows a chip in both), and Ouro-2.6B's cell: 16
#: heads of 128 without KV groups, one head a lane group of the packed family.
FLASH_SHAPES = {
    "flagship": (8, 512, 16, 32),
    "gpt2_medium_b8": (8, 1024, 16, 64),
    "gpt2_large_fsdp4_b32": (8, 1024, 20, 64),
    "ouro_b2x4096": (2, 4096, 16, 128),
}


@pytest.mark.parametrize("name", list(FLASH_SHAPES))
def test_flash_fwd_bwd(one_chip, flagship, name):
    """Flash attention forward AND backward, bf16, through the op
    ``attention: auto`` resolves to on a TPU, with the tiles the kernel
    chooses from the shape: a tiling the chip's compiler refuses (scoped
    VMEM, a block shape) fails here and not on the chip."""
    from dtc_tpu.ops import flash_attention as fa
    from dtc_tpu.ops.attention import causal_attention, resolve_impl

    b, t, h, d = FLASH_SHAPES[name]
    if name == "flagship":
        assert (t, h, d) == (flagship.max_seq_len, flagship.n_heads, flagship.head_dim)
    assert resolve_impl(flagship.attention, t, d) == "flash"
    plan = fa.schedule(t, h, d)
    assert plan["fwd"]["schedule"] == plan["bwd"]["schedule"] == "triangle"
    qkv = _sds((b, t, h, d), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return causal_attention(q, k, v, impl=flagship.attention).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


def test_packed_rotary_fwd_bwd(one_chip):
    """Rotary positions in the packed layout at the Ouro cell's shape (2 rows
    x 4096, 16 heads of 128, bf16), forward and backward — the same kernel
    with the sine's sign turned: the 128-lane roll, the per-head lane slices
    of a ``(1, rows, H d)`` block and the stated ``vmem_limit_bytes`` are what
    interpret mode cannot refuse."""
    from dtc_tpu.ops.rotary import packed_rotary, supports_packed_rotary

    b, t, h, d = FLASH_SHAPES["ouro_b2x4096"]
    assert supports_packed_rotary(d, 1.0, h, t, 2)["rows"] == 256
    x = _sds((b, t, h * d), jnp.bfloat16, one_chip)

    def loss(q, k):
        q, k = packed_rotary(q, k, 1e6, d)
        return (q.astype(jnp.float32) * k.astype(jnp.float32)).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), x, x).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # in and out in the dtype: no float32 (B, T, H d) array is ever in HBM for the kernel
    calls = [line for line in text.splitlines() if "rotary_packed" in line and "custom-call(" in line]
    assert len(calls) == 2 and not any("f32[2,4096,2048]" in line for line in calls)


#: Attention shapes with KV groups (B, T, H, KV heads, D, block) of the
#: benchmark's layer-pattern cells: the transposed-layout kernels, each query
#: head's KV head picked by the block index maps.
GROUPED_FLASH_SHAPES = {
    "qwen3_next_b2": (2, 8192, 16, 2, 256, 1024),
    "lfm2_b4": (4, 8192, 32, 8, 64, 1024),   # groups 4 wide at head size 64: the packed family has none
}


@pytest.mark.parametrize("name", list(GROUPED_FLASH_SHAPES))
def test_flash_kv_groups_fwd_bwd(one_chip, name):
    """Flash attention with fewer KV heads than query heads, forward and
    backward (dq, and dk / dv summed over the group in VMEM), bf16, at the
    cells' shapes and tiles."""
    from dtc_tpu.ops.attention import causal_attention, resolve_impl

    b, t, h, hk, d, block = GROUPED_FLASH_SHAPES[name]
    assert resolve_impl("auto", t, d, block, block) == "flash"
    q = _sds((b, t, h, d), jnp.bfloat16, one_chip)
    kv = _sds((b, t, hk, d), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return causal_attention(q, k, v, impl="auto", block_q=block,
                                block_kv=block).astype(jnp.float32).sum()

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("axis,name", [
    ("data", "flagship"), ("model", "flagship"), ("data", "gpt2_large_fsdp4_b32"),
])
def test_flash_on_a_four_chip_mesh(topo, axis, name):
    """XLA cannot partition a Mosaic kernel: on more than one device the
    TPU lowering refuses a bare pallas_call ("cannot be automatically
    partitioned") — which interpret mode on the CPU mesh never showed,
    and which stopped EVERY multi-chip training leg. The op must run the
    kernel per (batch, heads) shard in a fully manual region: batch over
    data=4 (dp/fsdp; the four-chip cell's 32 rows) and heads over
    model=4 (tp)."""
    from flax import linen as nn

    from dtc_tpu.ops.attention import causal_attention
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    shape = (1, 4, 1) if axis == "data" else (1, 1, 4)
    mesh = build_mesh(shape, devices=list(topo.devices))
    b, t, h, d = FLASH_SHAPES[name]
    b = 4 * b if name != "flagship" else b  # the cell's rows over four chips
    qkv = _sds((b, t, h, d), jnp.bfloat16, NamedSharding(mesh, P("data", None, "model")))

    def loss(q, k, v):
        return causal_attention(q, k, v, impl="flash").astype(jnp.float32).sum()

    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert "all-gather" not in compiled.as_text()  # each shard stays home


def test_zigzag_ring_block_kernels(one_chip):
    """The ring-attention block kernels (forward with lse out, backward
    with the merged lse in) at one device's zigzag half-chunk of a
    T=4096 sequence over model=4: (B, 512, H·D) with hd 128 lanes."""
    from dtc_tpu.ops import flash_attention as fa

    b, tc, h, d = 2, 512, 16, 32
    assert fa.block_supported(tc, h, d)
    g = fa._packed_group(d, h)
    x = _sds((b, tc, h * d), jnp.bfloat16, one_chip)

    def fwd_bwd(q, k, v):
        out, lse = fa._block_call(q, k, v, d ** -0.5, True, g, d)
        return fa._block_call(q, k, v, d ** -0.5, False, g, d, do=out, o=out, lse=lse)

    _compile(fwd_bwd, x, x, x)


# ---------------------------------------------------------------------------
# gated deltanet


def test_gdn_chunks_fwd_bwd(one_chip):
    """The fused kernels of the Gated DeltaNet scan (chunk-local part and
    carry, the state in VMEM scratch across a row's chunks) at the
    benchmark cell's shape — 2 rows x 8192, 16 key heads serving 32 value
    heads of 128, chunks of 64, bf16 — through ``gated_delta_chunked``:
    (64, 64) blocks, transposed 64-wide operands and the stated
    ``vmem_limit_bytes`` are what interpret mode cannot refuse. Under a
    ``jax.checkpoint``, as in the layer, so that all three kernels compile:
    the primal, the forward that writes the states, the backward."""
    from dtc_tpu.ops.gated_delta import gated_delta_chunked, supports_chunk_kernel

    b, t, hk, hv, d = 2, 8192, 16, 32, 128
    assert supports_chunk_kernel(64, d, d, hv, hk, 2)
    qk = _sds((b, t, hk, d), jnp.bfloat16, one_chip)
    v = _sds((b, t, hv, d), jnp.bfloat16, one_chip)
    g = _sds((b, t, hv), jnp.float32, one_chip)

    def loss(q, k, v, g, beta):
        return gated_delta_chunked(q, k, v, g, beta, chunk=64, dtype=jnp.bfloat16).sum()

    text = _compile(jax.value_and_grad(jax.checkpoint(loss), argnums=range(5)), qk, qk, v, g, g).as_text()
    assert all(f"gdn_chunks_{leg}" in text for leg in ("fwd.", "fwd_res", "bwd"))


def test_gdn_layer_on_a_four_chip_mesh(topo):
    """The mixer at the cell's widths with its rows over data=4 (dp / fsdp
    of a pattern model): the kernel pair must sit in a manual region, each
    chip on its own rows — a bare ``pallas_call`` is refused on a mesh
    ("cannot be automatically partitioned"), which the CPU never shows."""
    import json

    from flax import linen as nn

    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.models.pattern import GatedDeltaNet
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    with open(os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as f:
        cfg = replace(ModelConfig(**json.load(f)["model"]), max_seq_len=1024)
    mesh = build_mesh((1, 4, 1), devices=list(topo.devices))
    layer = GatedDeltaNet(cfg)
    x = _sds((4, cfg.max_seq_len, cfg.d_model), jnp.bfloat16, NamedSharding(mesh, P("data")))
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, NamedSharding(mesh, P())),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.ones((1, *x.shape[1:]), x.dtype))))

    def loss(p, x):
        return layer.apply(p, x).astype(jnp.float32).sum()

    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        text = _compile(jax.grad(loss, argnums=(0, 1)), params, x).as_text()
    assert "gdn_chunks_fwd_res" in text and "gdn_chunks_bwd" in text
    assert "all-gather" not in text  # each chip's rows stay home


def test_packed_rotary_layer_on_a_four_chip_mesh(topo):
    """The Ouro cell's attention layer with its rows over data=4: the rotary
    kernel, like the flash kernels after it, must sit in a manual region, each
    chip on its own rows (a bare ``pallas_call`` is refused on a mesh)."""
    import json

    from flax import linen as nn

    from dtc_tpu.config.schema import ModelConfig
    from dtc_tpu.models.pattern import Attention, packed_rotary_plan
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES

    with open(os.path.join(REPO, "benchmark", "configs", "ouro-2.6b.json")) as f:
        cfg = replace(ModelConfig(**json.load(f)["model"]), max_seq_len=1024)
    assert packed_rotary_plan(cfg, cfg.max_seq_len, gated=False) is not None
    mesh = build_mesh((1, 4, 1), devices=list(topo.devices))
    layer = Attention(cfg, gated=False)
    x = _sds((4, cfg.max_seq_len, cfg.d_model), jnp.bfloat16, NamedSharding(mesh, P("data")))
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, NamedSharding(mesh, P())),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.ones((1, *x.shape[1:]), x.dtype))))

    def loss(p, x):
        return layer.apply(p, x).astype(jnp.float32).sum()

    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        text = _compile(jax.grad(loss, argnums=(0, 1)), params, x).as_text()
    assert text.count("rotary_packed") >= 2
    assert "all-gather" not in text  # each chip's rows stay home


# ---------------------------------------------------------------------------
# decode


@pytest.mark.parametrize("frontier", ["scalar", "per_row"])
def test_decode_kernel_per_layer(one_chip, flagship, frontier):
    """The per-layer decode kernel (``decode_attention: fused``) over the
    flagship's packed bf16 cache, generate's scalar frontier and the
    serving engine's (B,) slot frontiers."""
    from dtc_tpu.ops.decode_attention import fused_decode_attention

    b, s = 8, flagship.max_seq_len
    h, d = flagship.n_heads, flagship.head_dim
    q = _sds((b, 1, h * d), jnp.bfloat16, one_chip)
    kv = _sds((b, s, h * d), jnp.bfloat16, one_chip)
    idx = _sds(() if frontier == "scalar" else (b,), jnp.int32, one_chip)
    _compile(
        lambda q, k, v, i: fused_decode_attention(q, k, v, i, h=h, d=d),
        q, kv, kv, idx,
    )


@pytest.mark.parametrize(
    "kv_cache_dtype,t", [("auto", 1), ("int8", 1), ("auto", 4), ("int8", 4)],
    ids=["bf16", "int8", "spec_verify_bf16", "spec_verify_int8"],
)
def test_decode_megakernel(one_chip, flagship, kv_cache_dtype, t):
    """``decode_attention: fused_layers`` through ``generate.decode_step``
    at flagship width, batch 8: plain decode and the speculative verify
    window (t=4), bf16 and int8 KV. The gate must say "fits" AND the
    chip's compiler must agree — the state "gate says fits, compiler
    refuses" is what this case exists to keep out."""
    from dtc_tpu import generate as G
    from dtc_tpu.ops import decode_fused

    cfg = replace(
        flagship, decode_attention="fused_layers", kv_cache_dtype=kv_cache_dtype,
    )
    assert decode_fused.decode_backend(cfg, t, verify=t > 1) == "fused_layers"
    model, b = GPT(cfg), 8
    shapes = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.ones((b, 1), jnp.int32),
            train=False, decode=True,
        )
    )
    to_chip = lambda x: _sds(x.shape, x.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(to_chip, shapes["params"])
    cache = jax.tree.map(to_chip, shapes["cache"])
    tok = _sds((b, t), jnp.int32, one_chip)
    _compile(
        lambda p, c, tk: G.decode_step(model, p, c, tk, spec_verify=t > 1),
        params, cache, tok,
    )


# ---------------------------------------------------------------------------
# overlapped collectives (four described chips)


@pytest.mark.parametrize("shard_axis,k,n", [(0, 512, 2048), (1, 2048, 512)],
                         ids=["fc1_contract", "fc2_out"])
def test_overlap_ring_matmul_fwd_bwd(mesh4, shard_axis, k, n):
    """The fused ring all-gather-matmul and its backward (dx re-gather +
    streamed dw reduce-scatter) — the barrier / collective_id / MESH
    device-id branches no CPU test can take — at the flagship's fsdp
    data=4 shapes: global batch 8, 128-wide weight blocks."""
    from dtc_tpu.ops.overlap_collectives import _pallas_ok, overlap_dense_matmul

    assert _pallas_ok(2 * 512, k, n, 4, shard_axis, 2)
    x = _sds((8, 512, k), jnp.bfloat16, NamedSharding(mesh4, P("data")))
    w_spec = P("data", None) if shard_axis == 0 else P(None, "data")
    w = _sds((k, n), jnp.bfloat16, NamedSharding(mesh4, w_spec))

    def loss(x, w):
        y = overlap_dense_matmul(
            x, w, shard_axis=shard_axis, axis_name="data", mesh=mesh4,
            backend="pallas",
        )
        return y.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1)), x, w)


def test_overlap_reduce_scatter_matmul(mesh4):
    """The standalone streamed matmul + reduce-scatter kernel."""
    from dtc_tpu.ops.overlap_collectives import reduce_scatter_matmul

    rows = NamedSharding(mesh4, P("data"))
    a = _sds((8 * 512, 512), jnp.bfloat16, rows)
    b = _sds((8 * 512, 2048), jnp.bfloat16, rows)
    _compile(
        lambda a, b: reduce_scatter_matmul(
            a, b, shard_axis=1, axis_name="data", mesh=mesh4, backend="pallas",
        ),
        a, b,
    )


# ---------------------------------------------------------------------------
# the whole step


def test_flagship_dp_train_step_fits_one_chip(topo, shipped):
    """The flagship DP train step — the trainer's own step builder, model
    and optimizer, batch 8 × seq 512 — compiled for one described chip
    with the flash kernel in it, and under the chip's 16 GB."""
    from flax import linen as nn
    from flax.training.train_state import TrainState

    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES
    from dtc_tpu.train.train_step import Batch, create_gspmd_train_step
    from dtc_tpu.train.trainer import _guarded_optimizer

    train_cfg, flagship, opt_cfg = shipped
    mesh = build_mesh((1, 1, 1), devices=[topo.devices[0]])
    replicated = NamedSharding(mesh, P())
    model = GPT(flagship)
    tx = _guarded_optimizer(train_cfg, opt_cfg)
    tokens = jnp.ones((1, flagship.max_seq_len), jnp.int32)

    def init():
        params = model.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
            tokens, train=False,
        )["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    state = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, replicated), jax.eval_shape(init)
    )
    xy = _sds((train_cfg.batch, flagship.max_seq_len), jnp.int32, replicated)
    rng = _sds((2,), jnp.uint32, replicated)
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        step = create_gspmd_train_step(mesh, DEFAULT_RULES)
        compiled = step.lower(state, Batch(x=xy, y=xy), rng).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert 0 < need < V5E_HBM_BYTES, mem


#: The benchmark's layer-pattern cells: (configuration file, Mosaic calls at
#: least, names the compiled step must hold).
PATTERN_CELLS = {
    # flash forward, dq and dk/dv; the scan's primal, residual-writing forward and backward
    "qwen3-next-80b-a3b.train-ep16share-b2x8192": (
        "qwen3-next-80b-a3b", 5, ("gdn_chunks_fwd", "gdn_chunks_fwd_res", "gdn_chunks_bwd")),
    # flash forward, dq and dk/dv on the transposed layout: 32 query heads on 8 KV heads of 64
    "lfm2-8b-a1b.train-ep4share-8k": ("lfm2-8b-a1b", 3, ()),
    # the packed flash kernels at 16 heads of 128 (forward, and the fused backward) and the
    # packed rotary kernel before them, in the ONE copy of the stack that the scan over the
    # four passes holds
    "ouro-2.6b.train-loop4-b2x4096": ("ouro-2.6b", 2, ("rotary_packed",)),
}

#: The Ouro cell's compiled peak on the parent of the PR that packed its rotary (PR 39):
#: the kernel keeps nothing for its backward, so the step may not ask for more.
OURO_PEAK_BEFORE_PACKED_ROTARY = 14_489_447_424


@pytest.mark.parametrize("cell", list(PATTERN_CELLS))
def test_pattern_cell_train_step_fits_one_chip(topo, cell):
    """A layer-pattern cell of the benchmark through the trainer's own step
    builder, under the chip's memory — the tight resource of both. Qwen3-Next's
    one period (32 of 512 experts held, 2 rows x 8192): grouped KV heads at
    head size 256 through the flash kernels, the scan's fused kernels (the
    state carried in VMEM), the experts' loop over tiles. LFM2-8B-A1B's leading
    dense layer and one period (8 of 32 experts held, 4 rows x 8192): KV
    groups 4 wide at head size 64 through the same kernels, the short
    convolutions, the tied head. Ouro-2.6B's 8 layers run four times (2 rows x
    4096): the passes are one scanned body, so the step holds one copy of the
    stack, and no pass's logits are kept beside 9.8 GB of state (the per-token CE
    recomputes them in its backward, +6 % operations)."""
    import json

    from flax import linen as nn
    from flax.training.train_state import TrainState

    from dtc_tpu.config.schema import ModelConfig, OptimConfig
    from dtc_tpu.models.pattern import build_model, moe_plan
    from dtc_tpu.parallel.mesh import build_mesh
    from dtc_tpu.parallel.sharding import DEFAULT_RULES
    from dtc_tpu.train.optimizer import create_optimizer
    from dtc_tpu.train.train_step import Batch, create_gspmd_train_step

    config, calls, names = PATTERN_CELLS[cell]
    with open(os.path.join(REPO, "benchmark", "configs", f"{config}.json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(REPO, "benchmark", "workloads", f"{cell}.json")) as f:
        workload = json.load(f)
    cfg = ModelConfig(**{**model, **workload["train"]["model"]})
    rows = workload["traffic"]["rows"]
    mesh = build_mesh((1, 1, 1), devices=[topo.devices[0]])
    replicated = NamedSharding(mesh, P())
    net = build_model(cfg)
    tx = create_optimizer(OptimConfig(**workload["optim"]), total_steps=1_000_000)
    tokens = jnp.ones((1, cfg.max_seq_len), jnp.int32)

    def init():
        params = net.init({"params": jax.random.PRNGKey(0)}, tokens, train=False)["params"]
        return TrainState.create(apply_fn=net.apply, params=params, tx=tx)

    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, replicated), jax.eval_shape(init))
    xy = _sds((rows, cfg.max_seq_len), jnp.int32, replicated)
    rng = _sds((2,), jnp.uint32, replicated)
    with mesh, nn.logical_axis_rules(DEFAULT_RULES):
        step = create_gspmd_train_step(mesh, DEFAULT_RULES, counters=True)
        compiled = step.lower(state, Batch(x=xy, y=xy), rng).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= calls
    assert all(name in text for name in names)
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print("peak_memory_in_bytes", peak)
    assert 0 < peak < V5E_HBM_BYTES
    if cfg.stack_passes > 1:
        # one copy of the stack: the flash forward once in the primal and once in a
        # layer's recomputation, one fused backward — not that times the passes — and the
        # rotary kernel before (in the backward: after) each of the three
        assert text.count('custom_call_target="tpu_custom_call"') == 6
        assert peak <= OURO_PEAK_BEFORE_PACKED_ROTARY
        _q_and_k_stay_packed(text, rows, cfg)
    plan = moe_plan(cfg, rows * cfg.max_seq_len)
    if plan is not None:
        _expert_loop_adds_in_place(text, rows * cfg.max_seq_len, plan["staged_rows"], cfg.d_model)


def _q_and_k_stay_packed(text: str, rows: int, cfg):
    """What interpret mode cannot say of the packed rotary (``ops/rotary.py``):
    in the optimized step the kernel's custom call stands three times (a
    layer's forward, its recomputation, its backward), each under
    ``attn_full/attn_qkv`` and not under ``attn_kernel`` (whose time the flash
    roofline divides by), and between a q / k projection's matmul and the
    flash call nothing re-lays q or k: no 4-D ``(B, T, H, d)`` array exists
    anywhere in the step, and no ``copy`` / ``transpose`` / ``reshape`` of a
    ``(B, T, H d)`` array stands under ``attn_full`` (a bitcast is free and is
    printed as ``bitcast``)."""
    import re

    t, h, d = cfg.max_seq_len, cfg.n_heads, cfg.head_dim
    calls = [line for line in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = .* custom-call\(", line)
             and "rotary_packed" in line]
    assert len(calls) == 3, len(calls)
    assert all("/attn_full/attn_qkv/rotary_packed" in line and "attn_kernel" not in line for line in calls)
    assert f"[{rows},{t},{h},{d}]" not in text
    attn = [line.strip() for line in text.splitlines() if "/attn_full/" in line]
    moved = [line for line in attn
             if re.match(rf"(ROOT )?%\S+ = \S+\[{rows},{t},{h * d}\]\S* (copy|copy-start|transpose|reshape)\(", line)]
    assert not moved, moved[:3]


def _expert_loop_adds_in_place(text: str, tokens: int, staged_rows: int, d: int):
    """What interpret mode cannot say of the experts' loop (``ops/moe_dispatch``):
    in the optimized step no ``copy`` of the tokens' ``(tokens, d)`` float32
    rows nor of the ``(staged_rows, d)`` staging stands under the ``moe`` scope
    — the loops carry both in place — and every fusion that scatter-adds a
    group's rows into the tokens' rows gives its result its first operand's
    buffer. (A layer's forward and its backward each hold one; the layer
    remat's forward needs no output, so XLA drops its loop.)"""
    import re

    large = tuple(f"f32[{n},{d}]" for n in (tokens, staged_rows))
    moe = [line.strip() for line in text.splitlines() if "/moe/" in line]
    made = [re.match(r"(?:ROOT )?%\S+ = (.*?) (copy|copy-start|fusion)\(", line) for line in moe]
    copies = [line for line, m in zip(moe, made)
              if m and m.group(2) != "fusion" and any(shape in m.group(1) for shape in large)]
    assert not copies, copies[:3]
    scatters = [line for line, m in zip(moe, made)
                if m and m.group(2) == "fusion" and m.group(1).startswith(large[0])
                and "combine/scatter-add" in line and "kind=kCustom" in line and not line.startswith("ROOT")]
    assert len(scatters) >= 2, len(scatters)
    for line in scatters:
        assert re.search(r'"aliasing_operands":\{"lists":\[\{"indices":\["0"', line), line[:200]
