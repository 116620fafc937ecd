"""What the files of pattern-model tests share (``test_pattern_model.py``,
``test_pattern_ops.py``, ``test_pattern_scan_kernels.py``, ``test_pattern_parallel.py``).

Everything is compared with the benchmark's plain references (float32
``jax.numpy``, dense attention, experts as a loop), loaded by path — there
is no second copy — on weights from their own ``make_weights``, at a toy
size. :data:`QWEN3` (the default of every helper):
``benchmark/reference_qwen3_next.py``, Gated DeltaNet as the token
recurrence; d 64, 2 key / 4 value heads of 16, 2 query heads on 1 KV head of
32 with 8 rotary dims, 8 experts top-2 of width 32, vocabulary 256.
:data:`LFM2`: ``benchmark/reference_lfm2_moe.py``, the short convolution as
a sum of shifted copies; d 64, a leading dense layer of width 96, 4 query
heads on 1 KV head of 16, 8 sigmoid-scored experts top-2 of width 32 chosen
with a selection bias, the head tied, vocabulary 256.
:data:`OURO`: ``benchmark/reference_ouro.py``, the passes as a Python loop;
d 64, three sandwich-normed layers of 4 heads of 16 and a SwiGLU of 96 run
four times, an exit gate after every pass, vocabulary 256.

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

from dtc_tpu.config.loader import load_yaml_dataclass
from dtc_tpu.config.schema import ModelConfig
from dtc_tpu.models import pattern
from tests.conftest import make_train_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT, LOOSE = 2e-4, 4e-2


def load_by_path(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Family:
    """A block family of the benchmark: its plain reference, the names its
    configuration file gives the program's leaves, its toy preset."""

    ref: object
    leaf_names: dict
    yaml: str

    @classmethod
    def of(cls, reference: str, config: str, preset: str) -> "Family":
        with open(os.path.join(REPO, "benchmark", "configs", f"{config}.json")) as f:
            names = json.load(f)["leaf_names"]
        return cls(load_by_path(os.path.join(REPO, "benchmark", f"{reference}.py"), reference),
                   names, os.path.join(REPO, "configs", preset))

    def cfg(self) -> ModelConfig:
        return load_yaml_dataclass(self.yaml, ModelConfig)


QWEN3 = Family.of("reference_qwen3_next", "qwen3-next-80b-a3b", "model_config_pattern_dev.yaml")
LFM2 = Family.of("reference_lfm2_moe", "lfm2-8b-a1b", "model_config_pattern_lfm2_dev.yaml")
OURO = Family.of("reference_ouro", "ouro-2.6b", "model_config_pattern_ouro_dev.yaml")
ref, LEAF_NAMES, TOY_YAML = QWEN3.ref, QWEN3.leaf_names, QWEN3.yaml


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return QWEN3.cfg()


@pytest.fixture(scope="module")
def lfm2_cfg() -> ModelConfig:
    return LFM2.cfg()


@pytest.fixture(scope="module")
def ouro_cfg() -> ModelConfig:
    return OURO.cfg()


def cell_cfg(name: str) -> tuple[ModelConfig, dict]:
    """(the program's configuration, the ``model`` group) of a configuration
    file of the benchmark."""
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        model = json.load(f)["model"]
    return ModelConfig(**model), model


def as_model(cfg: ModelConfig) -> dict:
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in dataclasses.asdict(cfg).items()}


def weights(cfg: ModelConfig, seed: int = 3, family: Family = QWEN3) -> dict:
    with jax.default_matmul_precision("highest"):
        return family.ref.make_weights(as_model(cfg), jnp.asarray(family.ref.seed_words(seed)))


def program_params(w: dict, family: Family = QWEN3) -> dict:
    """The reference's leaves laid out as the program's parameter tree."""
    tree: dict = {}
    for path, name in family.leaf_names.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = w[name]
    return tree


def layer_of(w: dict, position: int, family: Family = QWEN3, leading: bool = False) -> tuple[dict, dict]:
    """(program subtree, reference dict) of one layer: a position of the
    period with its periods axis taken off, or a leading layer."""
    stage = program_params(w, family)["stage"]
    if leading:
        return stage["leading"][f"layer_{position}"], family.ref.layer_params(w, f"lead.{position}.")
    flat = family.ref.layer_params(w, position if family is QWEN3 else f"blocks.{position}.")
    return (jax.tree.map(lambda a: a[0], stage["periods"][f"layer_{position}"]),
            {k: v[0] for k, v in flat.items()})


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), (
        np.max(np.abs(got - want)) / np.max(np.abs(want)))


def normed_input(cfg, seed=0, rows=2):
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, cfg.max_seq_len, cfg.d_model))
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True))


def one_device_steps(cfg, opt_cfg, batches, w=None, family: Family = QWEN3):
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
                lambda a, b: jnp.asarray(b, a.dtype), state.params, program_params(w, family)))
        step = create_train_step(mesh, model=model, state=state)
        losses = []
        for batch in batches:
            batch = jnp.asarray(batch)
            state, loss, counters = step(state, Batch(x=batch[:, :-1], y=batch[:, 1:]), jax.random.PRNGKey(0))
            losses.append(float(loss))
    return state, losses, counters


def scan_inputs(b, t, hk, h, dk, dv, seed=0):
    """Inputs of ``gated_delta_chunked`` as the mixer makes them (unit keys, scaled unit
    queries, decays that forget in a few tokens) and a cotangent for its output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, hk, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, t, h, dv))


def out_and_grads(fn, args, co):
    """``fn``'s value and its five gradients under the cotangent ``co``."""
    return (fn(*args), *jax.grad(lambda *a: jnp.sum(fn(*a) * co), argnums=range(5))(*args))
