"""Test harness: 8 virtual CPU devices standing in for a TPU slice.

The reference has no tests and no simulated-mesh story (SURVEY.md §4); here
every multi-device code path (GSPMD DP/TP, shard_map PP, 3D) runs on an
8-fake-device CPU mesh via --xla_force_host_platform_device_count.

NOTE: tests always run on the CPU, whatever is attached: the platform is
forced via jax.config.update after import (it wins over JAX_PLATFORMS),
and XLA_FLAGS is set before the first backend use. The chip is reached
through ``chip_smoke.py``, never through pytest.

NOTE: tiny test models use compute_dtype=float32, not bfloat16: besides
tighter parity tolerances, XLA's CPU backend CRASHES (check-fail in
AllReducePromotion, "Invalid binary instruction opcode copy") compiling
the pipeline step's bf16 collectives — an upstream XLA CPU bug; the TPU
backend handles bf16 collectives natively.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from dtc_tpu.config.schema import MeshConfig, ModelConfig, OptimConfig, TrainConfig  # noqa: E402


# Heavyweight suites kept OUT of `-m quick` but still in tier-1
# (`-m 'not slow'` — its scope is unchanged by the tiering): the PP
# schedule files pay minutes of 1F1B trace+XLA-compile per test, the
# multihost file launches real 2-process runs, the resilience file
# drives full chaos/rollback training runs, and the checkpoint file is
# Orbax + SIGTERM-subprocess I/O (187 s solo). Measured per-file on this
# 1-core host (PR 4), including any of them pushes `-m quick` past its
# 15-min budget.
_QUICK_EXCLUDE_FILES = {
    "test_pp_1f1b.py",
    "test_pp_dropout.py",
    "test_pp_vocab_chunking.py",
    "test_multihost.py",
    "test_resilience.py",
    "test_checkpoint.py",
    # Drives full chaos finetune + mixed-tenant chaos serving runs.
    "test_adapters.py",
    # Drives full elastic kill/shrink chaos training runs (ISSUE 15).
    "test_elastic.py",
    # Drives the goodput chaos acceptance run: a NaN-rollback training
    # run plus a replica-kill fleet run in one test (ISSUE 16).
    "test_goodput.py",
    # Drives pool grow/shrink resizes and a combined-chaos pool run
    # (ISSUE 17).
    "test_pool.py",
}


def pytest_collection_modifyitems(config, items):
    """Test tiering (round-5 VERDICT #6): anything not opted into a
    heavier tier is `quick`, so `pytest -m quick` is the <= 15-min
    critical path on a 1-core host, `-m kernels` the interpret-mode
    Pallas suites, `-m slow` the subprocess/perf tests — and the tier-1
    command (`-m 'not slow'`) is unchanged. Marking is additive-by-default
    so a NEW test file lands in `quick` without any registration step
    (unless listed in _QUICK_EXCLUDE_FILES above)."""
    for item in items:
        if (
            item.get_closest_marker("slow") is None
            and item.get_closest_marker("kernels") is None
            and item.path.name not in _QUICK_EXCLUDE_FILES
        ):
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="session", autouse=True)
def _assert_eight_devices():
    assert jax.device_count() == 8, (
        f"tests need 8 virtual CPU devices, got {jax.device_count()}"
    )


@pytest.fixture
def tiny_model_cfg():
    # Divisibility: n_heads=4 and d_model=64 shard over model=2/4;
    # n_layers=4 splits over pipe=2/4.
    return ModelConfig(
        vocab_size=97,
        d_model=64,
        n_layers=4,
        n_heads=4,
        d_ff=128,
        max_seq_len=32,
        dropout=0.0,
        param_dtype="float32",
        compute_dtype="float32",
        attention="dense",
    )


@pytest.fixture
def opt_cfg():
    return OptimConfig(lr=1e-3, weight_decay=0.1, grad_clip=1.0)


def make_train_cfg(parallel: str, **kw) -> TrainConfig:
    defaults = dict(
        seed=0,
        parallel=parallel,
        batch=8,
        steps=4,
        log_every=2,
        output_dir="",
        dataset="synthetic",
        warmup_steps=0,
        prefetch=0,
        mesh=MeshConfig(),
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


@pytest.fixture
def train_cfg_factory():
    return make_train_cfg
