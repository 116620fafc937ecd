"""Real 2-process multi-host training on CPU (round-2 VERDICT item 2c).

Two OS processes, 4 virtual CPU devices each, joined into one 8-device JAX
distributed runtime via a local coordinator (gloo CPU collectives). Each
process feeds its own half of the global batch through
``jax.make_array_from_process_local_data``; the test asserts

- both processes compute IDENTICAL losses (the gradient all-reduce really
  spans processes — independent training would diverge immediately because
  the processes feed different data),
- the loss differs from a run where both processes feed process-0's data
  (i.e. the per-process streams actually contribute distinct batches),
- only process 0 writes log.csv (lead-only logging).

The reference has no multi-process anything (SURVEY.md §2.2 "Multi-host").
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

coord, pid, variant = sys.argv[1], int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=coord, num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()       # 2 x 4 virtual
assert jax.local_device_count() == 4
dup = variant == "dup"

from dtc_tpu.config.schema import MeshConfig, ModelConfig, OptimConfig, TrainConfig
from dtc_tpu.train.trainer import make_host_iterator, train

model_cfg = ModelConfig(
    vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
    max_seq_len=32, dropout=0.0, param_dtype="float32",
    compute_dtype="float32", attention="dense",
)
opt_cfg = OptimConfig(lr=1e-3, weight_decay=0.1, grad_clip=1.0)
# "tp_in_host": the canonical pod layout — tensor parallelism over each
# process's local devices (fast links), data parallelism across processes
# (slow links, one gradient all-reduce per step).
mesh = MeshConfig(model=4, data=2) if variant == "tp_in_host" else MeshConfig()
train_cfg = TrainConfig(
    seed=0, parallel="tp" if variant == "tp_in_host" else "dp",
    batch=8, steps=3, log_every=1,
    output_dir=os.environ["DTC_OUT"], dataset="synthetic",
    warmup_steps=0, prefetch=0, mesh=mesh,
)

host_it = None
if dup:
    # Negative control: both processes feed process-0's stream.
    from dtc_tpu.data.synthetic import synthetic_batch_iterator
    host_it = synthetic_batch_iterator(4, 33, 97, seed=0)

res = train(train_cfg, model_cfg, opt_cfg, host_iterator=host_it)
print("LOSSES", json.dumps([pid, res.losses]))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(tmp_path, variant: str):
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in (0, 1):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            .replace("--xla_force_host_platform_device_count=8", "")
            + " --xla_force_host_platform_device_count=4"
        )
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["DTC_OUT"] = str(tmp_path / f"variant_{variant}")
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER, coord, str(pid), variant],
                env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
        )
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=420)
        if p.returncode != 0:
            pytest.fail(f"worker rc={p.returncode}\nstdout:{out[-2000:]}\nstderr:{err[-2000:]}")
        outs.append(out)
    losses = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("LOSSES"):
                pid, vals = json.loads(line.split(" ", 1)[1])
                losses[pid] = vals
    return losses


def test_two_process_training(tmp_path):
    losses = _launch(tmp_path, "dp")
    assert set(losses) == {0, 1}
    # Cross-process gradient sync: both processes see the same global loss.
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert len(losses[0]) == 3 and all(np.isfinite(losses[0]))

    # Lead-only logging: process 0 wrote the CSV; nothing from process 1.
    out_dir = tmp_path / "variant_dp"
    rows = (out_dir / "log.csv").read_text().strip().splitlines()
    assert len(rows) == 4  # header + 3 steps

    # Distinct per-process data: duplicating process-0's stream on both
    # hosts changes the global batch, hence the losses.
    dup_losses = _launch(tmp_path, "dup")
    np.testing.assert_allclose(dup_losses[0], dup_losses[1], rtol=1e-6)
    assert not np.allclose(losses[0], dup_losses[0], rtol=1e-4), (
        "per-process streams look identical — striding/offsets not applied"
    )


def test_two_process_tp_within_host_dp_across(tmp_path):
    """The canonical pod layout: a (data=2, model=4) mesh where tensor
    parallelism stays on each process's local devices and data parallelism
    crosses the process boundary. Exercises cross-process GSPMD collectives
    beyond the plain gradient all-reduce (activations replicated across
    hosts, per-layer TP all-reduces local)."""
    losses = _launch(tmp_path, "tp_in_host")
    assert set(losses) == {0, 1}
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    assert len(losses[0]) == 3 and all(np.isfinite(losses[0]))
