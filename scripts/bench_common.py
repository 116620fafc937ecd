"""Shared flagship-step benchmark harness for scripts/{ablate,profile_step}.py.

One place defines the flagship model/optimizer shapes and the
warmup + timed-loop protocol, so the ablation and the profiler always
measure the same program.
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# ONE flagship config definition, owned by bench.py (REPO is on sys.path
# above): bench rows, the sweeps, and anything deriving MFU from a config
# all build the same model.
from bench import flagship_model_cfg  # noqa: E402  (re-export for scripts)


def build_step(batch=32, grad_clip=1.0, weight_decay=0.1, parallel="dp",
               collectives="xla", precision="fp32", **model_knobs):
    """Returns (step_fn, state, batch_obj, key, (mesh, rules), model_cfg)
    for the flagship GPT-89.6M train step with the given knobs.

    ``parallel="fsdp"`` + ``collectives`` drive the ISSUE 12 overlap A/B
    rows: FSDP_RULES activate and the model config carries the
    collectives mode (resolve_collectives — the same lift the trainer
    does), so the benched step is the trainer's step.
    ``precision="bf16_mixed"`` (ISSUE 14) drives the mixed-precision A/B
    rows the same way — resolve_precision lifts bf16 params/compute onto
    the model config and create_optimizer holds the fp32 masters."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from dtc_tpu.config.schema import MeshConfig, OptimConfig, TrainConfig
    from dtc_tpu.data.synthetic import synthetic_batch_iterator
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.parallel.mesh import mesh_from_config
    from dtc_tpu.parallel.sharding import DEFAULT_RULES, FSDP_RULES
    from dtc_tpu.train.train_step import (
        Batch, create_train_step, resolve_precision,
    )
    from dtc_tpu.train.trainer import init_state

    model_cfg = flagship_model_cfg(**model_knobs)
    if collectives != "xla":
        model_cfg = dataclasses.replace(model_cfg, collectives=collectives)
    opt_cfg = OptimConfig(lr=3e-4, weight_decay=weight_decay,
                          grad_clip=grad_clip, precision=precision)
    model_cfg = resolve_precision(opt_cfg, model_cfg)
    train_cfg = TrainConfig(
        seed=0, parallel=parallel, batch=batch, steps=1, log_every=1,
        output_dir="", dataset="synthetic", warmup_steps=0, prefetch=0,
        mesh=MeshConfig(),
    )
    rules = FSDP_RULES if parallel == "fsdp" else DEFAULT_RULES
    mesh = mesh_from_config(parallel, train_cfg.mesh)
    model = GPT(model_cfg)
    with mesh, nn.logical_axis_rules(rules):
        state = init_state(model, model_cfg, train_cfg, opt_cfg, mesh, rules)
        # state= pins out_shardings so the step compiles ONCE (see
        # train_step.state_shardings — without it GSPMD layout churn pays
        # a second identical cold compile on the call after warmup step 1).
        step_fn = create_train_step(mesh, model=model, state=state)
    tok = next(synthetic_batch_iterator(batch, model_cfg.max_seq_len + 1, model_cfg.vocab_size))
    batch_obj = Batch(x=jnp.asarray(tok[:, :-1]), y=jnp.asarray(tok[:, 1:]))
    key = jax.random.key(0, impl="rbg")
    return step_fn, state, batch_obj, key, (mesh, rules), model_cfg


def time_step(steps=20, warmup=6, trace_dir=None, trace_steps=6, **knobs) -> float:
    """Warmup + timed loop; returns ms/step. Sync is by value fetch (a
    host transfer cannot return before the device work completes).
    ``trace_dir`` wraps ``trace_steps``
    traced iterations (used by profile_step) before the ``steps``-iteration
    timed loop — tracing few steps keeps the trace small without shortening
    the timing protocol."""
    import jax
    import numpy as np
    from flax import linen as nn

    step_fn, state, batch, key, (mesh, rules), _ = build_step(**knobs)
    with mesh, nn.logical_axis_rules(rules):
        for i in range(warmup):
            state, loss = step_fn(state, batch, jax.random.fold_in(key, i))
        float(np.asarray(loss))
        if trace_dir is not None:
            with jax.profiler.trace(trace_dir):
                for i in range(trace_steps):
                    state, loss = step_fn(state, batch, jax.random.fold_in(key, 100 + i))
                float(np.asarray(loss))
        t0 = time.perf_counter()
        for i in range(steps):
            state, loss = step_fn(state, batch, jax.random.fold_in(key, 200 + i))
        float(np.asarray(loss))
        return (time.perf_counter() - t0) / steps * 1e3
