"""The ``train`` runner: one cell through ``dtc_tpu.train.trainer.train``.

The window drives the entry ``main.py`` calls, with configurations built by
the program's own ``config.loader`` from the cell's files. Two seams let the
benchmark own the inputs and see the first steps without a change to the
program; both wrap public functions of ``dtc_tpu.train.trainer`` for the
length of the one ``train`` call:

- ``init_state``: the program builds its state as always; the parameters are
  then replaced, in their own shardings, by the benchmark's weights made on
  the device from ``--seed`` (or the workload's ``weights_seed``) in one
  jitted call (``reference.make_weights``, the call the plain reference
  makes too).
- ``create_train_step``: the compiled step the program builds is wrapped by
  a recorder. The trainer's warm-up steps are the set-up's first steps: they
  go through the loop's own call and feed, on rows that all differ, and the
  recorder keeps each one's loss, the norms of AdamW's first moment after
  step 1 and of the parameters' change after step 3. From the first timed
  step on it only looks at the clock, and raises the trainer's own SIGTERM
  stop flag inside the step that ends the window. Same object, same state:
  what the set-up drove is what the window times.

The trainer's per-step stamps (``TrainResult.elapsed_times``) are the
timings; its ``step`` / ``recompile`` events and its profiler window are
read as they are.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import signal
import time
from typing import Any

import numpy as np
import yaml

import compare
import reference
import traffic as traffic_mod

SETUP_STEPS = 3          # the trainer's warm-up steps; the reference follows all
FOREVER = 1_000_000      # steps / log_every: the window ends by the clock

_BLOCK = {
    ("ln_1", "scale"): "ln_1.g", ("ln_1", "bias"): "ln_1.b",
    ("ln_2", "scale"): "ln_2.g", ("ln_2", "bias"): "ln_2.b",
    ("attn", "q_proj", "kernel"): "q.w", ("attn", "q_proj", "bias"): "q.b",
    ("attn", "k_proj", "kernel"): "k.w", ("attn", "k_proj", "bias"): "k.b",
    ("attn", "v_proj", "kernel"): "v.w", ("attn", "v_proj", "bias"): "v.b",
    ("attn", "out_proj", "kernel"): "out.w", ("attn", "out_proj", "bias"): "out.b",
    ("mlp", "fc1", "kernel"): "fc1.w", ("mlp", "fc1", "bias"): "fc1.b",
    ("mlp", "fc2", "kernel"): "fc2.w", ("mlp", "fc2", "bias"): "fc2.b",
}
_TOP = {
    ("embed", "wte", "embedding"): "wte", ("embed", "wpe", "embedding"): "wpe",
    ("head", "ln_f", "scale"): "ln_f.g", ("head", "ln_f", "bias"): "ln_f.b",
    ("head", "lm_head", "kernel"): "head.w", ("head", "lm_head", "bias"): "head.b",
}


def ref_name(path: tuple[str, ...]) -> str:
    """The reference's name for a leaf of the program's parameter tree."""
    if path in _TOP:
        return _TOP[path]
    if path[:2] == ("stage", "blocks") and path[3:] in _BLOCK:
        return "blocks." + _BLOCK[path[3:]]
    raise KeyError(f"the program has a parameter the reference does not know: {path}")


def _paths(tree) -> list[tuple[str, ...]]:
    import jax

    return [tuple(getattr(k, "key", getattr(k, "name", None)) for k in p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _by_ref(tree) -> dict[str, Any]:
    import jax

    return dict(zip(map(ref_name, _paths(tree)), jax.tree.leaves(tree)))


def _adam_mu(opt_state):
    """AdamW's first moment, wherever the optimizer chain keeps it."""
    stack = [opt_state]
    while stack:
        s = stack.pop()
        if hasattr(s, "mu") and hasattr(s, "nu"):
            return s.mu
        if isinstance(s, (tuple, list)):
            stack.extend(s)
    raise LookupError("no Adam moments in the optimizer state")


class Recorder:
    """Wraps the program's compiled step; see the module docstring."""

    def __init__(self, step_fn, model: dict, words, seconds: float):
        self.step_fn, self.model, self.words = step_fn, model, words
        self.seconds = seconds
        self.calls = 0
        self.losses: list[float] = []
        self.grad1 = self.dparam = None
        self.t_window = None
        self.stop_sent = False
        self.t_built = time.perf_counter()
        self.t_setup_steps: list[float] = []

    def __call__(self, state, batch, rng):
        self.calls += 1
        k = self.calls - SETUP_STEPS
        if k >= 1:
            now = time.perf_counter()
            if k == 1:
                self.t_window = now
            elif not self.stop_sent:
                mean_step = (now - self.t_window) / (k - 1)
                if now - self.t_window + 0.5 * mean_step >= self.seconds:
                    self.stop_sent = True
                    signal.raise_signal(signal.SIGTERM)  # the trainer's stop flag
            return self.step_fn(state, batch, rng)
        state, loss = self.step_fn(state, batch, rng)
        self._record(state, loss)
        self.t_setup_steps.append(time.perf_counter())
        return state, loss

    def _record(self, state, loss) -> None:
        import jax
        import jax.numpy as jnp

        self.losses.append(float(jax.device_get(loss)))
        if self.calls == 1:
            mu = _by_ref(_adam_mu(state.opt_state))
            norms = jax.jit(reference.leaf_norms)(mu)
            self.grad1 = {k: np.asarray(v) / (1.0 - reference.B1)
                          for k, v in jax.device_get(norms).items()}
        if self.calls == SETUP_STEPS:
            model = self.model
            params = _by_ref(state.params)
            # The seed's weights again, laid out as the program lays out its
            # own: never a whole second model on one chip.
            layout = {k: v.sharding for k, v in params.items()}

            def delta(params, words):
                w0 = reference.make_weights(model, words)
                return reference.leaf_norms(
                    {k: v.astype(jnp.float32)
                     - jax.lax.with_sharding_constraint(w0[k], layout[k])
                     for k, v in params.items()})

            self.dparam = jax.device_get(jax.jit(delta)(params, self.words))


@contextlib.contextmanager
def _seams(model: dict, seed: int, seconds: float, box: dict):
    import jax

    from dtc_tpu.train import trainer

    real_init, real_create = trainer.init_state, trainer.create_train_step
    words = reference.seed_words(seed)

    def init_state(*args, **kwargs):
        state = real_init(*args, **kwargs)
        paths = _paths(state.params)
        shapes = reference.leaf_shapes(model)
        leaves, treedef = jax.tree.flatten(state.params)
        for p, leaf in zip(paths, leaves):
            if tuple(leaf.shape) != shapes[ref_name(p)]:
                raise ValueError(f"{p}: program {leaf.shape}, reference {shapes[ref_name(p)]}")
        if len(leaves) != len(shapes):
            raise ValueError("the reference has leaves the program lacks")

        dtypes = [leaf.dtype for leaf in leaves]
        shardings = treedef.unflatten([leaf.sharding for leaf in leaves])
        for leaf in leaves:
            leaf.delete()  # the program's own draw: never both sets at once

        def make(w):
            ref = reference.make_weights(model, w)
            return treedef.unflatten(
                [ref[ref_name(p)].astype(dt) for p, dt in zip(paths, dtypes)])

        return state.replace(params=jax.jit(make, out_shardings=shardings)(words))

    def create_train_step(*args, **kwargs):
        box["recorder"] = Recorder(real_create(*args, **kwargs), model, words, seconds)
        return box["recorder"]

    trainer.init_state, trainer.create_train_step = init_state, create_train_step
    try:
        yield
    finally:
        trainer.init_state, trainer.create_train_step = real_init, real_create


def weights_seed(workload: dict, seed: int) -> int:
    """The seed of a run's weights: ``--seed``, or the workload's
    ``weights_seed`` where it names one checkpoint for every run to start
    from (the rows stay ``--seed``'s)."""
    return int(workload.get("weights_seed", seed))


def build_configs(cell) -> tuple[dict, dict, dict]:
    """The program's three configuration mappings for this cell."""
    wl = cell.workload
    model = {**cell.config["model"], **wl["train"].get("model", {})}
    train = {k: v for k, v in wl["train"].items() if k != "model"}
    obs = dict(train.pop("obs", {}))
    if cell.trace:
        obs["profile_start"], obs["profile_stop"] = wl["trace_steps"]
    train.update(
        seed=cell.seed % 2**31, batch=int(wl["traffic"]["rows"]), steps=FOREVER,
        log_every=FOREVER, output_dir=os.path.join(cell.out_dir, "train"),
        dataset="synthetic", warmup_steps=SETUP_STEPS, overwrite=True, obs=obs,
    )
    return train, model, dict(wl["optim"])


def _load_program_configs(cell):
    from dtc_tpu.config.loader import load_config

    train, model, optim = build_configs(cell)
    paths = []
    for name, data in (("train", train), ("model", model), ("optim", optim)):
        path = os.path.join(cell.out_dir, f"{name}_config.yaml")
        with open(path, "w") as f:
            # not json.dump: it writes 1e-05, which YAML 1.1 reads as a string
            yaml.safe_dump(data, f)
        paths.append(path)
    return load_config(*paths), model, optim


def _assert_flash(model_cfg) -> None:
    """The configured backend is the one that runs: ``attention: auto`` must
    resolve to the compiled flash kernel (not dense, not interpreted)."""
    from dtc_tpu.ops import attention, flash_attention

    impl = attention.resolve_impl(
        model_cfg.attention, model_cfg.max_seq_len, model_cfg.head_dim,
        model_cfg.attention_block_q, model_cfg.attention_block_kv,
    )
    if impl != "flash" or flash_attention._interpret():
        raise SystemExit(
            f"attention: {model_cfg.attention} resolved to {impl} "
            f"(interpreted: {flash_attention._interpret()}); the cell needs "
            "the compiled flash kernel")


def read_events(out_dir: str) -> list[dict]:
    path = os.path.join(out_dir, "train", "obs", "events.r0.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def memory_stats() -> list[dict]:
    import jax

    return [dict(d.memory_stats() or {}) for d in jax.local_devices()]


def drive(cell) -> dict:
    """The program's part of a run: set-up, the window, what was read."""
    from dtc_tpu.train import trainer

    (train_cfg, model_cfg, opt_cfg), model, optim = _load_program_configs(cell)
    if cell.require_chip:
        _assert_flash(model_cfg)
    wl = cell.workload
    seq_len = model["max_seq_len"]
    feed = traffic_mod.token_rows(wl["traffic"], model["vocab_size"], seq_len, cell.seed)
    box: dict = {}
    t_train = time.perf_counter()
    with _seams(model, weights_seed(wl, cell.seed), cell.seconds, box):
        result = trainer.train(train_cfg, model_cfg, opt_cfg, host_iterator=feed)
    rec: Recorder = box["recorder"]
    step_ends = [float(t) for t in result.elapsed_times]
    losses = [float(v) for v in result.losses]
    stats = memory_stats()
    del result
    gc.collect()
    return {
        "cell": cell.name, "chips": cell.chips, "seed": cell.seed,
        "model": model, "optim": optim, "workload": wl, "out_dir": cell.out_dir,
        "t_window": rec.t_window, "step_ends": step_ends,
        "tokens_per_step": traffic_mod.tokens_per_step(wl["traffic"], seq_len),
        "events": read_events(cell.out_dir),
        "memory_stats": stats,
        "profile_dir": os.path.join(cell.out_dir, "train", "profile") if cell.trace else None,
        "attempted": len(step_ends),
        "failed": sum(not math.isfinite(v) for v in losses) + (len(step_ends) - len(losses)),
        "program": {"losses": rec.losses, "grad1": rec.grad1, "dparam": rec.dparam},
        # where the set-up went: (what, seconds since the process started)
        "setup_phases": [(k, t - cell.t_process) for k, t in (
            ("imports_and_configs", t_train), ("state_and_step_built", rec.t_built),
            *((f"setup_step_{i + 1}", t) for i, t in enumerate(rec.t_setup_steps)),
            ("first_timed_step", rec.t_window))],
    }


def follow(run: dict, **how) -> dict:
    """The reference over the set-up's three steps of ``run``'s seed.
    ``how`` reaches ``reference.run_steps`` (``matmul``: the control;
    ``rows``: a planted fault)."""
    import jax

    wl, model = run["workload"], run["model"]
    batches = [traffic_mod.token_rows_at(wl["traffic"], model["vocab_size"],
                                         model["max_seq_len"], run["seed"], i)
               for i in range(SETUP_STEPS)]
    return reference.run_steps(model, run["optim"], weights_seed(wl, run["seed"]), batches,
                               devices=jax.devices()[:run["chips"]], **how)


def run(cell) -> dict:
    run = drive(cell)
    # The comparison, once the window has closed, the memory has been read
    # and the program's state is freed.
    t0 = time.perf_counter()
    run["readings"] = compare.readings(run["program"], follow(run))
    run["correct"], run["checks"] = compare.judge(run["readings"], cell.workload["limits"])
    run["reference_s"] = time.perf_counter() - t0
    return run
