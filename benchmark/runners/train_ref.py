"""The ``train_ref`` runner: the ``train`` runner for a configuration that
names its own plain reference.

``runners/train.py`` imports the GPT-2 reference and maps GPT-2 leaf names;
this runner takes both from the cell's configuration file instead:
``reference`` (a module of ``benchmark/`` with ``reference.py``'s interface)
and ``leaf_names`` (the program's parameter path, joined by ``/``, to the
reference's leaf name). The rest is the same run: the two seams around
``trainer.train`` (``init_state``: the seed's weights in the program's own
shardings; ``create_train_step``: the recorder around the compiled step),
the trainer's warm-up steps as the set-up's steps, the window by the clock,
the comparison once the window has closed. What is the same is imported from
``runners/train.py``; what names the reference is written again here
(PERF.md section 7 lists it for the next ``benchmark`` issue to fold).

Two differences of the program's side: a layer-pattern model's step returns
``(state, loss, counters)``, which the recorder passes on untouched; and the
kernels this cell needs are asserted by ``_assert_kernels`` (flash attention
compiled at the configuration's head size and KV grouping; the program on a
TPU, where the expert matmuls lower to Mosaic).
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import math
import os
import signal
import time
from contextlib import contextmanager

import numpy as np

import compare
import traffic as traffic_mod


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_runners_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _sibling("train")
SETUP_STEPS = base.SETUP_STEPS


class Names:
    """The reference's name for every leaf of the program's tree."""

    def __init__(self, config: dict):
        self.table = dict(config["leaf_names"])

    def __call__(self, path: tuple[str, ...]) -> str:
        try:
            return self.table["/".join(path)]
        except KeyError:
            raise KeyError(f"the program has a parameter the configuration's "
                           f"leaf_names do not know: {'/'.join(path)}") from None

    def by_ref(self, tree) -> dict:
        import jax

        return dict(zip(map(self, base._paths(tree)), jax.tree.leaves(tree)))


class Recorder:
    """``runners/train.py``'s recorder, for a step that may return more
    than ``(state, loss)`` and a reference named by the configuration."""

    def __init__(self, step_fn, ref, names: Names, model: dict, words, seconds: float):
        self.step_fn, self.ref, self.names = step_fn, ref, names
        self.model, self.words, self.seconds = model, words, seconds
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
        out = self.step_fn(state, batch, rng)
        self._record(out[0], out[1])
        self.t_setup_steps.append(time.perf_counter())
        return out

    def _record(self, state, loss) -> None:
        import jax
        import jax.numpy as jnp

        ref, names = self.ref, self.names
        self.losses.append(float(jax.device_get(loss)))
        if self.calls == 1:
            norms = jax.jit(ref.leaf_norms)(names.by_ref(base._adam_mu(state.opt_state)))
            self.grad1 = {k: np.asarray(v) / (1.0 - ref.B1)
                          for k, v in jax.device_get(norms).items()}
        if self.calls == SETUP_STEPS:
            model = self.model
            params = names.by_ref(state.params)
            layout = {k: v.sharding for k, v in params.items()}

            def delta(params, words):
                w0 = ref.make_weights(model, words)
                return ref.leaf_norms(
                    {k: v.astype(jnp.float32)
                     - jax.lax.with_sharding_constraint(w0[k], layout[k])
                     for k, v in params.items()})

            self.dparam = jax.device_get(jax.jit(delta)(params, self.words))


@contextmanager
def _seams(ref, names: Names, model: dict, seed: int, seconds: float, box: dict):
    import jax

    from dtc_tpu.train import trainer

    real_init, real_create = trainer.init_state, trainer.create_train_step
    words = ref.seed_words(seed)

    def init_state(*args, **kwargs):
        state = real_init(*args, **kwargs)
        paths = base._paths(state.params)
        shapes = ref.leaf_shapes(model)
        leaves, treedef = jax.tree.flatten(state.params)
        for p, leaf in zip(paths, leaves):
            if tuple(leaf.shape) != shapes[names(p)]:
                raise ValueError(f"{p}: program {leaf.shape}, reference {shapes[names(p)]}")
        if len(leaves) != len(shapes):
            raise ValueError("the reference has leaves the program lacks")
        dtypes = [leaf.dtype for leaf in leaves]
        shardings = treedef.unflatten([leaf.sharding for leaf in leaves])
        for leaf in leaves:
            leaf.delete()  # the program's own draw: never both sets at once

        def make(w):
            made = ref.make_weights(model, w)
            return treedef.unflatten([made[names(p)].astype(dt) for p, dt in zip(paths, dtypes)])

        return state.replace(params=jax.jit(make, out_shardings=shardings)(words))

    def create_train_step(*args, **kwargs):
        box["recorder"] = Recorder(real_create(*args, **kwargs), ref, names, model, words, seconds)
        return box["recorder"]

    trainer.init_state, trainer.create_train_step = init_state, create_train_step
    try:
        yield
    finally:
        trainer.init_state, trainer.create_train_step = real_init, real_create


def _assert_kernels(model_cfg) -> None:
    """The configured backends are the ones that run: a pattern model on a
    TPU, its full attention through the compiled flash kernel."""
    import jax

    from dtc_tpu.ops import attention, flash_attention

    if not model_cfg.layer_pattern:
        raise SystemExit("the train_ref runner drives a layer-pattern model; this configuration has none")
    impl = attention.resolve_impl(
        model_cfg.attention, model_cfg.max_seq_len, model_cfg.head_dim,
        model_cfg.attention_block_q, model_cfg.attention_block_kv)
    if impl != "flash" or flash_attention._interpret() or jax.default_backend() != "tpu":
        raise SystemExit(
            f"attention: {model_cfg.attention} resolved to {impl} on backend "
            f"{jax.default_backend()} (interpreted: {flash_attention._interpret()}); the cell "
            "needs the compiled flash kernel")


def drive(cell) -> dict:
    """The program's part of a run: set-up, the window, what was read."""
    from dtc_tpu.train import trainer

    (train_cfg, model_cfg, opt_cfg), model, optim = base._load_program_configs(cell)
    if cell.require_chip:
        _assert_kernels(model_cfg)
    ref = importlib.import_module(cell.config["reference"])
    names = Names(cell.config)
    wl = cell.workload
    seq_len = model["max_seq_len"]
    feed = traffic_mod.token_rows(wl["traffic"], model["vocab_size"], seq_len, cell.seed)
    box: dict = {}
    t_train = time.perf_counter()
    with _seams(ref, names, model, base.weights_seed(wl, cell.seed), cell.seconds, box):
        result = trainer.train(train_cfg, model_cfg, opt_cfg, host_iterator=feed)
    rec: Recorder = box["recorder"]
    step_ends = [float(t) for t in result.elapsed_times]
    losses = [float(v) for v in result.losses]
    stats = base.memory_stats()
    del result
    gc.collect()
    return {
        "cell": cell.name, "chips": cell.chips, "seed": cell.seed,
        "model": model, "optim": optim, "workload": wl, "out_dir": cell.out_dir,
        "t_window": rec.t_window, "step_ends": step_ends,
        "tokens_per_step": traffic_mod.tokens_per_step(wl["traffic"], seq_len),
        "events": base.read_events(cell.out_dir),
        "memory_stats": stats,
        "profile_dir": os.path.join(cell.out_dir, "train", "profile") if cell.trace else None,
        "attempted": len(step_ends),
        "failed": sum(not math.isfinite(v) for v in losses) + (len(step_ends) - len(losses)),
        "program": {"losses": rec.losses, "grad1": rec.grad1, "dparam": rec.dparam},
        "setup_phases": [(k, t - cell.t_process) for k, t in (
            ("imports_and_configs", t_train), ("state_and_step_built", rec.t_built),
            *((f"setup_step_{i + 1}", t) for i, t in enumerate(rec.t_setup_steps)),
            ("first_timed_step", rec.t_window))],
        "reference_module": cell.config["reference"],
    }


def follow(run: dict, **how) -> dict:
    """The configuration's reference over the set-up's three steps of
    ``run``'s seed (``matmul``: the control; ``rows`` / ``frozen``: a
    planted fault)."""
    import jax

    ref = importlib.import_module(run["reference_module"])
    wl, model = run["workload"], run["model"]
    batches = [traffic_mod.token_rows_at(wl["traffic"], model["vocab_size"],
                                         model["max_seq_len"], run["seed"], i)
               for i in range(SETUP_STEPS)]
    return ref.run_steps(model, run["optim"], base.weights_seed(wl, run["seed"]), batches,
                         devices=jax.devices()[:run["chips"]], **how)


def run(cell) -> dict:
    run = drive(cell)
    t0 = time.perf_counter()
    run["readings"] = compare.readings(run["program"], follow(run))
    run["correct"], run["checks"] = compare.judge(run["readings"], cell.workload["limits"])
    run["reference_s"] = time.perf_counter() - t0
    return run
