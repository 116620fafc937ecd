"""Trainer loop, program spans on the device's clock:
device idle between two executions of the step program while the host is inside
``train.rng``: the loop's eager ``jax.random.fold_in``, two small device
programs launched from Python every step.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_rng_ms")
