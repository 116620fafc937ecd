"""Trainer loop, program spans on the device's clock:
device idle between two executions of the step program while the host is in
the rest of the loop: ``train.data_wait``, ``train.obs`` (the telemetry's own
work), ``train.tail`` and what of ``train.dispatch`` is neither rng nor launch.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_loop_ms")
