"""Trainer loop, program spans on the device's clock:
device idle between two executions of the step program while the host is inside
``train.launch``: the ``train_step(...)`` call, up to the runtime's enqueue.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_launch_ms")
