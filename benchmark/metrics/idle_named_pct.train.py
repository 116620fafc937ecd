"""Trainer loop, program spans on the device's clock:
the share of the device's idle time between executions of the step program
that fell under any ``train.*`` span, in percent. Healthy at 95 or more: below
that the loop does work that no phase of ``StepClock`` covers.
Mean over the kept periods of the traced window (percent); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "idle_named_pct")
