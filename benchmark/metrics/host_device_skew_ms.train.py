"""Device, trace:
magnitude of the least shift of the device's timeline that makes every
launch of the step program causal against the host's (enqueue before start,
end before the completion notice). It says how far any reading by eye of host
against device in this trace is off. Both ends of the interval: ``run.json``.
Mean over the kept periods of the traced window (ms a step); ``spans.py``."""

from spans import metric


def read(run: dict):
    return metric(run, "host_device_skew_ms")
