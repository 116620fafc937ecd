"""Trainer start-up, program counter: ``trace_s + lower_s`` of the
``startup`` event (``jax.monitoring``'s ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration``): the host's Python and MLIR work before the
first timed step, which grows with the tree."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None else e["trace_s"] + e["lower_s"]
