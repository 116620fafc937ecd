"""Trainer start-up, program counter: ``backend_compile_s -
cache_retrieval_s`` of the ``startup`` event: what this run compiled anew
(``backend_compile_duration`` wraps ``compile_or_get_cached``, so it holds a
hit's retrieval: JAX 0.9.0, ``jax/_src/compiler.py``)."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None else e["backend_compile_s"] - e["cache_retrieval_s"]
