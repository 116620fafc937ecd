"""Trainer start-up, program counter: ``cache_retrieval_s`` of the
``startup`` event (``/jax/compilation_cache/cache_retrieval_time_sec``): what a
warm run pays for the programs the persistent cache holds."""

from trainer_clock import event


def read(run: dict):
    e = event(run)
    return None if e is None else e["cache_retrieval_s"]
