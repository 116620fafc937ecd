"""Trainer loop, program spans: over the window's ``slow_step`` events the
sum of every other phase's excess over its own trailing median —
``data_wait``, ``rng``, ``launch``, ``other`` and ``between`` (its first
reader): what the host's phases held of the slow steps (ms)."""

from trainer_clock import HOST_PHASES, slow_steps


def read(run: dict):
    slow = slow_steps(run)
    if slow is None:
        return None
    return 1e3 * sum(e[f"{p}_excess_s"] for e in slow for p in HOST_PHASES)
