"""Trainer loop, program counter: over the window's ``slow_step`` events
the sum of ``host_late_s``, the canary's largest lateness inside each: how
much of the excess the host itself slept through (ms)."""

from trainer_clock import slow_steps


def read(run: dict):
    slow = slow_steps(run)
    return None if slow is None else 1e3 * sum(e["host_late_s"] for e in slow)
