"""Trainer loop, program spans: over the window's ``slow_step`` events the
sum of ``block``'s excess over its own trailing median: what the device, its
driver or the way up held of the slow steps (ms)."""

from trainer_clock import slow_steps


def read(run: dict):
    slow = slow_steps(run)
    return None if slow is None else 1e3 * sum(e["block_excess_s"] for e in slow)
