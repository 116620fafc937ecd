"""Trainer loop, program counter: the window's ``slow_step`` events — steps
whose period passed 1.1 x the trailing median of the periods before — the
steps the profiler touched left out. 0 on a run without one; None where the
program has no detector (its ``step`` events carry no ``cpu_s``)."""

from trainer_clock import slow_steps


def read(run: dict):
    slow = slow_steps(run)
    return None if slow is None else len(slow)
