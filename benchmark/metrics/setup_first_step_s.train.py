"""Trainer start-up, program spans: the ``startup`` event's phases
``step_build`` + ``warmup_first``: building the step and its first call up to
its loss fetched — trace, lower, compile or cache load, first execution (and
the recorder's reads of that step)."""

from trainer_clock import FIRST_STEP, phases_s


def read(run: dict):
    return phases_s(run, FIRST_STEP)
