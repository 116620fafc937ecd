"""Trainer start-up, program spans: every other phase of the ``startup``
event — ``data``, ``obs``, ``eval_setup``, ``warmup_rest`` (which holds the
recorder's reads of the second and third set-up steps) — so that the four
parts of ``setup_s`` add up to what the phases name."""

from trainer_clock import FIRST_STEP, STATE, event, phases_s


def read(run: dict):
    e = event(run)
    return None if e is None else e["named_s"] - phases_s(run, STATE + FIRST_STEP)
