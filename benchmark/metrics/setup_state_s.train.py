"""Trainer start-up, program spans: the ``startup`` event's phases
``distributed`` + ``mesh`` + ``model`` + ``state`` + ``restore`` (the
benchmark's weights are made inside ``state``, through its seam)."""

from trainer_clock import STATE, phases_s


def read(run: dict):
    return phases_s(run, STATE)
