"""What the readers of the trainer's ``startup`` and ``slow_step`` events
share (``dtc_tpu/obs/telemetry.py``; the metrics ``setup_*.train`` and
``slow_step*.train``). A program without them — the parent of the PR that
brought them — gives every reader None."""

from __future__ import annotations

from xtrace import profiled_steps

#: The ``startup`` event's phases by the part of ``setup_s`` they belong to;
#: whatever else the event names is the third part, ``setup_warmup_rest_s``.
STATE = ("distributed", "mesh", "model", "state", "restore")
FIRST_STEP = ("step_build", "warmup_first")
#: A slow step's phases other than ``block``.
HOST_PHASES = ("data_wait", "rng", "launch", "other", "between")


def _events(run: dict, etype: str) -> list[dict]:
    return [e for e in (run.get("events") or []) if e.get("etype") == etype]


def event(run: dict) -> dict | None:
    return next(iter(_events(run, "startup")), None)


def phases_s(run: dict, names) -> float | None:
    """Seconds of the named start-up phases."""
    e = event(run)
    if e is None:
        return None
    return sum(e["phases"][k][1] for k in names if k in e["phases"])


def slow_steps(run: dict) -> list[dict] | None:
    """The window's ``slow_step`` events, the steps the profiler touched
    left out; ``[]`` on a run without one, None where the program has no
    detector (no ``step`` event carries ``cpu_s``)."""
    if not any("cpu_s" in e for e in _events(run, "step")):
        return None
    skip = profiled_steps(run)
    return [e for e in _events(run, "slow_step") if e.get("step") not in skip]
