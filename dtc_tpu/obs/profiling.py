"""Profiler trace capture around a training-step window (hardened).

Two failure modes that would kill a run warn-and-disable instead:

- a profiler session already active in the process (an outer harness, a
  previous run that leaked its session) — ``start_trace`` raises;
- an unwritable ``log_dir`` — ``start_trace`` validates nothing, so this
  surfaces as a ``FAILED_PRECONDITION`` from ``stop_trace``; worse, the
  failed stop leaves jax's module-global profile session marked active,
  wedging every later ``start_trace`` in the process. On a failed stop we
  therefore best-effort reset that state so one bad log dir doesn't
  disable profiling for the process lifetime.

Telemetry must never take down the training it observes.
"""

from __future__ import annotations

import jax


def _reset_wedged_session() -> None:
    """A stop_trace that raises (e.g. unwritable log_dir) leaves jax's
    module-global profile session marked active — permanently failing
    every later start_trace in the process. Clear it, best-effort."""
    try:
        from jax._src.profiler import _profile_state

        _profile_state.reset()
    except Exception:
        pass


class StepWindowProfiler:
    def __init__(self, start_step: int, stop_step: int, log_dir: str):
        self.start = start_step
        self.stop = stop_step
        self.log_dir = log_dir
        self._active = False
        self.enabled = stop_step > start_step
        self.failed: str | None = None

    def _disable(self, what: str, e: Exception) -> None:
        self.failed = f"{type(e).__name__}: {e}"
        self.enabled = False
        self._active = False
        print(
            f"[dtc_tpu] WARNING: profiler {what} failed ({self.failed}); "
            "disabling trace capture for this run"
        )
        if what == "stop_trace":
            _reset_wedged_session()

    def step(self, step: int) -> None:
        if not self.enabled:
            return
        if step == self.start and not self._active:
            try:
                jax.profiler.start_trace(self.log_dir)
                self._active = True
            except Exception as e:  # already active / unwritable log_dir
                self._disable("start_trace", e)
        elif step == self.stop and self._active:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self._disable("stop_trace", e)
            self._active = False

    def close(self) -> None:
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                self._disable("stop_trace", e)
            self._active = False
