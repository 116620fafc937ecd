"""Seeded open-loop arrival generation for the pool.

One generator for the seeded Poisson arrival schedule and the seeded
prompt set means a pool smoke and a chaos drill draw from the same
distribution and a seed reproduces either bit-for-bit.

The draw ORDER is part of the contract: arrivals first, then prompts,
from one ``np.random.RandomState(seed)``.
"""

from __future__ import annotations

import numpy as np


def poisson_arrivals(
    rng: np.random.RandomState, n_requests: int, rps: float | None
) -> np.ndarray:
    """Cumulative arrival offsets (seconds from window start) for an
    open-loop Poisson process at ``rps`` requests/second. ``rps=None``
    is the closed-loop degenerate case: everything arrives at t=0."""
    if rps is None:
        return np.zeros(n_requests)
    return np.cumsum(rng.exponential(1.0 / rps, size=n_requests))


def seeded_prompts(
    rng: np.random.RandomState, n_requests: int, prompt_len: int,
    vocab_size: int,
) -> list[list[int]]:
    """``n_requests`` uniform-random token prompts of ``prompt_len``."""
    return [
        rng.randint(0, vocab_size, size=prompt_len).tolist()
        for _ in range(n_requests)
    ]


def arrival_schedule(
    seed: int, n_requests: int, prompt_len: int, vocab_size: int,
    rps: float | None,
) -> tuple[np.ndarray, list[list[int]]]:
    """The benches' full request material: ``(arrivals, prompts)`` from
    one seeded RNG (arrivals drawn first — see module docstring)."""
    rng = np.random.RandomState(seed)
    arrivals = poisson_arrivals(rng, n_requests, rps)
    prompts = seeded_prompts(rng, n_requests, prompt_len, vocab_size)
    return arrivals, prompts
