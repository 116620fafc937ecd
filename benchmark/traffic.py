"""The one traffic generator: every cell's inputs come from its workload
file's ``traffic`` group and the run's seed, and from nothing else.

Kinds (``traffic.kind``):

- ``token_rows`` — an endless stream of ``(rows, seq_len + 1)`` int32 token
  arrays for a training cell. Array ``i`` is a pure function of
  ``(seed, i)``, so the plain reference can ask for the same first arrays
  the program was fed. Tokens follow ``traffic.tokens``: ``zipf`` (exponent
  ``a``, folded into the vocabulary; with probability ``copy_p`` a position
  repeats the token ``copy_back`` places earlier, so rows have the short
  range structure of text and the loss can fall) or ``uniform``. All rows
  of all arrays differ.

A later kind (arrivals and lengths for generation or serving cells) is a new
branch here only if its parameters cannot be said in the existing ones; a
new cell is a new data file.
"""

from __future__ import annotations

import itertools
from typing import Iterator

import numpy as np


def token_rows_at(traffic: dict, vocab_size: int, seq_len: int, seed: int, index: int) -> np.ndarray:
    rows = int(traffic["rows"])
    tok = traffic.get("tokens", {"distribution": "uniform"})
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7, int(index)]))
    shape = (rows, seq_len + 1)
    if tok["distribution"] == "uniform":
        return rng.integers(0, vocab_size, size=shape, dtype=np.int32)
    if tok["distribution"] != "zipf":
        raise ValueError(f"unknown token distribution {tok['distribution']!r}")
    tokens = (rng.zipf(float(tok["a"]), size=shape).astype(np.int64) - 1) % vocab_size
    back, p = int(tok.get("copy_back", 0)), float(tok.get("copy_p", 0.0))
    if back > 0 and p > 0:
        copy = rng.random(shape) < p
        copy[:, :back] = False
        tokens = np.where(copy, np.roll(tokens, back, axis=1), tokens)
    return tokens.astype(np.int32)


def token_rows(traffic: dict, vocab_size: int, seq_len: int, seed: int) -> Iterator[np.ndarray]:
    for i in itertools.count():
        yield token_rows_at(traffic, vocab_size, seq_len, seed, i)


def tokens_per_step(traffic: dict, seq_len: int) -> int:
    return int(traffic["rows"]) * seq_len
