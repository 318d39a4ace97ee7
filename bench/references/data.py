"""The token rows of the benchmark's traffic, regenerated for the reference.

The timed path is fed by the program's own loader; this copy of its
arithmetic (one NumPy generator per (seed, step, row), uniform token ids)
lets the reference see the same rows without taking anything the program
made.  A loader that feeds other rows makes `correct` false.
"""
from __future__ import annotations

import numpy as np


def token_rows(seed: int, step: int, rows: int, seq_len: int,
               vocab: int) -> np.ndarray:
    """[rows, seq_len] int32 token ids of the global batch of `step`."""
    return np.stack([
        np.random.default_rng(np.random.SeedSequence([seed, step, r]))
        .integers(0, vocab, seq_len, dtype=np.int32)
        for r in range(rows)])
