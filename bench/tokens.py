"""Seeded token rows for the step cells.

Row ``r`` of batch ``i`` is a pure function of (seed, i, r): Zipf
distributed ids (text-like marginals, exponent ``a``) taken modulo the
vocabulary, as the program's ``data/pipeline.SyntheticLM`` draws them.
Every row of every batch differs, and the same seed gives the same rows.
"""
from __future__ import annotations

import numpy as np


def rows(seed: int, batch: int, n_rows: int, length: int, vocab: int,
         a: float = 1.3) -> np.ndarray:
    """(n_rows, length) int32 token ids of batch ``batch``."""
    out = np.empty((n_rows, length), np.int32)
    for r in range(n_rows):
        rng = np.random.default_rng([int(seed) % 2**64, batch, r])
        out[r] = rng.zipf(a, length) % vocab
    return out


def lm_batch(seed: int, batch: int, n_rows: int, seq: int, vocab: int,
             a: float = 1.3):
    """{tokens, labels}: ``seq`` inputs and their next tokens."""
    t = rows(seed, batch, n_rows, seq + 1, vocab, a)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}
