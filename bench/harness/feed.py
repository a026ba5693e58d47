"""Seeded training rows: documents packed back to back.

Each row is ``seq_len + 1`` tokens of documents whose lengths are drawn
lognormal and whose tokens follow a Zipf law over the vocabulary, each
document ended by token 0.  Every seed gives rows of the same shape; the
seed changes only their content.  The system under test receives only
these arrays.
"""
from __future__ import annotations

import numpy as np


def packed_rows(seed: int, salt: int, n_rows: int, seq_len: int,
                vocab: int, *, doc_mean: float, doc_sigma: float,
                zipf_a: float) -> np.ndarray:
    """(n_rows, seq_len + 1) int32 token ids in [0, vocab)."""
    rng = np.random.default_rng([int(seed), int(salt)])
    # one fixed shuffle of the ids, so frequent tokens are not all small
    perm = rng.permutation(vocab - 1) + 1
    out = np.empty((n_rows, seq_len + 1), np.int32)
    mu = np.log(doc_mean) - doc_sigma ** 2 / 2
    for r in range(n_rows):
        row, n = [], 0
        while n < seq_len + 1:
            length = max(int(rng.lognormal(mu, doc_sigma)), 1)
            ranks = (rng.zipf(zipf_a, length) - 1) % (vocab - 1)
            row.append(perm[ranks])
            row.append(np.zeros(1, np.int64))
            n += length + 1
        out[r] = np.concatenate(row)[:seq_len + 1]
    return out


def train_batches(seed: int, n_batches: int, batch: int, seq_len: int,
                  vocab: int, traffic) -> np.ndarray:
    """(n_batches, batch, seq_len + 1): every row differs from every
    other."""
    return np.stack([packed_rows(seed, i, batch, seq_len, vocab,
                                 doc_mean=traffic["doc_mean"],
                                 doc_sigma=traffic["doc_sigma"],
                                 zipf_a=traffic["zipf_a"])
                     for i in range(n_batches)])
