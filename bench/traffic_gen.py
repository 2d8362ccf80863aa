"""The one traffic generator: every mix is a data file that this reads.

A training mix gives the batch, the sequence length and the token
distribution.  The seed changes which tokens are drawn, never the shapes:
every seed does the same work.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np



def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *salt])


class TrainSource:
    """Batches of next-token rows, each row drawn anew from the seed and
    the step: ids follow a Zipf law over the vocabulary (rank order
    shuffled once per seed), so the early steps have a unigram to learn.
    Iterating continues from the last batch handed out."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.batch_size = int(mix["batch"])
        self.seq_len = int(mix["seq_len"])
        self.vocab = vocab
        self.seed = seed
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(mix.get("zipf_alpha", 1.0))
        self._cdf = np.cumsum(p / p.sum())
        self._ids = _rng(seed, 1).permutation(vocab).astype(np.int32)
        self._next = 0

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        u = _rng(self.seed, 2, step).random(
            (self.batch_size, self.seq_len + 1))
        x = self._ids[np.minimum(np.searchsorted(self._cdf, u),
                                 self.vocab - 1)]
        return {"tokens": x[:, :-1].copy(), "targets": x[:, 1:].copy()}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        b = self.batch(self._next)
        self._next += 1
        return b

