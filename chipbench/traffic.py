"""The one traffic generator: token batches drawn from a workload file's
parameters and ``--seed``.

Every batch is a pure function of (seed, step), made on the host with
numpy, so the reference regenerates exactly the rows the program trained
on without taking anything from the program.  Labels are the next token at
every position (a sequence of ``seq_len + 1`` tokens is drawn), so every
position carries loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def seed_words(seed: int, n: int = 4) -> Tuple[int, ...]:
    """``n`` 32-bit words from a seed of any size (seeds may pass
    2**31)."""
    return tuple(int(w) for w in
                 np.random.SeedSequence(int(seed)).generate_state(n))


class Traffic:
    """``batch_at(step)`` -> {"tokens", "labels"}: (batch, seq_len) int32."""

    def __init__(self, params: Dict, *, vocab: int, batch: int,
                 seq_len: int, seed: int):
        tokens = params["tokens"]
        if tokens["dist"] != "zipf":
            raise ValueError(f"unknown token distribution {tokens['dist']!r}")
        self.vocab, self.batch, self.seq_len = vocab, batch, seq_len
        self.seed = int(seed)
        # Zipf unigram law over the vocabulary; which id gets which rank is
        # a permutation drawn from the seed.
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(tokens["exponent"])
        self._cdf = np.cumsum(p / p.sum())
        self._ids = np.random.default_rng(
            np.random.SeedSequence([self.seed, 0])
        ).permutation(vocab).astype(np.int32)

    def batch_at(self, step: int, *, half: bool = False
                 ) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1, int(step)])
        )
        u = rng.random((self.batch, self.seq_len + 1))
        rank = np.minimum(np.searchsorted(self._cdf, u), self.vocab - 1)
        seq = self._ids[rank]
        labels = seq[:, 1:].copy()
        if half:
            # a fault for the correctness check's tests: the second half of
            # every sequence carries no loss, so the mean runs over the rest
            labels[:, self.seq_len // 2:] = -1
        return {"tokens": np.ascontiguousarray(seq[:, :-1]),
                "labels": labels}
