"""The one generator of training traffic, driven by a mix's data file.

A mix (``bench/traffic/<name>.json``) fixes the batch, the sequence
length, the cross-step window W, the transport, the token distribution and
the optimizer's settings.  Every seed gets the same sizes; the seed only
draws the token ids.  Step ``t``'s rows come from their own generator
(``[seed, t]``), so every step's rows differ and any step can be drawn again
for the reference.
"""
from __future__ import annotations

import numpy as np


class TokenBatches:
    """Next-token batches: ``tokens`` (B, S) and ``labels`` (B, S), int32.

    Ids follow a Zipf law of exponent ``alpha`` over the vocabulary, the
    ranks mapped to ids by a permutation drawn from the seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        tok = mix["tokens"]
        if tok["distribution"] != "zipf":
            raise ValueError(f"unknown token distribution {tok['distribution']!r}")
        self.batch, self.seq, self.seed = mix["batch"], mix["seq"], seed
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(tok["alpha"])
        self.cdf = np.cumsum(p / p.sum())
        self.ids = np.random.default_rng([seed, 2**32 - 1]).permutation(vocab)

    def step(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        u = np.random.default_rng([self.seed, t]).random(
            (self.batch, self.seq + 1))
        ranks = np.minimum(np.searchsorted(self.cdf, u), len(self.cdf) - 1)
        rows = self.ids[ranks].astype(np.int32)
        return rows[:, :-1], rows[:, 1:]
