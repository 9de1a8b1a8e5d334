"""Token streams for the training cells, a pure function of (seed, step).

The order-1 Markov stream of the program's synthetic pipeline: each row
starts at a random token and steps ``next = (31 cur + 17 + noise) % V``
with noise in [0, 4). Every row of every step differs.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, step]))


def markov_tokens(seed: int, step: int, batch: int, seq: int, vocab: int):
    rng = _rng(seed, step)
    cur = rng.integers(0, vocab, size=(batch,), dtype=np.int64)
    noise = rng.integers(0, 4, size=(seq + 1, batch), dtype=np.int64)
    out = np.empty((seq + 1, batch), dtype=np.int64)
    for t in range(seq + 1):
        cur = (31 * cur + 17 + noise[t]) % vocab
        out[t] = cur
    return out.T.astype(np.int32)


def batch(seed: int, step: int, batch_size: int, seq: int, vocab: int) -> dict:
    """``{"tokens", "labels"}`` of shape (batch, seq) for one step."""
    tokens = markov_tokens(seed, step, batch_size, seq, vocab)
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
