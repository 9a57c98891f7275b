"""Token rows from the seed: the benchmark's one traffic generator for
training cells.

A copy of the trainer's synthetic stream (``repro.train.data``,
``SyntheticStream.batch``): step ``s`` of seed ``n`` draws
``u ~ U[0, 1)`` from ``numpy.random.default_rng((n, s))`` and takes token
``floor(V * u ** 2.2)``, a Zipf-like skew toward low ids.  Every step's
rows differ, and the same seed gives the same rows.
"""
from __future__ import annotations

import numpy as np

SKEW = 2.2


def tokens(seed: int, step: int, batch: int, seq_len: int,
           vocab: int) -> np.ndarray:
    rng = np.random.default_rng((int(seed), int(step)))
    u = rng.random((batch, seq_len))
    t = (vocab * u ** SKEW).astype(np.int64)
    return np.clip(t, 0, vocab - 1).astype(np.int32)
