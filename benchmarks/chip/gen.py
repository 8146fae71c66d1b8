"""Seeded inputs: the run seed as numpy and JAX seeds, and the token rows
of training traffic (uniform ids, one row per seed, global step and batch
row, so every row of a run differs).  The seed draws ids, never sizes or
step counts, so every seed does the same work and runs of different seeds
compare.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def seed_words(seed: int) -> List[int]:
    """A run seed of any size as 32-bit words for numpy's SeedSequence."""
    seed = int(seed) % (1 << 64)
    return [seed & 0xFFFFFFFF, seed >> 32]


def jax_seed(seed: int, stream: int = 0) -> int:
    """A non-negative 31-bit seed for ``jax.random.key``, derived from the
    run seed and a stream number (weights 0, sampling 1, ...)."""
    ss = np.random.SeedSequence(seed_words(seed) + [stream])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed) + [int(s) for s in stream])


# ---------------------------------------------------------------------------
# training rows
# ---------------------------------------------------------------------------

def train_rows(seed: int, step: int, batch: int, seq: int,
               vocab: int) -> np.ndarray:
    """(batch, seq + 1) uniform token ids for global step ``step``."""
    return rng(seed, 1, step).integers(0, vocab, (batch, seq + 1),
                                       dtype=np.int32)


def train_batch(seed: int, step: int, workers: int, batch: int, seq: int,
                vocab: int) -> Dict[str, np.ndarray]:
    """The program's batch layout: (K, B, S) tokens and next-token labels;
    worker w of step s reads rows drawn for global row index s·K + w."""
    rows = np.stack([train_rows(seed, step * workers + w, batch, seq, vocab)
                     for w in range(workers)])
    return {"tokens": rows[..., :-1], "labels": rows[..., 1:]}
