"""The traffic's token rows, made from the seed by the benchmark itself.

``synthetic_lm`` is the stream that the program's data pipeline
(``SyntheticLM``) is configured to produce: per step, a seeded Zipf draw
over the vocabulary in which 35% of positions copy a fixed function of
the previous token.  The benchmark checks the rows the program fed its
first steps against these, and hands these to the reference.
"""

from __future__ import annotations

import numpy as np


def synthetic_lm(seed: int, step: int, vocab: int, seq_len: int,
                 batch: int, host_id: int = 0) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, step,
                                                        host_id]))
    probs = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    probs = probs / probs.sum()
    shape = (batch, seq_len + 1)
    rows = rng.choice(vocab, size=shape, p=probs)
    follow = (rows * 31 + 7) % vocab
    copy = rng.random(shape) < 0.35
    rows[:, 1:] = np.where(copy[:, 1:], follow[:, :-1], rows[:, 1:])
    return {"tokens": rows[:, :-1].astype(np.int32),
            "targets": rows[:, 1:].astype(np.int32)}
