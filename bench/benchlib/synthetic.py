"""Token stream made from a seed: the benchmark's copy of the program's
``data/synthetic.py::SyntheticLM``, kept here so that a change to the
program cannot move the traffic the benchmark feeds it.

The stream is an order-1 Markov process over the vocabulary: with
probability ``structure`` the next token is a fixed random successor of the
previous one, otherwise uniform.  Deterministic in ``(seed, cursor)``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import numpy as np


@dataclass
class SyntheticLM:
    vocab_size: int
    seq_len: int
    seed: int = 0
    structure: float = 0.8

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self._succ = rng.integers(0, self.vocab_size,
                                  size=(self.vocab_size,), dtype=np.int64)

    def batch(self, cursor: int, batch_size: int) -> Dict[str, np.ndarray]:
        """Batch ``cursor``: ``tokens`` and next-token ``labels``, both
        int32 ``[batch_size, seq_len]``."""
        rng = np.random.default_rng((self.seed, cursor))
        toks = np.empty((batch_size, self.seq_len + 1), dtype=np.int32)
        toks[:, 0] = rng.integers(0, self.vocab_size, size=batch_size)
        noise = rng.random((batch_size, self.seq_len))
        rand_next = rng.integers(0, self.vocab_size,
                                 size=(batch_size, self.seq_len))
        for t in range(self.seq_len):
            follow = self._succ[toks[:, t]]
            toks[:, t + 1] = np.where(noise[:, t] < self.structure,
                                      follow, rand_next[:, t])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
