"""Seeded packed-token batches: the language model's synthetic source.

Every sequence is `seq_len + 1` ids drawn uniformly from the vocabulary
rows the model holds (a sliced vocabulary is a smaller vocabulary: the ids
come from the slice), packed with no padding and no document boundary. The
step reads inputs `[:, :-1]` and next-token targets `[:, 1:]`.
"""

from __future__ import annotations

import numpy as np


class SyntheticTokens:
    """Iterator of {'tokens': int32[batch, seq_len + 1]} numpy batches, a
    pure function of (seed, draw count): `restore_state` seeks by
    re-deriving the generator, the shared iterator-state contract of
    data/iterator_state.py."""

    supports_state = True

    def __init__(self, batch_size: int, seq_len: int, vocab_size: int,
                 seed: int = 0):
        if seq_len < 1 or vocab_size < 2:
            raise ValueError(f"seq_len {seq_len}, vocab_size {vocab_size}")
        self.batch_size, self.seq_len = batch_size, seq_len
        self.vocab_size, self._seed = vocab_size, seed
        self._rng = np.random.default_rng(seed)

    def restore_state(self, step: int) -> bool:
        """Seek so the NEXT draw is the `step`-th (0-based) of the stream."""
        if int(step) < 0:
            return False
        self._rng = np.random.default_rng(self._seed)
        for _ in range(int(step)):
            next(self)
        return True

    def __iter__(self):
        return self

    def __next__(self):
        return {"tokens": self._rng.integers(
            0, self.vocab_size, (self.batch_size, self.seq_len + 1),
            dtype=np.int32)}
