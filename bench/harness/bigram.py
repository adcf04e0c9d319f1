"""Seeded bigram token batches for training cells (a copy of the
program's ``repro.data.synthetic.BigramLM`` generator, kept with the
benchmark so that it cannot move under a later change to the program):
tokens follow a fixed random table of ``branching`` successors per token,
with a ``noise`` share of uniform-random tokens."""
from __future__ import annotations

import numpy as np


class BigramLM:
    def __init__(self, vocab_size: int, seed: int, branching: int = 8,
                 noise: float = 0.05):
        self.vocab_size, self.branching, self.noise = \
            vocab_size, branching, noise
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 11])
        self.table = rng.integers(0, vocab_size, size=(vocab_size, branching))

    def batch(self, step: int, batch_size: int, seq_len: int) -> dict:
        rng = np.random.default_rng([self.seed, 12, step])
        tokens = np.empty((batch_size, seq_len + 1), np.int32)
        tokens[:, 0] = rng.integers(0, self.vocab_size, batch_size)
        choice = rng.integers(0, self.branching, (batch_size, seq_len))
        noise_mask = rng.random((batch_size, seq_len)) < self.noise
        noise_tok = rng.integers(0, self.vocab_size, (batch_size, seq_len))
        for t in range(seq_len):
            nxt = self.table[tokens[:, t], choice[:, t]]
            tokens[:, t + 1] = np.where(noise_mask[:, t], noise_tok[:, t],
                                        nxt)
        return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
