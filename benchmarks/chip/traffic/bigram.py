"""Token batches for a training job, from the job's parameters and a seed.

The arithmetic is that of a bigram "language": the first token of a row
is uniform over the vocabulary, and each next token is ``(a * cur + b) %
V`` except with probability ``noise`` a fresh uniform token, with ``a``
odd in [7, 33] and ``b`` in [1, V) drawn once from the seed.  It is
computed for all positions at once: between two fresh tokens the chain is
the affine map iterated k times, ``x_{s+k} = (A_k x_s + B_k) % V`` with
``A_k = a^k % V`` and ``B_k = b (1 + a + ... + a^(k-1)) % V`` tabulated
once per job.

``batch(step)`` is a pure function of (seed, step): every step's rows are
new, the same seed gives the same batches, and a sample of them can be
made again after the window for the reference.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class Traffic:
    def __init__(self, job: Dict, vocab: int, seed: int):
        self.rows = job["rows_per_chip"] * job["chips"]
        self.seq = job["seq_len"]
        self.vocab = int(vocab)
        self.noise = float(job["noise"])
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0])
        self.a = int(rng.integers(3, 17)) * 2 + 1
        self.b = int(rng.integers(1, self.vocab))
        V, T = self.vocab, self.seq
        A = np.empty(T, np.int64)
        B = np.empty(T, np.int64)
        A[0], B[0] = 1, 0
        for k in range(1, T):
            A[k] = (A[k - 1] * self.a) % V
            B[k] = (B[k - 1] * self.a + self.b) % V
        self._A, self._B = A, B

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.seq

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rows, T, V = self.rows, self.seq, self.vocab
        rng = np.random.default_rng([self.seed, 1, int(step)])
        fresh = rng.random((rows, T)) < self.noise
        fresh[:, 0] = True
        draw = rng.integers(0, V, size=(rows, T), dtype=np.int64)
        pos = np.arange(T)[None, :]
        start = np.maximum.accumulate(np.where(fresh, pos, 0), axis=1)
        k = pos - start
        base = np.take_along_axis(draw, start, axis=1)
        tokens = (self._A[k] * base + self._B[k]) % V
        return {"tokens": tokens.astype(np.int32)}
