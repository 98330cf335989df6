"""Language-model training batches: token ids drawn independently from a
Zipf law over the whole vocabulary (the id of rank r has weight
(r + 1) ** -zipf_exponent), each batch from the seed and its index; the
labels are the next ids.  Every seed gives batches of the same shape.

Parameters (the mix's file): ``batch``, ``seq``, ``zipf_exponent``."""
from __future__ import annotations

import numpy as np

from bench.common import host_rng


class Source:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.batch_size, self.seq = mix["batch"], mix["seq"]
        w = (np.arange(vocab, dtype=np.float64) + 1.0) ** -mix["zipf_exponent"]
        self.cdf = np.cumsum(w) / w.sum()
        self.vocab, self.seed = vocab, seed

    def batch(self, i: int) -> dict:
        """Batch ``i``: {"tokens", "labels"}, int32 (batch, seq)."""
        u = host_rng(self.seed, 1, i).random((self.batch_size, self.seq + 1))
        ids = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                         self.vocab - 1).astype(np.int32)
        return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
