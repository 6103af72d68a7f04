"""Token rows for a decoder: Zipf ids with local bigram copies.

As ``make_lm_data`` in ``repro.data.synthetic`` states it (30% of positions
copy their predecessor + 1), drawn without a per-position Python loop.
Traffic keys: ``seq_len``, ``copy_p``, ``eval_batch``.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import EVAL, TRAIN, rng


class Source:
    """``{"tokens", "labels"}`` of shape ``(B, S)``: Zipf ids, bigram copies."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = seed
        self.vocab = int(config["vocab_size"])
        self.seq_len = int(traffic["seq_len"])
        self.copy_p = float(traffic["copy_p"])
        self.eval_rows = int(traffic["eval_batch"])
        probs = 1.0 / np.arange(1, self.vocab + 1, dtype=np.float64)
        self.cdf = np.cumsum(probs / probs.sum())
        self.cdf[-1] = 1.0

    def _rows(self, gen: np.random.Generator, n: int) -> dict:
        s = self.seq_len + 1
        base = np.searchsorted(self.cdf, gen.random((n, s)), side="right")
        copy = gen.random((n, s)) < self.copy_p
        copy[:, 0] = False
        pos = np.arange(s)
        # A copied position is its predecessor + 1, so a run of copies after
        # the last drawn position j reads base[j] + (t - j).
        last = np.maximum.accumulate(np.where(copy, 0, pos[None, :]), axis=1)
        toks = (np.take_along_axis(base, last, axis=1) + (pos[None, :] - last)) % self.vocab
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def batch(self, learner: int, k: int, size: int) -> dict:
        return self._rows(rng(self.seed, TRAIN, learner, k), size)

    def eval_batch(self, learner: int) -> dict:
        return self._rows(rng(self.seed, EVAL, learner), self.eval_rows)
