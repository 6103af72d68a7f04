"""Housing-style regression rows: the task the paper stress-tests.

13 features, a fixed nonlinear ground truth plus noise, as
``make_housing_data`` in ``repro.data.synthetic`` states it, with the target
scaled to unit size.  Traffic keys: ``noise``, ``eval_batch``.
"""

from __future__ import annotations

import numpy as np

from bench.traffic import EVAL, TRAIN, TRUTH, rng


class Source:
    """Rows ``(x (B, 13), y (B, 1))`` of one fixed nonlinear ground truth."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.seed = seed
        self.n_features = int(config["n_features"])
        self.noise = float(traffic["noise"])
        self.eval_rows = int(traffic["eval_batch"])
        truth = rng(seed, TRUTH)
        self.w1 = truth.normal(size=(self.n_features,)).astype(np.float32)
        self.w2 = truth.normal(size=(self.n_features,)).astype(np.float32)

    def _rows(self, gen: np.random.Generator, n: int):
        x = gen.normal(size=(n, self.n_features)).astype(np.float32)
        y = (x @ self.w1 + 0.5 * np.tanh(x @ self.w2)) / np.sqrt(self.n_features)
        y = y + self.noise * gen.normal(size=(n,))
        return x, y[:, None].astype(np.float32)

    def batch(self, learner: int, k: int, size: int):
        return self._rows(rng(self.seed, TRAIN, learner, k), size)

    def eval_batch(self, learner: int):
        return self._rows(rng(self.seed, EVAL, learner), self.eval_rows)
