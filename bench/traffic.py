"""The one generator of every traffic mix: learners' private data from a seed.

A traffic file (``bench/traffic/<name>.json``) fixes the federation's shape:
the number of learners, their examples, the evaluation batch, how many
warm-up rounds the comparison follows, the data's own parameters (noise,
sequence length...), and under ``federation`` the ``FederationEnv`` fields
the run passes as they are (protocol, local steps, batch size, codec, arena
precision, aggregation rule, arena shards...).  The configuration's ``data``
key names the kind of rows its learners hold, ``bench/data/<kind>.py``, whose
``Source`` draws them.

Learner ``i``'s ``k``-th batch is a pure function of ``(seed, i, k)``, so the
plain reference replays exactly what each learner saw, and every batch holds
fresh rows.  Every seed gets the same sizes.
"""

from __future__ import annotations

import numpy as np

from bench import spec

# A stream id per use keeps the draws of one seed independent.
TRUTH, TRAIN, EVAL = 0, 1, 2


def rng(seed: int, *ids: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, *ids])


def make(config: dict, traffic: dict, seed: int):
    """The data source of a configuration's learners under one traffic mix."""
    return spec.part("data", config["data"]).Source(config, traffic, seed)


class LearnerFeed:
    """Learner ``i``'s ``data_fn``: its ``k``-th call returns batch ``k``."""

    def __init__(self, source, learner: int):
        self.source = source
        self.learner = learner
        self.calls = 0

    def __call__(self, size: int):
        out = self.source.batch(self.learner, self.calls, size)
        self.calls += 1
        return out
