"""Synchronous rounds: every learner starts from the round's global model.

Round ``r``: each learner takes its local steps on its batches
``r*S .. r*S+S-1`` from the global model, uploads its row, and once all rows
are in, the rule folds them and the server step commits the next model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import Readings, change_norms


def run(fl, rounds: int) -> Readings:
    out = Readings(losses=[], changes=[])
    g = fl.start
    for r in range(rounds):
        base = fl.flatten(g).astype(jnp.float32)
        fold = fl.Fold(fl.settings, base)
        losses = []
        for i in range(fl.n):
            p, val = fl.train(g, i, r * fl.steps)
            losses.append(val)
            fold.add(fl.upload(p, i), fl.weights[i])
        g = fl.commit(base, fold)
        out.losses.append([float(v) for v in jax.device_get(losses)])
        out.changes.append(change_norms(g, fl.start))
    return out
