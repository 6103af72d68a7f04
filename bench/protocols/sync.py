"""Synchronous rounds: every learner starts from the round's global model.

Round ``r``: each learner takes its local steps on its batches
``r*S .. r*S+S-1`` from the global model, uploads its row, and once all rows
are in, the rule folds them and the server step commits the next model.

The global model is held as one float32 row between rounds; each learner
starts from its own unflattened copy, so that besides the initial model,
the global row and the fold's sum, only one learner's parameters or row
are live at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import Readings, change_norms


def run(fl, rounds: int) -> Readings:
    out = Readings(losses=[], changes=[])
    base = fl.flatten(fl.start).astype(jnp.float32)
    for r in range(rounds):
        fold = fl.Fold(fl.settings, base)
        losses = []
        for i in range(fl.n):
            row, val = fl.update(base, i, r * fl.steps)
            losses.append(val)
            fold.add(row, fl.weights[i])
            del row
        g = fl.commit(base, fold)
        del fold
        base = fl.flatten(g).astype(jnp.float32)
        out.losses.append([float(v) for v in jax.device_get(losses)])
        out.changes.append(change_norms(g, fl.start))
        del g
    return out
