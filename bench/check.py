"""The comparison that decides ``correct``: numbers, each beside its limit.

Three numbers compare a federation's first rounds with the plain reference
(``bench/reference.py``):

* ``loss_gap``: the worst, over rounds and learners, relative gap between
  the training loss a learner reported and the reference's.
* ``update_gap``: the first round's change of the global model (the FedAvg
  pseudo-gradient the server step gets), by the worst leaf: the gap between
  the program's leaf norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf.
* ``change_gap``: the same for the change after the last compared round.
* ``change_gap_median``: that round's gap of the median leaf, where the worst
  leaf swings from seed to seed with the noise of the later rounds.

Leaves whose first-round change in the reference is under a thousandth of
the median leaf's move by rounding alone and are left out of all three.
A cell's limits file gives each number its limit, or ``null`` for a number
it does not compare (``PERF.md`` says why).
"""

from __future__ import annotations

import math
import statistics

from bench.reference import Readings

NUMBERS = ("loss_gap", "update_gap", "change_gap", "change_gap_median")
QUIET = 1e-3


def _leaf_gaps(prog: dict, ref: dict, kept: list[str]) -> list[float]:
    median = statistics.median(ref.values())
    gaps = [abs(prog[p] - ref[p]) / max(ref[p], median) for p in kept]
    return [g if math.isfinite(g) else math.inf for g in gaps]


def numbers(prog: Readings, ref: Readings) -> dict[str, float]:
    first = ref.changes[0]
    if set(prog.changes[0]) != set(first):
        raise ValueError("program and reference leaves differ")
    quiet = QUIET * statistics.median(first.values())
    kept = [p for p, v in first.items() if v >= quiet]
    loss_gap = 0.0
    for lp_round, lr_round in zip(prog.losses, ref.losses, strict=True):
        for lp, lr in zip(lp_round, lr_round, strict=True):
            gap = abs(lp - lr) / abs(lr)
            loss_gap = max(loss_gap, gap if math.isfinite(gap) else math.inf)
    last = _leaf_gaps(prog.changes[-1], ref.changes[-1], kept)
    return {
        "loss_gap": loss_gap,
        "update_gap": max(_leaf_gaps(prog.changes[0], first, kept)),
        "change_gap": max(last),
        "change_gap_median": statistics.median(last),
    }


def verdict(values: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per compared number, ``[value, limit]``."""
    shown = {k: [values[k], float(limits[k]["limit"])] for k in NUMBERS
             if limits[k]["limit"] is not None}
    ok = all(math.isfinite(v) and v <= lim for v, lim in shown.values())
    return ok, shown
