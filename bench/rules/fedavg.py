"""FedAvg: the example-weighted mean of the round's rows.

Folded one row at a time as changes from the global row, so that no more
than one row is held besides the sum.
"""

from __future__ import annotations


class Fold:
    def __init__(self, settings: dict, base):
        self.base = base
        self.acc = None
        self.total = 0.0

    def add(self, row, weight: float) -> None:
        term = weight * (row - self.base)
        self.acc = term if self.acc is None else self.acc + term
        self.total += weight

    def result(self):
        """The aggregated model's row."""
        return self.base + self.acc / self.total
