"""The raw uplink: the learner's float32 row, bit for bit."""

from __future__ import annotations

BITS = 32


def transmit(row):
    return row
