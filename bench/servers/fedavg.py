"""The FedAvg server step: the aggregated model, or a step of ``server_lr`` toward it."""

from __future__ import annotations


def commit(base, aggregated, settings: dict):
    lr = float(settings["server_lr"])
    if lr == 1.0:
        return aggregated
    return base + lr * (aggregated - base)
