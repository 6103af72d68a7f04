#!/usr/bin/env python3
"""The upper readings of a cell's limits: the control and the planted faults.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 [--variants control,rows]

For each seed the plain reference is put in the program's place, once in
the next precision below the configuration's (the control: every float
state in bfloat16 for a float32 cell; the codec at half its bits, int4 for
int8, for a quantized uplink) and once with each fault planted
(``half_batch``, ``upload_altered``), and each is compared with the
reference as a run compares the program.  A state left unchanged reads
exactly 1 on ``update_gap`` and needs no run.  The ``rows`` variant is a
diagnostic, not a fault: FedAvg folded as the program's reduce computes it,
the weighted mean of the absolute rows, which differs from the reference's
fold of changes only in rounding.  One JSON line per seed and variant.  It
needs one chip, also for a cell on four: the reference runs on one.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAULTS = ("half_batch", "upload_altered")


def control_kw(w) -> dict:
    """The reference one precision step below what the configuration states."""
    import functools

    import jax.numpy as jnp

    from bench import spec

    codec = spec.part("codecs", spec.federation(w.traffic)["upload_codec"])
    if codec.BITS < 32:
        return {"uplink": functools.partial(codec.transmit, bits=codec.BITS // 2)}
    return {"dtype": jnp.bfloat16}


class RowsFold:
    """FedAvg as the program's reduce computes it: sum(w_i * row_i) / sum(w_i)."""

    def __init__(self, settings: dict, base):
        self.acc = None
        self.total = 0.0

    def add(self, row, weight: float) -> None:
        term = weight * row
        self.acc = term if self.acc is None else self.acc + term
        self.total += weight

    def result(self):
        return self.acc / self.total


def variant_kw(w, variant: str) -> dict:
    if variant == "control":
        return control_kw(w)
    if variant == "rows":
        return {"fold": RowsFold}
    return {"fault": variant}


def readings(w, seed: int, variants=("control",) + FAULTS):
    from bench import check, harness

    ref = harness.reference_readings(w, seed)
    for variant in variants:
        got = harness.reference_readings(w, seed, **variant_kw(w, variant))
        yield variant, check.numbers(got, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(("control",) + FAULTS))
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device, harness, spec

    w = spec.load(args.workload)
    harness.configure_jax()
    # The reference replays on one device, whatever the cell's chips.
    dev = device.require_tpu(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        for variant, values in readings(w, seed, args.variants.split(",")):
            print(json.dumps({"workload": w.name, "seed": seed, "variant": variant,
                              "device": dev["kind"], **values}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
