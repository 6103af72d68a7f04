#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees the TPU chips
the cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``: each
number compared, beside its limit.  The same numbers end standard error.
Without a TPU, or when the checkout lacks the program, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program at {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device, harness, spec

    def log(msg: str) -> None:
        print(f"bench: {msg}", file=sys.stderr, flush=True)

    try:
        workload = spec.load(args.workload)
        result = harness.run(workload, args.seed, args.seconds, bool(args.trace), log=log)
    except (spec.SpecError, device.NoChip) as e:
        log(str(e))
        return 2
    for name, value in result["readings"].items():
        if name not in result["check"]:
            log(f"reading {name} {value!r} (not compared)")
    for name, (value, limit) in result["check"].items():
        log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
