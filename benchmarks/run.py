"""Benchmark harness entry point — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

| benchmark       | paper artifact                  |
|-----------------|---------------------------------|
| bench_agg       | §4.2 OpenMP-vs-none 10x claim   |
| bench_ops       | Figs. 5/6/7 per-op comparison   |
| bench_round     | Table 2 federation round times  |
| bench_transport | dispatch/serialization share    |
| roofline_table  | §Roofline (from dry-run jsonl)  |

Prints ``name,...`` CSV lines; writes experiments/bench_results.json.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import bench_agg, bench_ops, bench_round, bench_transport, roofline_table
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweep (slow)")
    args = ap.parse_args()
    enable_compile_cache()

    results = {}
    print("# bench_agg (paper §4.2 parallel-aggregation claim)")
    results["agg"] = bench_agg.run(
        sizes=("100k", "1m", "10m"),
        learner_counts=(10, 25, 50, 100, 200) if args.full else (10, 25, 50),
        iters=3,
    )
    print("\n# bench_transport (flat-tensor wire format)")
    results["transport"] = bench_transport.run()
    print("\n# bench_ops (Figs. 5/6/7)")
    results["ops"] = bench_ops.run(
        sizes=("100k", "1m", "10m") if args.full else ("100k", "1m"),
        learner_counts=(10, 25, 50, 100, 200) if args.full else (10, 25),
    )
    print("\n# bench_round (Table 2)")
    results["round"] = bench_round.run(
        learner_counts=(10, 25, 50, 100, 200) if args.full else (10, 25),
        size="10m",
    )
    print("\n# roofline (from dry-run records, if present)")
    print(roofline_table.summarize())

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(results, f, indent=1)
    print("\nwrote experiments/bench_results.json")


if __name__ == "__main__":
    main()
