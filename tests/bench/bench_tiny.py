"""Tiny stand-ins of the benchmark's cells, for CPU tests of the harness.

Each keeps its cell's traffic mix, codec, arena and limits, and shrinks the
model and the number of learners so that a run takes seconds on a CPU.
"""

from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

CELLS = ("mlp10m-sync-f32-n50", "qwen2-0.5b-sync-f32-n8", "mlp10m-sync-int8-n50")

SHRINK = {
    "mlp": dict(n_hidden_layers=4, width=32),
    "dense_lm": dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                     num_key_value_heads=2, num_hidden_layers=2, vocab_size=512),
}


def workload(cell: str, **traffic) -> spec.Workload:
    """Cell ``cell`` of ``BENCHMARK.json`` at a size a CPU test can hold."""
    w = spec.load(cell)
    config = dict(w.config, **SHRINK[w.family])
    t = dict(w.traffic, learners=4, **traffic)
    if w.family == "dense_lm":
        t.update(seq_len=32, federation=dict(t["federation"], local_steps=2))
    return spec.Workload(name=w.name, chips=w.chips, config=config, traffic=t,
                         limits=w.limits, end_to_end=w.end_to_end,
                         per_layer=w.per_layer)


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())
