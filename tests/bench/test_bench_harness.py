"""Each cell's code path through the harness on the CPU, at a tiny size."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import bench_tiny

from bench import check, counts, harness, trace

KEYS = ["correct", "attempted", "failed", "metrics", "device", "window", "readings", "check"]


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_cell_runs_through_the_harness(cell):
    w = bench_tiny.workload(cell)
    res = harness.run(w, 2**31 + 11, 0.3, False, require_chip=False, log=lambda m: None)
    assert list(res) == KEYS
    json.dumps(res)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0
    assert res["attempted"] == 4 * res["window"]["rounds"] > 0
    assert res["window"]["compiles"] == 0
    assert {k: m["unit"] for k, m in res["metrics"].items()} == {"round_s": "s", "setup_s": "s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert set(res["check"]) <= set(res["readings"]) == set(check.NUMBERS)
    for value, limit in res["check"].values():
        assert 0 <= value <= limit


def test_the_two_departures():
    """Uploads arrive without ``params``; the event log is empty between rounds."""
    w = bench_tiny.workload(bench_tiny.CELLS[0])
    fed = harness.Federation(w, 3, harness.Spans(False))
    seen = []
    ingest = fed.controller.ingest

    def spy(update):
        seen.append((update.params, update.upload))
        return ingest(update)

    fed.controller.ingest = spy
    try:
        for _ in range(2):
            fed.round()
            assert len(fed.engine.event_log) == 0
    finally:
        fed.close()
    assert len(seen) == 8
    assert all(p is None and up is not None for p, up in seen)


def _traced_context(w):
    """What a traced run on a v5e would hand the readers, with made-up times."""
    rounds = [types.SimpleNamespace(train_dispatch_s=0.01, aggregation_s=0.1,
                                    eval_round_s=1.0)] * 3
    spans = harness.Spans(False)
    for name in ("fit", "ingest"):
        spans.wrap(name, lambda: None)()
    programs = {"jit_step": 0.5, "jit_masked_weighted_average": 0.01,
                "jit_masked_fedavg_q8": 0.01, "jit_quantize": 0.01}
    summary = trace.Summary(window_s=30.0, busy_s=0.6, devices=1, program_s=programs,
                            program_calls=dict.fromkeys(programs, 3),
                            idle_by_span={"fit": 29.0})
    return harness.Context(
        w=w, dev={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        timings=rounds, window_s=30.0, spans=spans, setup_compile={"compile_s": 5.0},
        shapes=counts.Shapes(rows=8, width=1 << 20, arena_dtype=w.traffic["federation"]["arena_dtype"]),
        trace=summary)


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_every_per_layer_metric_of_a_cell_is_read(cell):
    w = bench_tiny.workload(cell)
    got = harness.per_layer(_traced_context(w))
    assert list(got) == [m["name"] for m in w.per_layer]
    units = {m["name"]: m["unit"] for m in w.per_layer}
    for name, m in got.items():
        assert m["unit"] == units[name] and m["value"] > 0, (name, m)
    assert got["device_idle_frac"]["value"] == pytest.approx(0.98)


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", bench_tiny.CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_cli_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cli(bench_tiny.ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.ROOT / "bench", tmp_path / "bench")
    shutil.copytree(bench_tiny.ROOT / "tests" / "bench", tmp_path / "tests" / "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cli(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
