"""Each cell's code path through the harness on the CPU, at a tiny size."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import bench_tiny

from bench import check, counts, harness, spans, spec, trace

KEYS = ["correct", "attempted", "failed", "metrics", "device", "window", "readings", "check"]


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_cell_runs_through_the_harness(cell):
    w = bench_tiny.workload(cell)
    res = bench_tiny.run(cell, 2**31 + 11, 0.3)
    assert list(res) == KEYS
    json.dumps(res)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0
    assert res["attempted"] == 4 * res["window"]["rounds"] > 0
    assert res["window"]["compiles"] == 0
    assert {k: m["unit"] for k, m in res["metrics"].items()} == {"round_s": "s", "setup_s": "s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == w.chips
    assert set(res["check"]) <= set(res["readings"]) == set(check.NUMBERS)
    for value, limit in res["check"].values():
        assert 0 <= value <= limit


def test_the_two_departures():
    """Uploads arrive without ``params``; the event log is empty between rounds."""
    w = bench_tiny.workload(bench_tiny.pick())
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
    """What a traced run on a v5e would hand the readers, with made-up times.

    The program's telemetry and trace hold what the cell's readers say they
    read: each span of a reader's ``SPANS`` as a histogram, each counter of
    its ``COUNTERS``, each program of its ``PROGRAMS`` in the trace.  A
    reader of a new counter, span or kernel declares it in its own file.
    """
    rounds = [types.SimpleNamespace(train_dispatch_s=0.01, aggregation_s=0.1,
                                    eval_round_s=1.0)] * 3
    bench_spans = types.SimpleNamespace(mean_s=lambda name: 0.01)
    readers = [spec.metric_reader(m["name"]) for m in w.per_layer]
    before, after, programs = {}, {}, {"jit_step": 0.5}
    for r in readers:
        for span in getattr(r, "SPANS", ()):
            before[span] = {"count": 2, "sum": 0.002, "min": 0.001, "max": 0.001, "last": 0.001}
            after[span] = {"count": 26, "sum": 0.05, "min": 0.001, "max": 0.003, "last": 0.002}
        for counter in getattr(r, "COUNTERS", ()):
            before[counter], after[counter] = 4.0e6, 2.8e7
        for prog in getattr(r, "PROGRAMS", ()):
            programs[f"jit_{prog}"] = 0.01
    summary = trace.Summary(window_s=30.0, busy_s=0.6, devices=1, program_s=programs,
                            program_calls=dict.fromkeys(programs, 3),
                            idle_by_span={"fit": 29.0})
    return harness.Context(
        w=w, dev={"platform": "tpu", "kind": "TPU v5 lite", "count": w.chips},
        timings=rounds, window_s=30.0, spans=bench_spans, setup_compile={"compile_s": 5.0},
        shapes=counts.Shapes(rows=8, width=1 << 20,
                             arena_dtype=w.traffic["federation"]["arena_dtype"]),
        before=before, after=after, trace=summary)


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_every_per_layer_metric_of_a_cell_is_read(cell):
    w = bench_tiny.workload(cell)
    got = harness.per_layer(_traced_context(w))
    assert list(got) == [m["name"] for m in w.per_layer]
    units = {m["name"]: m["unit"] for m in w.per_layer}
    for name, m in got.items():
        assert m["unit"] == units[name] and m["value"] > 0, (name, m)
    assert got["device_idle_frac"]["value"] == pytest.approx(0.98)


def test_a_new_readers_counter_reaches_it_with_no_test_edited(monkeypatch):
    """A reader of a counter no cell reads yet, added as its own file, is read."""
    made_up = types.ModuleType("made_up_dropped_share")
    made_up.COUNTERS = ("learner.step.made_up_dropped", "learner.step.made_up_routed")
    made_up.read = lambda ctx: (spans.delta(ctx.before, ctx.after, made_up.COUNTERS[0])
                                / spans.delta(ctx.before, ctx.after, made_up.COUNTERS[1]))
    find = spec.metric_reader
    monkeypatch.setattr(spec, "metric_reader",
                        lambda name: made_up if name == "made_up_dropped_share" else find(name))
    w = bench_tiny.workload(bench_tiny.pick())
    entry = {"name": "made_up_dropped_share", "unit": "ratio"}
    w = dataclasses.replace(w, per_layer=w.per_layer + (entry,))
    got = harness.per_layer(_traced_context(w))
    assert got["made_up_dropped_share"] == {"value": 1.0, "unit": "ratio"}


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", bench_tiny.pick(),
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
