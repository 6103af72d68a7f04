"""The program's spans read over a window: deltas, means, idle by thread."""

import pytest

import bench_tiny

from bench import spans, trace

MS = 1_000_000  # nanoseconds


def test_window_deltas_and_means():
    before = {"channel.recv": {"count": 2, "sum": 0.5}, "channel.upload_bytes": 10}
    after = {"channel.recv": {"count": 6, "sum": 0.9},
             "controller.commit": {"count": 1, "sum": 0.2},
             "learner.fit": {"count": 0, "sum": 0.0},
             "channel.upload_bytes": 20}
    got = spans.window(before, after)
    assert got == {"channel.recv": (4, pytest.approx(0.4)),
                   "controller.commit": (1, 0.2)}
    assert spans.mean_ms(got, "channel.recv") == pytest.approx(100.0)
    assert spans.mean_ms(got, "learner.fit") is None


def _planes():
    loop = [("engine.wait", 0, 4 * MS), ("controller.ingest", 4 * MS, 1 * MS),
            ("controller.screen", 4 * MS, MS // 2)]
    worker = [("engine.task.wait", 0, 2 * MS), ("channel.recv", 2 * MS, MS),
              ("learner.fit", 3 * MS, 5 * MS), ("learner.steps", 3 * MS, 2 * MS)]
    return [
        ("/host:CPU", [("python", [(trace.WINDOW, 0, 10 * MS)] + loop),
                       ("python", worker),
                       ("python", [("bench.fit", 0, 10 * MS)])]),
        ("/device:TPU:0", [("XLA Modules", [("jit_step(1)", 1 * MS, 1 * MS),
                                            ("jit_step(1)", 6 * MS, 1 * MS)])]),
    ]


def test_idle_goes_to_each_threads_innermost_program_span():
    # Device busy [1, 2) and [6, 7) ms of a [0, 10) ms window.
    idle = spans.idle_by_thread(_planes())
    ms = pytest.approx
    assert idle["idle_s"] == ms(0.008)
    # After 8 ms no thread has a program span open.
    assert idle["no_span_s"] == ms(0.002)
    loop, worker = idle["threads"]
    assert loop["role"] == "loop" and worker["role"] == "worker"
    assert loop["idle_s"] == {"engine.wait": ms(0.003), "controller.screen": ms(0.0005),
                              "controller.ingest": ms(0.0005),
                              spans.NO_SPAN: ms(0.004)}
    assert worker["idle_s"] == {"engine.task.wait": ms(0.001), "channel.recv": ms(0.001),
                                "learner.steps": ms(0.002), "learner.fit": ms(0.002),
                                spans.NO_SPAN: ms(0.002)}
    roles = spans.by_role(idle)
    assert set(roles) == {"loop", "worker"}
    assert roles["worker"]["learner.fit"] == ms(0.002)


def test_idle_by_thread_needs_the_window():
    with pytest.raises(ValueError):
        spans.idle_by_thread(_planes()[1:])


def test_a_tiny_cell_reads_its_spans_over_the_window():
    w = bench_tiny.workload(bench_tiny.pick(codec="raw"))
    out = spans.run(w, 2**31 + 7, 0.3, require_chip=False)
    n = int(w.traffic["learners"]) * out["rounds"]
    assert out["rounds"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(spans.METRICS)
    assert all(v > 0 for v in out["metrics"].values()), out["metrics"]
    counts = {k: c for k, (c, _) in out["spans"].items()}
    assert counts["channel.upload"] == counts["controller.ingest"] == n
    assert counts["channel.recv"] == counts["engine.task.wait"] == 2 * n
    assert counts["controller.commit"] == counts["engine.aggregate"] == out["rounds"]
    check = out["check"]
    assert check["engine.aggregate_s"] == pytest.approx(check["aggregation_s"])
    # The program's spans and the benchmark's wrappers time the same calls.
    assert check["learner.fit_ms"] <= check["fit_ms"]
    assert check["controller.ingest_ms"] <= check["ingest_ms"]


def test_a_counters_delta_is_its_change_and_none_where_absent():
    before = {"channel.upload_bytes": 10, "store.arena.rows": 8,
              "channel.recv": {"count": 1, "sum": 0.1}}
    after = {"channel.upload_bytes": 30, "store.arena.rows": 16, "controller.late": 2.5,
             "channel.recv": {"count": 3, "sum": 0.3}}
    assert spans.delta(before, after, "channel.upload_bytes") == 20
    assert spans.delta(before, after, "store.arena.rows") == 8  # a gauge
    assert spans.delta(before, after, "controller.late") == 2.5  # made in the window
    assert spans.delta(before, after, "channel.recv_transfers") is None
    with pytest.raises(TypeError):
        spans.delta(before, after, "channel.recv")


def test_the_span_tool_keeps_its_keys_on_a_quantized_cell():
    w = bench_tiny.workload(bench_tiny.pick(codec="int8"))
    out = spans.run(w, 2**31 + 5, 0.2, require_chip=False)
    assert list(out) == ["workload", "seed", "device", "rounds", "attempted", "failed",
                         "round_s", "window_s", "busy_s", "metrics", "check", "spans",
                         "idle", "breakdown"]
    assert out["failed"] == 0 and out["attempted"] == 4 * out["rounds"] > 0
    assert set(out["metrics"]) == set(spans.METRICS)
    assert list(out["idle"]) == ["idle_s", "no_span_s", "threads", "by_role"]
