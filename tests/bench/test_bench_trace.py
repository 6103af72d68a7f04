"""The reduction from a profiler trace to busy time, idle gaps and programs."""

import pathlib

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)

from bench import trace

MS = 1_000_000  # nanoseconds


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert trace.gaps(trace.clip(busy, 1, 6), 1, 6) == [(3, 5)]


def test_idle_goes_to_the_innermost_open_span():
    spans = [("fit", 0, 10), ("upload", 4, 6)]
    charged = trace.attribute([(3, 5), (8, 12)], spans)
    assert charged == {"fit": 1 + 2, "upload": 1, trace.NO_SPAN: 2}


def test_a_moment_is_charged_once_for_each_idle_device():
    spans = [("upload", 0, 10)]
    # Two devices idle over [2, 4), one of them also over [6, 8).
    charged = trace.attribute([(2, 4), (6, 8), (2, 4)], spans)
    assert charged == {"upload": 2 + 2 + 2}


def test_reduce_averages_idle_over_the_devices_as_busy_time():
    host = [(trace.WINDOW, 0.0, 10 * MS), ("bench.upload", 0.0, 10 * MS)]
    planes = [
        ("/host:CPU", [("python", host)]),
        ("/device:TPU:0", [("XLA Modules", [("jit_step(1)", 0.0, 8 * MS)])]),
        ("/device:TPU:1", [("XLA Modules", [("jit_gather(2)", 0.0, 2 * MS)])]),
    ]
    s = trace.reduce(planes)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.005)
    assert s.idle_by_span == pytest.approx({"upload": 0.005})
    assert s.busy_s + sum(s.idle_by_span.values()) == pytest.approx(s.window_s)


def _planes(device_events, host_events):
    return [
        ("/host:CPU", [("python", host_events)]),
        ("/device:TPU:0", [("XLA Modules", device_events),
                           ("XLA Ops", [("fusion.1", 0.0, 1.0)])]),
    ]


def test_reduce_reads_modules_inside_the_window():
    device_events = [
        ("jit_step(11)", 1 * MS, 2 * MS),
        ("jit_masked_weighted_average(7)", 4 * MS, 1 * MS),
        ("jit_step(11)", 6 * MS, 2 * MS),
        ("jit_step(11)", 20 * MS, 2 * MS),  # after the window: not counted
    ]
    host_events = [
        (trace.WINDOW, 0.0, 10 * MS),
        ("bench.fit", 0.0, 3 * MS),
        ("bench.ingest", 3 * MS, 5 * MS),
        ("other", 0.0, 10 * MS),
    ]
    s = trace.reduce(_planes(device_events, host_events))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)
    assert s.program_s == pytest.approx({"jit_step": 0.004,
                                         "jit_masked_weighted_average": 0.001})
    assert s.program_calls == {"jit_step": 2, "jit_masked_weighted_average": 1}
    assert s.program_time("masked_weighted_average") == pytest.approx((0.001, 1))
    # Idle: [0,1) fit, [3,4) ingest, [5,6) ingest, [8,10) no span.
    assert s.idle_by_span == pytest.approx(
        {"fit": 0.001, "ingest": 0.002, trace.NO_SPAN: 0.002})
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_step", pytest.approx(0.004)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_without_a_window_fails():
    with pytest.raises(ValueError):
        trace.reduce(_planes([], [("bench.fit", 0.0, 1.0)]))


RECORDED = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"


def test_reduce_a_recorded_tpu_trace():
    """Three calls of two jitted lambdas, traced on one v5e chip (TPU v5 lite)
    inside a ``bench.window`` span, under ``bench.matmul`` and ``bench.scale``."""
    s = trace.reduce(trace.read_planes(str(RECORDED)))
    assert s.devices == 1
    assert s.program_calls["jit__lambda"] == 3
    assert s.window_s == pytest.approx(3.2989035, rel=1e-6)
    assert s.busy_s == pytest.approx(0.000302443, rel=1e-3)
    assert s.idle_by_span["matmul"] == pytest.approx(2.4233028, rel=1e-6)
    assert set(s.idle_by_span) == {"matmul", "scale", trace.NO_SPAN}
