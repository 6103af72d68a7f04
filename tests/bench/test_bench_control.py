"""The comparison that decides ``correct`` fails its control and its faults.

At a size a CPU test can hold: the control (the reference put in the
program's place, one precision step below the configuration's) must fail a
cell's limits, and a run with the timed path broken underneath must come
out not correct, once for each fault a federation cell can have.
"""

import pytest

import bench_tiny

from bench import calibrate, check


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_control_fails_the_limits(cell):
    w = bench_tiny.workload(cell)
    (variant, values), = calibrate.readings(w, 7, variants=("control",))
    ok, shown = check.verdict(values, w.limits)
    assert variant == "control" and not ok, shown


CASES = [(cell, fault) for cell in bench_tiny.CELLS for fault in bench_tiny.faults(cell)]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f}" for c, f in CASES])
def test_a_broken_timed_path_is_not_correct(cell, fault):
    res = bench_tiny.run(cell, 5, 0.1, plant=fault)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize(
    "cell", [c for c in bench_tiny.CELLS if bench_tiny.catches_a_left_out_commit(c)])
def test_a_later_commit_left_out_is_not_correct(cell):
    """A change_gap limit of 1 or more (the f32 MLP cell's) passes this fault; the rest catch it."""
    res = bench_tiny.run(cell, 5, 0.1, plant="last_commit_left_out")
    assert res["correct"] is False, res["check"]


def test_unbroken_runs_are_correct():
    res = bench_tiny.run(bench_tiny.pick(codec="raw"), 5, 0.1)
    assert res["correct"] is True, res["check"]
