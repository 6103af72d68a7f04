"""The comparison that decides ``correct`` fails its control and its faults.

At a size a CPU test can hold: the control (the reference put in the
program's place, one precision step below the configuration's) must fail a
cell's limits, and a run with the timed path broken underneath must come
out not correct, once for each fault a federation cell can have.
"""

import pytest

import bench_tiny

from bench import calibrate, check, harness


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_control_fails_the_limits(cell):
    w = bench_tiny.workload(cell)
    (variant, values), = calibrate.readings(w, 7, variants=("control",))
    ok, shown = check.verdict(values, w.limits)
    assert variant == "control" and not ok, shown


def _unchanged(fed):
    """The server step returns the global model as it was."""
    fed.controller._commit = lambda new_buffer: None


def _half_batch(fed):
    """Every learner's step sees half its batch: the mean over the rest."""
    for learner in fed.controller._learners.values():
        feed = learner._data_fn

        def half(size, feed=feed):
            import jax

            return jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], feed(size))

        learner._data_fn = half


def _upload_altered(fed):
    """One learner's first uploaded value is off by 1.0 where it is produced."""
    channel = fed.controller.channel
    upload = channel.upload

    def altered(buffer, metadata=None, codec=None):
        if (metadata or {}).get("learner_id") == "learner_000":
            buffer = buffer.at[0].add(1.0)
        return upload(buffer, metadata=metadata, codec=codec)

    channel.upload = altered


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "upload_altered": _upload_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    w = bench_tiny.workload(cell)
    res = harness.run(w, 5, 0.1, False, require_chip=False, plant=FAULTS[fault],
                      log=lambda m: None)
    assert res["correct"] is False, res["check"]


def _last_commit_left_out(fed):
    """The last compared round's server step returns the model as it was."""
    commit = fed.controller._commit
    calls = []

    def skip_last(new_buffer):
        calls.append(new_buffer)
        if len(calls) == int(fed.w.traffic["reference_rounds"]):
            return None
        return commit(new_buffer)

    fed.controller._commit = skip_last


@pytest.mark.parametrize("cell", bench_tiny.CELLS[1:])
def test_a_later_commit_left_out_is_not_correct(cell):
    """The f32 MLP cell's limit sits above this fault; the cells sharing `_commit` catch it."""
    w = bench_tiny.workload(cell)
    res = harness.run(w, 5, 0.1, False, require_chip=False, plant=_last_commit_left_out,
                      log=lambda m: None)
    assert res["correct"] is False, res["check"]


def test_unbroken_runs_are_correct():
    w = bench_tiny.workload(bench_tiny.CELLS[0])
    res = harness.run(w, 5, 0.1, False, require_chip=False, log=lambda m: None)
    assert res["correct"] is True, res["check"]
