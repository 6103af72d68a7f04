"""Unified telemetry registry: instruments, shims, and wire reconciliation.

Pins the tentpole's metrics contract:

* :class:`Telemetry` get-or-create semantics (same name ⇒ same instrument,
  kind mismatch raises) and the ``value``/``snapshot`` read surface;
* every deprecated attribute shim (``channel.stats.*``,
  ``controller.dispatch_serializations``, store counters) reads the exact
  same instrument the registry exposes;
* the counters reconcile against exact byte/message counts computed from
  first principles after a real federation run — the same formulas
  ``tests/test_dispatch.py`` asserts on the shims.
"""

import functools
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ArenaStore,
    Channel,
    Controller,
    Counter,
    EvalReport,
    Gauge,
    Histogram,
    Learner,
    LocalUpdate,
    ModelStore,
    SyncProtocol,
    Telemetry,
)
from repro.optim import sgd


def _make_learner(i):
    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    rng = np.random.default_rng(i)
    X = rng.normal(size=(16, 4)).astype(np.float32)
    y = X @ np.ones((4, 1), np.float32)
    return Learner(
        f"l{i}", loss_fn, lambda p, b: {"eval_loss": loss_fn(p, b)},
        lambda bs: (X, y), lambda: (X, y), sgd(0.05), 16,
    )


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------


def test_get_or_create_returns_same_instrument():
    t = Telemetry()
    c1 = t.counter("a.b")
    c2 = t.counter("a.b")
    assert c1 is c2
    c1.add(3)
    assert t.value("a.b") == 3 and isinstance(t.value("a.b"), int)


def test_kind_mismatch_raises():
    t = Telemetry()
    t.counter("x")
    with pytest.raises(ValueError, match="counter"):
        t.gauge("x")
    with pytest.raises(ValueError):
        t.histogram("x")


def test_counter_monotonic():
    c = Counter("n")
    c.add(2)
    c.add(0.5)
    assert c.value == 2.5
    with pytest.raises(ValueError):
        c.add(-1)


def test_gauge_last_set_wins():
    g = Gauge("v")
    g.set(7)
    g.set(3)
    assert g.value == 3


def test_histogram_summary_and_mean():
    h = Histogram("lat")
    assert h.mean == 0.0
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    assert h.mean == pytest.approx(2.0)
    r = h.render()
    assert r == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0, "last": 2.0}


def test_value_default_and_histogram_mean():
    t = Telemetry()
    assert t.value("missing") == 0
    assert t.value("missing", default=None) is None
    t.histogram("h").observe(4.0)
    assert t.value("h") == 4.0


def test_snapshot_is_sorted_jsonable():
    t = Telemetry()
    t.counter("z.last").add(1)
    t.gauge("a.first").set(2)
    t.histogram("m.mid").observe(0.5)
    snap = t.snapshot()
    assert list(snap) == sorted(snap)
    json.dumps(snap)  # JSON-able end to end
    assert t.names() == ["a.first", "m.mid", "z.last"]


# ---------------------------------------------------------------------------
# shims read the registry
# ---------------------------------------------------------------------------


def test_channel_stats_shim_reads_registry():
    ch = Channel()
    ch.send({"w": jnp.zeros((50,), jnp.float32)})
    assert ch.stats.messages == ch.telemetry.value("channel.messages") == 1
    assert ch.stats.bytes_moved == ch.telemetry.value("channel.bytes_moved") == 200
    assert ch.stats.serializations == 1
    assert ch.stats.total_bytes == ch.stats.bytes_moved  # no uploads yet


def test_store_shims_and_bind_telemetry_carries_values():
    store = ModelStore()
    from repro.core import ModelRecord

    store.insert(ModelRecord("l0", 0, jnp.zeros((8,), jnp.float32), 1))
    assert store.total_inserts == 1 and store.bytes_ingested == 32
    shared = Telemetry()
    store.bind_telemetry(shared)
    assert shared.value("store.model.total_inserts") == 1
    assert shared.value("store.model.bytes_ingested") == 32
    store.insert(ModelRecord("l1", 0, jnp.zeros((8,), jnp.float32), 1))
    assert shared.value("store.model.total_inserts") == store.total_inserts == 2


def test_arena_counters_in_registry():
    t = Telemetry()
    arena = ArenaStore(num_params=16, n_max=1, row_align=16, telemetry=t)
    arena.write("a", jnp.zeros((16,), jnp.float32), weight=1.0)
    arena.write("b", jnp.ones((16,), jnp.float32), weight=1.0)  # forces grow
    assert t.value("store.arena.total_writes") == arena.total_writes == 2
    assert t.value("store.arena.bytes_ingested") == arena.bytes_ingested == 128
    assert t.value("store.arena.grow_events") == arena.grow_events == 1


# ---------------------------------------------------------------------------
# reconciliation: registry values == exact wire math after a real run
# ---------------------------------------------------------------------------


def test_federation_counters_reconcile_exactly():
    n, rounds = 3, 2
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1), jnp.float32)})
    for i in range(n):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=rounds)
    ctrl.shutdown()

    tm = ctrl.telemetry
    assert tm is ctrl.channel.telemetry  # one registry for the federation
    down = ctrl.manifest.total_bytes
    row_bytes = 4 * ctrl.arena.padded_params

    # downlink: train + eval fan-out each round, one serialization per model
    # version (round models + the final post-aggregation eval model)
    assert tm.value("channel.messages") == 2 * n * rounds
    assert tm.value("channel.bytes_moved") == 2 * n * rounds * down
    assert tm.value("channel.serializations") == rounds + 1
    assert tm.value("controller.dispatch_serializations") == rounds + 1
    # uplink: one measured upload per train task, flat fast path only
    assert tm.value("channel.upload_messages") == n * rounds
    assert tm.value("channel.upload_serializations") == n * rounds
    assert tm.value("channel.upload_bytes") == n * rounds * row_bytes
    assert tm.value("controller.upload_fallback_packs") == 0
    # store: every upload became one arena row write
    assert tm.value("store.arena.total_writes") == n * rounds
    assert tm.value("store.arena.bytes_ingested") == n * rounds * row_bytes
    # engine: gauges track the final round/version, histograms saw a round
    assert tm.value("controller.model_version") == rounds
    assert tm.value("engine.round_id") == rounds
    assert tm.get("engine.round_s").count == rounds
    assert tm.get("engine.aggregate_s").count == rounds

    # the deprecated shims are views of the same instruments
    stats = ctrl.channel.stats
    assert stats.messages == tm.value("channel.messages")
    assert stats.upload_bytes == tm.value("channel.upload_bytes")
    assert ctrl.dispatch_serializations == tm.value(
        "controller.dispatch_serializations"
    )
    assert ctrl.upload_fallback_packs == 0
    assert ctrl.arena.total_writes == tm.value("store.arena.total_writes")

    # snapshot mirrors value() for every scalar instrument
    snap = tm.snapshot()
    for name in ("channel.messages", "channel.upload_bytes",
                 "controller.dispatch_serializations",
                 "store.arena.total_writes"):
        assert snap[name] == tm.value(name)


def test_per_upload_bytes_are_integral():
    """Mirror of the conformance arithmetic: cumulative upload bytes divide
    evenly into per-upload payloads on the raw codec."""
    n, rounds = 2, 2
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1), jnp.float32)})
    for i in range(n):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=rounds)
    ctrl.shutdown()
    tm = ctrl.telemetry
    per_upload = (tm.value("channel.upload_bytes")
                  / tm.value("channel.upload_messages"))
    assert per_upload == int(per_upload) == 4 * ctrl.arena.padded_params


def test_engine_telemetry_survives_mock_controller():
    """The engine must build a private registry when its controller has no
    telemetry attribute (the mock-controller pattern of engine unit tests)."""
    from repro.core import RoundEngine

    class _Mock:
        pass

    eng = RoundEngine(_Mock())
    assert isinstance(eng.telemetry, Telemetry)
    eng.telemetry.counter("x").add(1)
    eng.shutdown()


# ---------------------------------------------------------------------------
# spans: one timing mechanism, in the registry and in the profiler's trace
# ---------------------------------------------------------------------------


def test_span_folds_into_its_histogram_nests_and_exposes_seconds():
    t = Telemetry()
    with t.span("outer", round=1) as outer:
        assert outer.seconds is None
        with t.span("inner", round=1, learner="l0") as first:
            time.sleep(0.01)
        with t.span("inner", learner=None) as second:
            pass
    assert first.seconds >= 0.01
    assert outer.seconds >= first.seconds + second.seconds
    inner = t.get("inner")
    assert isinstance(inner, Histogram)
    assert inner.count == 2
    assert inner.sum == pytest.approx(first.seconds + second.seconds)
    assert t.snapshot()["outer"]["sum"] == outer.seconds
    # A body that raises is timed too; the error passes through.
    with pytest.raises(KeyError):
        with t.span("outer"):
            raise KeyError("x")
    assert t.get("outer").count == 2
    # ``start`` counts from an earlier reading, taken on any thread.
    t0 = time.perf_counter()
    time.sleep(0.01)
    with t.span("wait", start=t0) as wait:
        pass
    assert wait.seconds >= 0.01 and t.get("wait").sum == wait.seconds


def test_spans_from_many_threads_lose_no_observation():
    import sys
    import threading

    t = Telemetry()
    threads, per = 16, 500

    def work(i):
        for _ in range(per):
            with t.span("engine.task.wait", learner=f"l{i}"):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert t.get("engine.task.wait").count == threads * per


def test_span_lands_in_a_profiler_trace_on_its_own_thread(tmp_path):
    import glob
    import threading

    import jax

    t = Telemetry()

    def work():
        with t.span("learner.fit", round=3, learner="l1"):
            with t.span("learner.steps", round=3, learner="l1"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        worker = threading.Thread(target=work)
        worker.start()
        worker.join()
        with t.span("engine.wait", round=3):
            pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in ("learner.fit", "learner.steps", "engine.wait"):
                    found[ev.name] = ((plane.name, index), dict(ev.stats),
                                      ev.start_ns, ev.end_ns)
    assert set(found) == {"learner.fit", "learner.steps", "engine.wait"}
    fit, steps, wait = found["learner.fit"], found["learner.steps"], found["engine.wait"]
    assert fit[1] == steps[1] == {"round": 3, "learner": "l1"}
    assert wait[1] == {"round": 3}
    # The nested span sits inside its parent on the worker's line; the
    # loop's span is on a line of its own.
    assert fit[0] == steps[0] != wait[0]
    assert fit[2] <= steps[2] and steps[3] <= fit[3]


def test_a_round_records_each_span_and_feeds_the_timers():
    n = 2
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1), jnp.float32)})
    updates = []
    for i in range(n):
        learner = _make_learner(i)
        fit = learner.fit

        def spy(params, task, fit=fit):
            updates.append(fit(params, task))
            return updates[-1]

        learner.fit = spy
        ctrl.register_learner(learner)
    (timings,) = ctrl.engine.run(rounds=1)
    ctrl.shutdown()

    tm = ctrl.telemetry
    counts = {name: tm.get(name).count for name in tm.names()
              if isinstance(tm.get(name), Histogram) and "." in name
              and not name.endswith("_s")}
    assert counts == {
        "channel.broadcast": 2,           # the round's model, then the new one
        "channel.recv": 2 * n,            # train and eval tasks
        "channel.upload": n,
        "channel.recv_upload": n,
        "controller.commit": 1,
        "controller.ingest": n,
        "controller.reduce": 1,
        "controller.screen": n,
        "controller.write": n,
        "engine.aggregate": 1,
        "engine.dispatch": 2,             # train, then eval
        "engine.evaluate": 1,
        "engine.task.wait": 2 * n,        # n per phase
        "engine.wait": n,                 # one per arrival
        "learner.evaluate": n,
        "learner.fit": n,
        "learner.pack": n,
        "learner.steps": n,
    }

    def total(name):
        return tm.get(name).sum

    approx = functools.partial(pytest.approx, rel=1e-9)
    assert tm.value("channel.serialize_s") == approx(total("channel.broadcast"))
    assert tm.value("channel.deserialize_s") == approx(total("channel.recv"))
    assert tm.value("channel.upload_serialize_s") == approx(total("channel.upload"))
    assert tm.value("channel.upload_deserialize_s") == approx(
        total("channel.recv_upload"))
    assert timings.train_dispatch_s + timings.eval_dispatch_s == approx(
        total("engine.dispatch"))
    assert timings.aggregation_s == approx(total("engine.aggregate"))
    assert timings.eval_round_s == approx(total("engine.evaluate"))
    assert sum(u.seconds_per_step * 2 for u in updates) == approx(
        total("learner.steps"))
