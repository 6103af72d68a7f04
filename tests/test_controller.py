"""Controller / scheduler / store / selection / driver behaviour tests."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AsyncProtocol, Channel, Controller, Driver, FederationEnv, Learner,
    ModelRecord, ModelStore, SelectionPolicy, SemiSyncProtocol, SyncProtocol,
    TerminationCriteria, select_learners,
)
from repro.optim import sgd


# ---------------------------------------------------------------------------
# model store
# ---------------------------------------------------------------------------


def _rec(lid, rid, nbytes=64):
    return ModelRecord(
        learner_id=lid, round_id=rid,
        buffer=np.zeros(nbytes // 4, np.float32), num_examples=10,
    )


def test_store_lineage_bounded():
    store = ModelStore(lineage_length=2)
    for r in range(5):
        store.insert(_rec("a", r))
    lin = store.lineage("a")
    assert [x.round_id for x in lin] == [3, 4]
    assert store.latest("a").round_id == 4


def test_store_eviction_never_drops_latest():
    store = ModelStore(lineage_length=3, capacity_bytes=400)
    for lid in ("a", "b"):
        for r in range(3):
            store.insert(_rec(lid, r, nbytes=100))
    # capacity forces eviction of old records but each learner keeps latest
    assert "a" in store and "b" in store
    assert store.latest("a").round_id == 2
    assert store.latest("b").round_id == 2
    assert store.resident_bytes() <= 400


def test_store_select_latest_subset():
    store = ModelStore()
    for lid in ("a", "b", "c"):
        store.insert(_rec(lid, 0))
    recs = store.select_latest(["a", "c", "missing"])
    assert [r.learner_id for r in recs] == ["a", "c"]


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def test_selection_all():
    ids = [f"l{i}" for i in range(10)]
    assert select_learners(SelectionPolicy("all"), ids, 0) == ids


def test_selection_random_deterministic_per_round():
    ids = [f"l{i}" for i in range(10)]
    pol = SelectionPolicy("random", fraction=0.5, seed=1)
    a = select_learners(pol, ids, 3)
    b = select_learners(pol, ids, 3)
    c = select_learners(pol, ids, 4)
    assert a == b and len(a) == 5
    assert a != c  # new round, new cohort (w.h.p.)


def test_selection_stratified_prefers_large():
    ids = ["small", "big"]
    n_ex = {"small": 1, "big": 10_000}
    pol = SelectionPolicy("stratified", fraction=0.5, seed=0)
    picks = [select_learners(pol, ids, r, n_ex)[0] for r in range(50)]
    assert picks.count("big") > 40


# ---------------------------------------------------------------------------
# protocols
# ---------------------------------------------------------------------------


def test_semi_sync_adapts_steps_to_speed():
    proto = SemiSyncProtocol(hyperperiod_s=1.0, default_steps=2)
    fast = proto.make_task(0, {"seconds_per_step": 0.01})
    slow = proto.make_task(0, {"seconds_per_step": 0.5})
    new = proto.make_task(0, {})
    assert fast.local_steps == 100
    assert slow.local_steps == 2
    assert new.local_steps == 2  # no profile yet -> default


def _make_learner(i, delay=0.0):
    W = jnp.ones((4, 1))

    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    rng = np.random.default_rng(i)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = X @ np.ones((4, 1), np.float32)

    def data_fn(bs):
        if delay:
            time.sleep(delay)
        j = rng.integers(0, 64, size=bs)
        return X[j], y[j]

    return Learner(
        f"l{i}", loss_fn, lambda p, b: {"eval_loss": loss_fn(p, b)},
        data_fn, lambda: (X, y), sgd(0.05), 64,
    )


def test_sync_round_reports_all_six_timings():
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i))
    t = ctrl.engine.run(rounds=1)[0]
    ctrl.shutdown()
    row = t.as_row()
    for key in ("train_dispatch_s", "train_round_s", "aggregation_s",
                "eval_dispatch_s", "eval_round_s", "federation_round_s"):
        assert row[key] > 0, key
    # dispatch must be cheaper than the full round (async fire-and-forget)
    assert row["train_dispatch_s"] < row["train_round_s"]
    assert "eval_loss" in t.metrics


def test_async_protocol_produces_updates_and_uses_staleness():
    ctrl = Controller(protocol=AsyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i, delay=0.002 * i))
    hist = ctrl.engine.run(total_updates=9)
    ctrl.shutdown()
    assert len(hist) >= 9
    assert ctrl._model_version >= 9


def test_secure_controller_round_matches_plain():
    def build(secure):
        ctrl = Controller(
            protocol=SyncProtocol(local_steps=3, batch_size=16), secure=secure
        )
        ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
        for i in range(3):
            ctrl.register_learner(_make_learner(i))
        ctrl.engine.run(rounds=1)
        out = np.asarray(ctrl.global_params["w"])
        ctrl.shutdown()
        return out

    plain, sec = build(False), build(True)
    np.testing.assert_allclose(plain, sec, atol=1e-3)


def test_driver_lifecycle_and_termination():
    env = FederationEnv(
        protocol="sync", local_steps=2, batch_size=16,
        termination=TerminationCriteria(max_rounds=3),
    )
    drv = Driver(env)
    learners = [_make_learner(i) for i in range(2)]
    drv.initialize({"w": jnp.zeros((4, 1))}, learners)
    hist = drv.run()
    assert len(hist) == 3
    assert all(not l.alive for l in learners)  # shutdown reached learners


def test_driver_rejects_dead_learner_at_init():
    env = FederationEnv(termination=TerminationCriteria(max_rounds=1))
    drv = Driver(env)
    dead = _make_learner(0)
    dead.shutdown()
    with pytest.raises(RuntimeError):
        drv.initialize({"w": jnp.zeros((4, 1))}, [dead])


def test_channel_counts_bytes_and_virtual_time():
    ch = Channel(bandwidth_gbps=1.0, latency_ms=1.0)
    params = {"w": jnp.ones((1000,), jnp.float32)}
    env = ch.send(params)
    back = ch.recv(env)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.ones(1000, np.float32))
    assert ch.stats.bytes_moved == 4000
    assert ch.stats.messages == 1
    expected_wire = 1e-3 + 4000 * 8 / 1e9
    assert abs(ch.stats.virtual_wire_s - expected_wire) < 1e-9


# ---------------------------------------------------------------------------
# admission screen: one host sync per upload (fused-norm regression)
# ---------------------------------------------------------------------------


class _CountingScalar:
    """A device-scalar proxy that counts host readbacks (`float()` calls)."""

    def __init__(self, value, counter):
        self._value, self._counter = value, counter

    def __float__(self):
        self._counter["readbacks"] += 1
        return float(self._value)


@pytest.mark.parametrize("codec,arena_dtype", [
    ("raw", "f32"), ("int8", "f32"), ("int8", "int8"),
])
def test_admission_screen_single_host_sync_per_upload(codec, arena_dtype):
    """The screen reads back ONE already-fused scalar per upload.

    Regression for the per-upload blocking device sync: the old screen
    launched a fresh full-row `jnp.linalg.norm` and blocked on it for every
    arrival.  Now the norm rides along inside the jitted upload decode
    (`recv_upload(..., with_norm=True)` / `recv_upload_quantized`), so the
    only host sync is one `float()` on a scalar the decode already
    scheduled — asserted here by (a) counting scalar readbacks through a
    proxy and (b) counting launches of the separate-norm program: the raw
    decode's one program is that norm, the int8 decodes fuse it, and any
    extra launch (the controller's fallback) fails the test.
    """
    from repro.core import transport
    from repro.core.learner import LocalUpdate

    ctrl = Controller(
        protocol=SyncProtocol(local_steps=1, batch_size=8),
        channel=Channel(upload_codec=codec),
        store_mode="arena", arena_dtype=arena_dtype,
        admission_control=True,
    )
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    counter = {"readbacks": 0}
    real_recv = ctrl.channel.recv_upload
    real_recv_q = ctrl.channel.recv_upload_quantized

    def spy_recv(envelope, with_norm=False):
        assert with_norm, "admission ingest must fuse the norm into decode"
        row, norm = real_recv(envelope, with_norm=True)
        return row, _CountingScalar(norm, counter)

    def spy_recv_q(envelope, out_params):
        q, s, norm = real_recv_q(envelope, out_params)
        return q, s, _CountingScalar(norm, counter)

    ctrl.channel.recv_upload = spy_recv
    ctrl.channel.recv_upload_quantized = spy_recv_q
    real_norm = transport._row_norm
    launches = {"norm": 0}

    def counting_norm(row):
        launches["norm"] += 1
        return real_norm(row)

    transport._row_norm = counting_norm
    norm_launches = 1 if codec == "raw" else 0
    try:
        rng = np.random.default_rng(0)
        P = ctrl.arena.padded_params
        for k in range(4):
            row = jnp.asarray(rng.normal(size=P), jnp.float32)
            env = ctrl.channel.upload(
                row, metadata={"learner_id": f"l{k % 2}", "round_id": 0})
            before, norms_before = counter["readbacks"], launches["norm"]
            ctrl.ingest(LocalUpdate(
                learner_id=f"l{k % 2}", round_id=0, params=None, buffer=None,
                num_examples=10, metrics={}, seconds_per_step=0.01,
                upload=env,
            ))
            assert counter["readbacks"] - before == 1, \
                "expected exactly one scalar readback per upload"
            assert launches["norm"] - norms_before == norm_launches, \
                "separate per-upload norm launch"
    finally:
        transport._row_norm = real_norm
        ctrl.shutdown()
    if arena_dtype == "int8" and codec == "int8":
        assert ctrl.telemetry.value("engine.uploads.quantized_direct", 0) == 4
