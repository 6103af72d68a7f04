"""Event-driven round engine: event grammar, policy hooks, wire-aware sizing.

Covers the engine refactor's acceptance surface:

* the 16-thread ``UploadArrived`` out-of-order hammer — arrival order must
  not change when aggregation fires or what it computes;
* the event-log grammar of a round (Dispatched* → UploadArrived* →
  AggregateFired → Evaluated);
* ``prox_mu`` plumbed through all three protocol policies (FedProx is
  reachable from protocol config);
* EWMA learner profiles (convergence, noise damping, legacy decay=0);
* wire-cost-aware semi-sync sizing (budget covers train + round-trip wire);
* secure + async: staleness-damped masked community updates in per-epoch
  mask sessions.
"""

import random
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AggregateFired,
    AsyncProtocol,
    Channel,
    Controller,
    Dispatched,
    EvalReport,
    Evaluated,
    FederationEnv,
    Learner,
    LearnerProfile,
    LocalUpdate,
    SemiSyncProtocol,
    SyncProtocol,
    UploadArrived,
)
from repro.core import secure as secure_mod
from repro.optim import sgd


def _make_learner(i):
    def loss_fn(p, b):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2)

    rng = np.random.default_rng(i)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = X @ np.ones((4, 1), np.float32)

    def data_fn(bs):
        j = rng.integers(0, 64, size=bs)
        return X[j], y[j]

    return Learner(
        f"l{i}", loss_fn, lambda p, b: {"eval_loss": loss_fn(p, b)},
        data_fn, lambda: (X, y), sgd(0.05), 64,
    )


# ---------------------------------------------------------------------------
# event-ordering hammer
# ---------------------------------------------------------------------------


class _GatedLearner(Learner):
    """A learner whose fit() blocks until the test releases its gate, then
    uploads a constant-valued pre-packed row — so 16 executor threads post
    their ``UploadArrived`` events in exactly the (shuffled) release order."""

    def __init__(self, lid, value, gate, pad_to):
        dummy = lambda *a, **k: None  # noqa: E731
        super().__init__(lid, dummy, dummy, dummy, dummy, sgd(0.1), 1)
        self._value = value
        self._gate = gate
        self._pad_to = pad_to

    def fit(self, params, task):
        self._gate.wait(timeout=30)
        return LocalUpdate(
            learner_id=self.learner_id, round_id=task.round_id,
            params=None, num_examples=1, metrics={}, seconds_per_step=1e-4,
            buffer=jnp.full((self._pad_to,), float(self._value), jnp.float32),
        )

    def evaluate(self, params, round_id):
        return EvalReport(self.learner_id, round_id, {"eval_loss": 0.0}, 1)


def test_event_ordering_hammer_16_threads():
    """16 concurrent workers posting UploadArrived in a shuffled order: the
    engine must ingest all of them, fire aggregation exactly once per round,
    and produce the order-independent exact mean."""
    n = 16
    # admission_control off: the screen's norm EWMA warms up in *arrival*
    # order, so with 15x-heterogeneous row norms (0..480) an unlucky
    # interleaving clips the largest row — exactly the order dependence
    # this test asserts the aggregation itself does not have.
    ctrl = Controller(
        protocol=SyncProtocol(local_steps=1, batch_size=1),
        max_dispatch_workers=n, arena_n_max=n, admission_control=False,
    )
    ctrl.set_initial_model({"w": jnp.zeros((8,), jnp.float32)})
    gates = {}
    for i in range(n):
        gates[f"l{i}"] = threading.Event()
        ctrl.register_learner(
            _GatedLearner(f"l{i}", i, gates[f"l{i}"], 1024)
        )

    rng = random.Random(0)
    releaser_done = threading.Event()

    def release_shuffled():
        # Scramble arrival order: all 16 fits are blocked on their gates in
        # executor threads; release them in a random permutation.
        order = list(gates)
        rng.shuffle(order)
        for lid in order:
            gates[lid].set()
        releaser_done.set()

    rounds = 3
    for r in range(rounds):
        for g in gates.values():
            g.clear()
        releaser_done.clear()
        threading.Thread(target=release_shuffled, daemon=True).start()
        (t,) = ctrl.engine.run(rounds=1)
        assert releaser_done.wait(timeout=30)
        # one aggregation per round, every upload ingested, exact mean:
        # values 0..15 with equal weights -> (0+..+15)/16 = 7.5 in any
        # summation order (exact in float32)
        assert ctrl.engine.aggregates_fired == r + 1
        assert ctrl.arena.total_writes == n * (r + 1)
        np.testing.assert_array_equal(
            np.asarray(ctrl.global_params["w"]), np.full((8,), 7.5, np.float32)
        )
        assert t.metrics == {"eval_loss": 0.0}
    ctrl.shutdown()

    # event-log grammar for the last round: 16 UploadArrived all precede the
    # AggregateFired, which precedes the Evaluated
    log = list(ctrl.engine.event_log)
    last_agg = max(i for i, e in enumerate(log) if isinstance(e, AggregateFired))
    arrivals = [i for i, e in enumerate(log) if isinstance(e, UploadArrived)]
    assert sum(1 for i in arrivals if last_agg - 17 < i < last_agg) == n
    assert isinstance(log[last_agg + 1], Evaluated)
    dispatched = [e for e in log if isinstance(e, Dispatched)]
    assert len(dispatched) == n * rounds


class _OverlapLearner(Learner):
    """Counts the learner tasks (fit or evaluate) running at once.

    Each fit waits up to ``linger_s`` for all ``n`` learners to be inside
    one, so tasks that may overlap do, and tasks held one at a time finish.
    """

    def __init__(self, lid, live, n, pad_to, linger_s=0.3):
        dummy = lambda *a, **k: None  # noqa: E731
        super().__init__(lid, dummy, dummy, dummy, dummy, sgd(0.1), 1)
        self._live, self._n, self._pad_to = live, n, pad_to
        self._linger_s = linger_s

    def _enter(self):
        with self._live["lock"]:
            self._live["now"] += 1
            self._live["most"] = max(self._live["most"], self._live["now"])

    def _leave(self):
        with self._live["lock"]:
            self._live["now"] -= 1

    def fit(self, params, task):
        self._enter()
        deadline = time.monotonic() + self._linger_s
        while self._live["now"] < self._n and time.monotonic() < deadline:
            time.sleep(0.005)
        self._leave()
        return LocalUpdate(
            learner_id=self.learner_id, round_id=task.round_id,
            params=None, num_examples=1, metrics={}, seconds_per_step=1e-4,
            buffer=jnp.ones((self._pad_to,), jnp.float32),
        )

    def evaluate(self, params, round_id):
        self._enter()
        self._leave()
        return EvalReport(self.learner_id, round_id, {"eval_loss": 0.0}, 1)


@pytest.mark.parametrize("backend, most", [("cpu", 4), ("tpu", 1)])
def test_learner_tasks_one_at_a_time_on_an_accelerator(monkeypatch, backend, most):
    """In-process learners share JAX's default device: on an accelerator the
    engine runs their tasks one at a time, so N model copies never sit in
    device memory at once; on the CPU backend the executor alone bounds
    them."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    n = 4
    live = {"lock": threading.Lock(), "now": 0, "most": 0}
    ctrl = Controller(
        protocol=SyncProtocol(local_steps=1, batch_size=1),
        max_dispatch_workers=n, arena_n_max=n, admission_control=False,
    )
    ctrl.set_initial_model({"w": jnp.zeros((8,), jnp.float32)})
    for i in range(n):
        ctrl.register_learner(_OverlapLearner(f"l{i}", live, n, 1024))
    ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    assert ctrl.arena.total_writes == n
    assert live["most"] == most
    np.testing.assert_array_equal(
        np.asarray(ctrl.global_params["w"]), np.ones((8,), np.float32)
    )


def test_engine_run_argument_contract():
    ctrl = Controller(protocol=SyncProtocol())
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    with pytest.raises(TypeError):
        ctrl.engine.run()  # round-based needs rounds=
    with pytest.raises(TypeError):
        ctrl.engine.run(total_updates=3)  # sync is not continuous
    ctrl.shutdown()

    actrl = Controller(protocol=AsyncProtocol())
    actrl.set_initial_model({"w": jnp.zeros((4, 1))})
    with pytest.raises(TypeError):
        actrl.engine.run(rounds=2)  # continuous needs total_updates=
    assert actrl.engine.run(total_updates=0) == []
    actrl.shutdown()


def test_learner_failure_surfaces_on_engine_thread():
    class _FailingLearner(Learner):
        def fit(self, params, task):
            raise RuntimeError("boom in fit")

    dummy = lambda *a, **k: None  # noqa: E731
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=1))
    ctrl.set_initial_model({"w": jnp.zeros((4,), jnp.float32)})
    ctrl.register_learner(_FailingLearner("bad", dummy, dummy, dummy, dummy,
                                          sgd(0.1), 1))
    with pytest.raises(RuntimeError, match="boom in fit"):
        ctrl.engine.run(rounds=1)
    ctrl.shutdown()


def test_engine_reruns_clean_after_learner_failure():
    """A failed round must not poison the next run(): in-flight tasks are
    drained and stale events discarded, so a retry round sees only its own
    cohort's arrivals and aggregates exactly once."""

    class _FlakyLearner(Learner):
        fail_next = True

        def fit(self, params, task):
            if _FlakyLearner.fail_next:
                _FlakyLearner.fail_next = False
                raise RuntimeError("transient learner failure")
            return super().fit(params, task)

    def flaky(i):
        base = _make_learner(i)
        fl = _FlakyLearner.__new__(_FlakyLearner)
        fl.__dict__.update(base.__dict__)
        return fl

    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(flaky(0))
    for i in range(1, 3):
        ctrl.register_learner(_make_learner(i))
    with pytest.raises(RuntimeError, match="transient learner failure"):
        ctrl.engine.run(rounds=1)
    # retry: the engine must start from a clean queue and outstanding count
    (t,) = ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    assert ctrl.engine.aggregates_fired == 1  # never fired in the bad round
    assert t.federation_round_s > 0 and "eval_loss" in t.metrics
    assert len(ctrl.history) == 1


# ---------------------------------------------------------------------------
# prox_mu: FedProx reachable from protocol config
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "proto",
    [
        SyncProtocol(prox_mu=0.25),
        SemiSyncProtocol(prox_mu=0.25),
        AsyncProtocol(prox_mu=0.25),
    ],
    ids=["sync", "semi_sync", "async"],
)
def test_prox_mu_reaches_train_task(proto):
    """Regression: every policy must stamp its prox_mu on the TrainTask
    (it used to be silently dropped, making FedProx unreachable)."""
    task = proto.size_task(0, {})
    assert task.prox_mu == 0.25
    # the legacy alias goes through the same path
    assert proto.make_task(0, {}).prox_mu == 0.25


def test_prox_mu_plumbed_through_federation_env():
    for name in ("sync", "semi_sync", "async"):
        env = FederationEnv(protocol=name, prox_mu=0.125)
        assert env.make_protocol().size_task(0, {}).prox_mu == 0.125
    assert FederationEnv(protocol="sync").make_protocol().size_task(0, {}).prox_mu == 0.0


def test_prox_mu_federation_runs_and_stays_finite():
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16,
                                            prox_mu=0.1))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=2)
    ctrl.shutdown()
    assert np.isfinite(np.asarray(ctrl.global_params["w"])).all()
    # the dispatched tasks carried the proximal coefficient
    tasks = [e.task for e in ctrl.engine.event_log if isinstance(e, Dispatched)]
    assert tasks and all(t.prox_mu == 0.1 for t in tasks)


# ---------------------------------------------------------------------------
# EWMA learner profiles
# ---------------------------------------------------------------------------


def test_ewma_profile_converges_under_noise():
    """A noisy-but-stationary step time must converge to its mean and the
    estimate's wobble must be far smaller than the observation noise."""
    rng = np.random.default_rng(0)
    prof = LearnerProfile(decay=0.8)
    true = 0.1
    estimates = []
    for _ in range(300):
        prof.observe_step_time(true + rng.uniform(-0.05, 0.05))
        estimates.append(prof["seconds_per_step"])
    tail = np.asarray(estimates[100:])
    assert abs(tail.mean() - true) < 0.01
    # noise damping: EWMA std well under the uniform(-.05,.05) sample std
    assert tail.std() < 0.015


def test_ewma_profile_converges_to_constant():
    prof = LearnerProfile(decay=0.8)
    prof.observe_step_time(1.0)  # stale initial estimate
    for _ in range(60):
        prof.observe_step_time(0.2)
    assert abs(prof["seconds_per_step"] - 0.2) < 1e-4


def test_decay_zero_is_legacy_last_sample():
    prof = LearnerProfile(decay=0.0)
    prof.observe_step_time(1.0)
    prof.observe_step_time(0.25)
    assert prof["seconds_per_step"] == 0.25


def test_profile_rejects_bad_decay():
    with pytest.raises(ValueError):
        LearnerProfile(decay=1.0)
    with pytest.raises(ValueError):
        LearnerProfile(decay=-0.1)


def test_controller_profiles_use_ewma():
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8),
                      profile_decay=0.5)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(_make_learner(0))
    ctrl.engine.run(rounds=3)
    ctrl.shutdown()
    prof = ctrl._learner_profiles["l0"]
    assert isinstance(prof, LearnerProfile)
    assert prof.observations == 3
    assert prof["seconds_per_step"] > 0
    assert prof["upload_bytes"] == 4 * ctrl.arena.padded_params


# ---------------------------------------------------------------------------
# wire-cost-aware semi-sync sizing
# ---------------------------------------------------------------------------


def test_semi_sync_wire_aware_subtracts_wire_time():
    proto = SemiSyncProtocol(hyperperiod_s=1.0, default_steps=2)
    prof = {"seconds_per_step": 0.01}
    assert proto.size_task(0, prof, wire_s=0.0).local_steps == 100
    assert proto.size_task(0, prof, wire_s=0.5).local_steps == 50
    # naive arm ignores the wire time
    naive = SemiSyncProtocol(hyperperiod_s=1.0, wire_aware=False)
    assert naive.size_task(0, prof, wire_s=0.5).local_steps == 100
    # wire time >= budget still dispatches the minimum task
    assert proto.size_task(0, prof, wire_s=2.0).local_steps == 1
    # no profile yet -> default steps regardless of wire time
    assert proto.size_task(0, {}, wire_s=0.5).local_steps == 2


def test_semi_sync_budget_covers_train_plus_wire():
    """Property: whenever at least one step fits in the post-wire budget,
    the wire-aware completion estimate stays within the hyper-period."""
    rng = np.random.default_rng(1)
    proto = SemiSyncProtocol(hyperperiod_s=1.0)
    for _ in range(200):
        sps = float(rng.uniform(1e-4, 0.2))
        wire = float(rng.uniform(0.0, 0.9))
        steps = proto.size_task(0, {"seconds_per_step": sps}, wire_s=wire).local_steps
        if proto.hyperperiod_s - wire >= sps:
            assert steps * sps + wire <= proto.hyperperiod_s + 1e-9


def test_controller_wire_time_estimate_matches_channel_model():
    ch = Channel(bandwidth_gbps=0.1, latency_ms=1.0)
    ctrl = Controller(protocol=SemiSyncProtocol(hyperperiod_s=0.05,
                                                batch_size=8),
                      channel=ch)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(_make_learner(0))
    down = ctrl.manifest.total_bytes
    # before any upload: the codec's modeled payload for the padded row
    up = 4 * ctrl.arena.padded_params
    assert ctrl.wire_time_s("l0") == pytest.approx(ch.round_trip_s(down, up))
    expect = 2 * 1e-3 + (down + up) * 8 / 0.1e9
    assert ctrl.wire_time_s("l0") == pytest.approx(expect)
    # after a round the profile's measured upload bytes take over
    ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    assert ctrl._learner_profiles["l0"]["upload_bytes"] == up
    assert ctrl.wire_time_s("l0") == pytest.approx(ch.round_trip_s(down, up))


def test_wire_aware_sizing_shapes_real_rounds():
    """Under a bandwidth cap, the wire-aware arm must assign fewer steps
    than the naive arm once profiles exist (the --schedule bench claim)."""
    class _FixedSpsLearner(Learner):
        # Reports a fixed seconds-per-step: the *sizing* is under test, and
        # wall-clock on a loaded CI box would make the expectation flaky.
        def fit(self, params, task):
            update = super().fit(params, task)
            update.seconds_per_step = 1e-3
            return update

    def run(wire_aware):
        ctrl = Controller(
            protocol=SemiSyncProtocol(hyperperiod_s=0.1, batch_size=8,
                                      default_steps=1, wire_aware=wire_aware),
            channel=Channel(bandwidth_gbps=0.0005, latency_ms=5.0),
        )
        ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
        base = _make_learner(0)
        fixed = _FixedSpsLearner.__new__(_FixedSpsLearner)
        fixed.__dict__.update(base.__dict__)
        ctrl.register_learner(fixed)
        ctrl.engine.run(rounds=3)
        steps = [e.task.local_steps
                 for e in ctrl.engine.event_log if isinstance(e, Dispatched)]
        wire = ctrl.wire_time_s("l0")
        ctrl.shutdown()
        return steps, wire

    aware_steps, wire = run(True)
    naive_steps, _ = run(False)
    assert wire > 0.05  # the cap makes wire time a large budget fraction
    # round 0 has no profile (both arms dispatch default_steps); later
    # rounds must be sized down by the wire-aware arm, and its modeled
    # completion must fit the hyper-period where the naive arm overshoots
    assert aware_steps[0] == naive_steps[0] == 1
    assert naive_steps[-1] == 100                    # 0.1 / 1e-3
    assert aware_steps[-1] == int((0.1 - wire) / 1e-3)
    assert aware_steps[-1] * 1e-3 + wire <= 0.1
    assert naive_steps[-1] * 1e-3 + wire > 0.1


# ---------------------------------------------------------------------------
# secure + async: per-epoch mask sessions
# ---------------------------------------------------------------------------


def test_custom_policy_weighting_hook_is_consulted():
    """The engine must route the reduce through policy.weighting(): a
    round-based policy declaring "staleness" gets the community aggregate
    (every valid stored model), not the cohort-masked FedAvg."""

    class StaleSync(SyncProtocol):
        def weighting(self):
            return "staleness"

    def run(proto):
        ctrl = Controller(protocol=proto)
        ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
        for i in range(2):
            ctrl.register_learner(_make_learner(i))
        # a heavy out-of-cohort row: included only by the community reduce
        ghost = jnp.full((ctrl.arena.padded_params,), 123.0, jnp.float32)
        ctrl.arena.write("ghost", ghost, weight=1e9, version=0.0)
        ctrl.engine.run(rounds=1)
        out = np.asarray(ctrl.global_params["w"])
        ctrl.shutdown()
        return out

    staleness_out = run(StaleSync(local_steps=1, batch_size=8))
    fedavg_out = run(SyncProtocol(local_steps=1, batch_size=8))
    np.testing.assert_allclose(staleness_out, 123.0, rtol=1e-3)  # ghost dominates
    assert np.abs(fedavg_out).max() < 10  # cohort-only reduce excluded it


def test_mask_session_seeds_are_fresh_per_epoch():
    seeds = {secure_mod.MaskSession(7, e).seed for e in range(200)}
    assert len(seeds) == 200  # every epoch re-keys the pads
    assert secure_mod.MaskSession(7, 3).seed == secure_mod.MaskSession(7, 3).seed
    assert secure_mod.MaskSession(7, 3).seed != secure_mod.MaskSession(8, 3).seed
    masker = secure_mod.MaskSession(7, 3).masker(4)
    assert masker.participants == (0, 1, 2, 3)


def test_secure_community_update_matches_clear_staleness_average():
    """aggregate_community with secure=True must equal the clear
    staleness-weighted average up to fixed-point quantization — exercised
    on a hand-built arena with mixed staleness."""
    alpha = 0.5
    ctrl = Controller(protocol=AsyncProtocol(staleness_alpha=alpha), secure=True)
    ctrl.set_initial_model({"w": jnp.zeros((8,), jnp.float32)})
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(3, 8)).astype(np.float32) * 0.5
    weights = [10.0, 20.0, 30.0]
    versions = [0.0, 1.0, 2.0]
    for i in range(3):
        buf = jnp.pad(jnp.asarray(rows[i]), (0, ctrl.arena.padded_params - 8))
        ctrl.arena.write(f"l{i}", buf, weight=weights[i], version=versions[i])
    ctrl._model_version = 3
    ctrl.aggregate_community()
    got = np.asarray(ctrl.global_params["w"])
    ctrl.shutdown()

    damped = np.asarray(
        [w * (1.0 + 3 - v) ** (-alpha) for w, v in zip(weights, versions)]
    )
    expect = (damped[:, None] * rows).sum(0) / damped.sum()
    np.testing.assert_allclose(got, expect, atol=1e-3)


def test_secure_async_federation_converges_and_hides_models():
    """End-to-end secure async on real learners: the engine runs community
    updates through per-epoch mask sessions and the model stays sane."""
    ctrl = Controller(protocol=AsyncProtocol(local_steps=2, batch_size=16),
                      secure=True)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i))
    hist = ctrl.engine.run(total_updates=6)
    stats = ctrl.channel.stats
    ctrl.shutdown()
    assert len(hist) >= 6
    assert ctrl._model_version >= 6
    assert np.isfinite(np.asarray(ctrl.global_params["w"])).all()
    assert stats.upload_messages == ctrl.arena.total_writes
    assert all(h.aggregation_s > 0 for h in hist)


def test_secure_async_single_learner_matches_plain_quantized():
    """n=1 async: secure and clear paths differ only by the fixed-point
    round-trip (the masks of a single participant cancel to zero)."""
    def run(secure):
        ctrl = Controller(protocol=AsyncProtocol(local_steps=2, batch_size=16),
                          secure=secure)
        ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
        ctrl.register_learner(_make_learner(0))
        ctrl.engine.run(total_updates=3)
        out = np.asarray(ctrl.global_params["w"])
        ctrl.shutdown()
        return out

    np.testing.assert_allclose(run(True), run(False), atol=1e-3)


# ---------------------------------------------------------------------------
# mid-round dropout: orphaned uploads are tolerated, never fatal
# ---------------------------------------------------------------------------


class _DroppingLearner(Learner):
    """A learner whose fit() deregisters it from the controller mid-round,
    so its upload lands *after* it left the federation (the orphan path)."""

    def __init__(self, inner, controller):
        self.__dict__.update(inner.__dict__)
        self._ctrl = controller

    def fit(self, params, task):
        update = super().fit(params, task)
        self._ctrl.deregister_learner(self.learner_id)
        return update


@pytest.mark.parametrize("store_mode", ["arena", "stack"])
def test_mid_round_dropout_upload_is_orphaned_not_fatal(store_mode):
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16),
                      store_mode=store_mode, max_dispatch_workers=1)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(_make_learner(0))
    ctrl.register_learner(_DroppingLearner(_make_learner(1), ctrl))
    ctrl.register_learner(_make_learner(2))

    hist = ctrl.engine.run(rounds=1)  # must not raise

    assert len(hist) == 1
    assert ctrl.telemetry.value("engine.uploads.orphaned") == 1
    assert ctrl.telemetry.value("engine.faults.dropouts") == 1
    assert "l1" not in ctrl._learners
    assert np.isfinite(np.asarray(ctrl.global_params["w"])).all()
    # the survivors keep federating
    hist2 = ctrl.engine.run(rounds=1)
    assert len(hist2) == 1
    ctrl.shutdown()


def test_every_learner_dropping_mid_round_raises():
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16),
                      max_dispatch_workers=1)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(_DroppingLearner(_make_learner(0), ctrl))
    with pytest.raises(RuntimeError, match="dropped out"):
        ctrl.engine.run(rounds=1)
    ctrl.shutdown()


# ---------------------------------------------------------------------------
# fault fates: dup must not double-register, lost-during-drain must retry
# ---------------------------------------------------------------------------


class _ScriptedInjector:
    """A FaultInjector stand-in with scripted upload fates: keys are
    ``(learner_id, round_id)`` or bare ``learner_id`` (every round)."""

    def __init__(self, fates):
        self.fates = dict(fates)

    def upload_fate(self, lid, rid):
        return self.fates.get((lid, int(rid))) or self.fates.get(lid, "ok")


def _faulty_controller(protocol, fates, **kwargs):
    from repro.core import FaultyChannel

    ctrl = Controller(
        protocol=protocol, channel=FaultyChannel(_ScriptedInjector(fates)),
        max_dispatch_workers=1, **kwargs,
    )
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    return ctrl


def test_dup_completing_quorum_is_not_counted_late():
    """A duplicated upload whose second copy completes the sync quorum must
    not leave the original frame re-registering it as a late straggler."""
    ctrl = _faulty_controller(
        SyncProtocol(local_steps=1, batch_size=16), {"l1": "dup"}
    )
    # one worker: l1 (dup-fated) is always the quorum-completing arrival
    hist = ctrl.engine.run(rounds=2)
    assert len(hist) == 2
    assert ctrl.telemetry.value("engine.faults.uploads_duplicated") == 2
    assert ctrl.telemetry.value("engine.faults.uploads_late") == 0
    assert ctrl.engine._late_carry == []
    ctrl.shutdown()


def test_dup_completing_buffer_leaves_no_phantom_member():
    """A duplicated upload whose second copy fills the FedBuff buffer fires
    the aggregate inside the recursion; the original frame must not re-append
    the learner to the freshly cleared buffer."""
    from repro.core import BufferedAsyncProtocol

    ctrl = _faulty_controller(
        BufferedAsyncProtocol(buffer_k=2, local_steps=1, batch_size=16),
        {"l1": "dup"},
    )
    ctrl.engine.run(total_updates=2)
    assert ctrl.engine._buffer == []  # no phantom carry-over
    fired = [e for e in ctrl.engine.event_log if isinstance(e, AggregateFired)]
    assert len(fired) == 2
    assert all(e.members == ("l0", "l1") for e in fired)
    assert ctrl.telemetry.value("engine.faults.uploads_duplicated") == 2
    ctrl.shutdown()


def test_lost_during_checkpoint_drain_rejoins_rotation(tmp_path):
    """An upload lost while the pre-checkpoint drain is absorbing arrivals
    (no immediate retry leg) must be re-dispatched after the checkpoint —
    and recorded in the checkpoint's pending dispatches — instead of
    silently leaving the rotation for the rest of the run."""
    from repro.checkpoint import checkpoint as ckpt
    from repro.core import BufferedAsyncProtocol

    ctrl = _faulty_controller(
        BufferedAsyncProtocol(buffer_k=1, local_steps=1, batch_size=16),
        {("l1", 0): "lost"},
    )
    # checkpoint after every community update: l0's first arrival fires,
    # the drain then absorbs l1's lost upload with fire=False
    ctrl.engine.run(
        total_updates=3, checkpoint_every=1, checkpoint_dir=str(tmp_path)
    )
    assert ctrl.telemetry.value("engine.faults.uploads_lost") == 1
    dispatched_l1 = [
        e for e in ctrl.engine.event_log
        if isinstance(e, Dispatched) and e.learner_id == "l1"
    ]
    assert len(dispatched_l1) >= 2  # the owed retry leg actually left
    # the checkpoint written around the drain owes l1's retry on restore
    _, _, meta = ckpt.restore_checkpoint(str(tmp_path), step=1)
    assert meta["pending_dispatch"] == ["l0", "l1"]
    ctrl.shutdown()

    ctrl2 = _faulty_controller(
        BufferedAsyncProtocol(buffer_k=1, local_steps=1, batch_size=16), {}
    )
    ctrl2.restore(str(tmp_path), step=1)
    assert ctrl2.engine._resume_dispatch == ["l0", "l1"]
    ctrl2.shutdown()


def test_rejoin_preserves_profile_and_decays_reputation():
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=1)
    prof = ctrl._learner_profiles["l0"]
    rep_before = prof.reputation()
    obs_before = prof.observations
    assert rep_before > 0

    ctrl.deregister_learner("l0")
    assert "l0" in ctrl._deregistered_at
    ctrl.engine.run(rounds=2)  # two rounds absent
    ctrl.register_learner(_make_learner(0))

    prof2 = ctrl._learner_profiles["l0"]
    assert prof2 is prof  # profile survives churn
    assert prof2.observations == obs_before
    assert prof2.reputation() == pytest.approx(rep_before * 0.9**2)
    assert ctrl.telemetry.value("engine.faults.rejoins") == 1
    assert "l0" not in ctrl._deregistered_at
    ctrl.engine.run(rounds=1)  # the rejoined learner participates again
    ctrl.shutdown()
