"""Serialize-once broadcast dispatch + flat-buffer upload fast path.

Asserts the acceptance surface of the dispatch re-engineering
(``docs/DISPATCH.md``):

* ``Channel.broadcast`` serializes once, shares one read-only byte buffer
  across every recipient's envelope, and charges per-recipient bytes/wire
  time — bit-identical received params vs the legacy per-send path;
* the controller serializes the global model exactly once per model version
  (train dispatch, eval fan-out and async re-dispatches share it) and never
  flattens a pytree on the arena upload path (counters);
* flat-upload parity with the legacy pack-on-arrival path on sync,
  semi-sync, async and secure protocols, in arena and stack store modes, and
  on the mesh-sharded arena under 8 forced host devices;
* ``ChannelStats`` survives being hammered from 16 threads without losing
  updates — on the downlink *and* the upload half;
* uplink byte/message totals reconcile exactly with round counts on sync,
  semi-sync, async and secure, in arena and stack modes, fast path and
  legacy (controller-stand-in) path alike;
* the empty-cohort check reads the arena's host-side row map
  (``ArenaStore.num_valid``), not the device mask.
"""

import os
import subprocess
import sys
import textwrap
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AsyncProtocol, Channel, Controller, Driver, FederationEnv, Learner,
    SemiSyncProtocol, SyncProtocol, TerminationCriteria, packing,
)
from repro.core.store import ArenaStore
from repro.optim import sgd

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _mixed_tree():
    return {
        "w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) * 0.25,
        "h": (jnp.arange(10, dtype=jnp.bfloat16) * 0.5),
        "s": jnp.asarray(3.5, jnp.float32),
    }


# ---------------------------------------------------------------------------
# channel-level broadcast
# ---------------------------------------------------------------------------


def test_broadcast_parity_with_per_send():
    tree = _mixed_tree()
    ch = Channel(bandwidth_gbps=1.0, latency_ms=1.0)
    sent = ch.recv(ch.send(tree))

    manifest = packing.build_manifest(tree)
    numeric = packing.pack_numeric(tree)
    bc = ch.broadcast(buffer=numeric, manifest=manifest)
    e1, e2 = bc.to({"task": 1}), bc.to({"task": 2})

    # shared read-only buffer, per-recipient metadata
    assert e1.buffer is e2.buffer and e1.manifest is e2.manifest
    assert e1.metadata == {"task": 1} and e2.metadata == {"task": 2}
    assert not e1.buffer.flags.writeable
    assert bc.recipients == 2

    # bit-identical received params vs per-send
    got = ch.recv(e1)
    for k in tree:
        assert got[k].dtype == sent[k].dtype
        assert np.asarray(got[k]).tobytes() == np.asarray(sent[k]).tobytes()

    # accounting: 2 serializations total (send + broadcast), 3 messages,
    # bytes and wire time counted per recipient
    nbytes = e1.buffer.nbytes
    assert ch.stats.serializations == 2
    assert ch.stats.messages == 3
    assert ch.stats.bytes_moved == 3 * nbytes
    per_msg = 1e-3 + nbytes * 8 / 1e9
    assert abs(ch.stats.virtual_wire_s - 3 * per_msg) < 1e-9


def test_broadcast_falls_back_to_pytree_once_with_codec():
    from repro.kernels.ops import QuantCodec

    tree = {"w": jnp.linspace(-1, 1, 64, dtype=jnp.float32)}
    ch = Channel(quantize_codec=QuantCodec())
    bc = ch.broadcast(
        params=tree,
        buffer=packing.pack_numeric(tree),
        manifest=packing.build_manifest(tree),
    )
    outs = [ch.recv(bc.to()) for _ in range(4)]
    assert ch.stats.serializations == 1 and ch.stats.messages == 4
    for out in outs:
        np.testing.assert_allclose(
            np.asarray(out["w"]), np.asarray(tree["w"]), atol=0.02
        )


def test_recv_compiles_one_split_for_equal_manifests_of_two_versions():
    """Two model versions whose manifests are equal but not the same object
    decode through one compiled split program."""
    v1 = {"a": jnp.arange(91, dtype=jnp.float32).reshape(7, 13),
          "b": jnp.full((13,), 0.5, jnp.float32)}
    v2 = {k: v * 3 + 1 for k, v in v1.items()}
    m1, m2 = packing.build_manifest(v1), packing.build_manifest(v2)
    assert m1 == m2 and m1 is not m2
    ch = Channel()
    before = packing._split_runs._cache_size()
    for version, m in ((v1, m1), (v2, m2)):
        bc = ch.broadcast(buffer=packing.pack_numeric(version), manifest=m)
        for _ in range(2):
            got = ch.recv(bc.to())
            for k in version:
                assert np.asarray(got[k]).tobytes() == np.asarray(version[k]).tobytes()
    assert packing._split_runs._cache_size() == before + 1


@pytest.mark.parametrize("tree_fn, codec, runs", [
    (lambda: {f"w{i:03d}": jnp.full((i % 5 + 1, 3), i, jnp.float32)
              for i in range(40)}, None, 1),
    (lambda: [jnp.ones((4,), jnp.float32), jnp.ones((3,), jnp.bfloat16),
              jnp.ones((2,), jnp.float32), jnp.zeros((5,), bool)], None, 4),
    (lambda: {"w": jnp.linspace(-1, 1, 64, dtype=jnp.float32)}, "int8", 3),
], ids=["f32", "mixed", "quantized"])
def test_recv_transfers_counts_one_per_dtype_run(tree_fn, codec, runs):
    """``channel.recv_transfers`` adds one per dtype run at every recv: 1 for
    a homogeneous tree, however many leaves; the int8 downlink codec's
    int32 / int8 / float32 leaves are 3."""
    from repro.kernels.ops import QuantCodec

    ch = Channel(quantize_codec=QuantCodec() if codec else None)
    env = ch.send(tree_fn())
    assert len(packing.wire_runs(env.manifest)) == runs
    for _ in range(3):
        ch.recv(env)
    assert ch.telemetry.value("channel.recv_transfers") == 3 * runs
    assert ch.stats.recv_transfers == 3 * runs


def test_pack_bytes_from_numeric_bit_identical_and_pad_oblivious():
    tree = _mixed_tree()
    manifest = packing.build_manifest(tree)
    want, _ = packing.pack_bytes(packing.unpack_numeric(
        packing.pack_numeric(tree), manifest))
    got = packing.pack_bytes_from_numeric(packing.pack_numeric(tree), manifest)
    assert want.tobytes() == got.tobytes()
    padded = packing.pack_numeric(tree, pad_to=256)
    assert packing.pack_bytes_from_numeric(padded, manifest).tobytes() == want.tobytes()


def test_channel_stats_threadsafe_under_16_thread_hammer():
    """send/recv/broadcast.to/upload/recv_upload from 16 threads must not
    lose counter updates in either wire direction."""
    tree = {"w": jnp.ones((50,), jnp.float32)}
    row = packing.pack_numeric(tree)
    ch = Channel()
    bc = ch.broadcast(buffer=row, manifest=packing.build_manifest(tree))
    n_threads, iters = 16, 25
    barrier = threading.Barrier(n_threads)

    def hammer():
        barrier.wait()
        for _ in range(iters):
            env = ch.send(tree)
            ch.recv(env)
            bc.to()
            up = ch.upload(row)
            ch.recv_upload(up)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    total = n_threads * iters
    nbytes = 50 * 4
    assert ch.stats.messages == 2 * total  # one send + one broadcast.to each
    assert ch.stats.bytes_moved == 2 * total * nbytes
    assert ch.stats.serializations == total + 1  # sends + the one broadcast
    assert ch.stats.recv_transfers == total  # one float32 run per recv
    assert bc.recipients == total
    # uplink half: every upload is its own message AND serialization
    assert ch.stats.upload_messages == total
    assert ch.stats.upload_serializations == total
    assert ch.stats.upload_bytes == total * nbytes
    assert ch.stats.upload_virtual_wire_s > 0
    assert ch.stats.total_bytes == ch.stats.bytes_moved + ch.stats.upload_bytes


# ---------------------------------------------------------------------------
# controller: serialize-once + flat uploads
# ---------------------------------------------------------------------------


def test_sync_rounds_serialize_once_per_version_and_never_flatten_uploads():
    n_learners, rounds = 4, 3
    ctrl = Controller(protocol=SyncProtocol(local_steps=2, batch_size=16))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(n_learners):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=rounds)
    stats = ctrl.channel.stats
    ctrl.shutdown()

    # one serialization per model version: the initial model (round 0 train
    # dispatch) plus one per aggregation (shared by eval + next train
    # dispatch) — NOT one per learner per fan-out.
    assert stats.serializations == rounds + 1
    assert ctrl.dispatch_serializations == rounds + 1
    # every learner still got its own envelope, twice per round (train+eval)
    assert stats.messages == 2 * n_learners * rounds
    # the arena upload path never flattened a pytree on arrival
    assert ctrl.upload_fallback_packs == 0
    assert ctrl.arena.total_writes == n_learners * rounds


def test_async_shares_serialization_between_community_updates():
    ctrl = Controller(protocol=AsyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i))
    hist = ctrl.engine.run(total_updates=9)
    stats = ctrl.channel.stats
    ctrl.shutdown()
    assert len(hist) >= 9
    assert ctrl.upload_fallback_packs == 0
    # at most one serialization per model version (initial + one per
    # community update); strictly fewer messages would mean dispatch stopped
    assert stats.serializations <= ctrl._model_version + 1
    assert stats.messages >= stats.serializations


def test_flat_uploads_disabled_counts_fallback_packs():
    ctrl = Controller(
        protocol=SyncProtocol(local_steps=1, batch_size=8), flat_uploads=False
    )
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    assert ctrl.upload_fallback_packs == 3  # controller packed every upload


def _global_after(protocol_fn, *, flat, secure=False, store_mode="arena",
                  rounds=2, n=3, async_updates=0):
    ctrl = Controller(protocol=protocol_fn(), secure=secure,
                      store_mode=store_mode, flat_uploads=flat)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(n):
        ctrl.register_learner(_make_learner(i))
    if async_updates:
        ctrl.engine.run(total_updates=async_updates)
    else:
        ctrl.engine.run(rounds=rounds)
    out = np.asarray(ctrl.global_params["w"])
    fallbacks = ctrl.upload_fallback_packs
    ctrl.shutdown()
    return out, fallbacks


@pytest.mark.parametrize(
    "proto,rounds",
    [
        (lambda: SyncProtocol(local_steps=2, batch_size=16), 2),
        # one round only: from round 2 on, semi-sync task sizing depends on
        # *measured* seconds-per-step, which is not comparable across arms
        (lambda: SemiSyncProtocol(hyperperiod_s=0.05, batch_size=16,
                                  default_steps=2), 1),
    ],
    ids=["sync", "semi_sync"],
)
def test_flat_upload_parity_sync_protocols(proto, rounds):
    fast, fb_fast = _global_after(proto, flat=True, rounds=rounds)
    slow, fb_slow = _global_after(proto, flat=False, rounds=rounds)
    # allclose, not bit-equal: arena row order follows upload *arrival*
    # order, so the float reduction's accumulation order varies per run
    np.testing.assert_allclose(fast, slow, rtol=1e-6, atol=1e-7)
    assert fb_fast == 0 and fb_slow > 0


def test_flat_upload_parity_secure():
    proto = lambda: SyncProtocol(local_steps=2, batch_size=16)  # noqa: E731
    fast, fb = _global_after(proto, flat=True, secure=True)
    slow, _ = _global_after(proto, flat=False, secure=True)
    np.testing.assert_array_equal(fast, slow)
    assert fb == 0


def test_flat_upload_parity_async_single_learner_deterministic():
    proto = lambda: AsyncProtocol(local_steps=2, batch_size=16)  # noqa: E731
    fast, fb = _global_after(proto, flat=True, n=1, async_updates=3)
    slow, _ = _global_after(proto, flat=False, n=1, async_updates=3)
    np.testing.assert_array_equal(fast, slow)
    assert fb == 0


def test_flat_upload_parity_stack_mode():
    proto = lambda: SyncProtocol(local_steps=2, batch_size=16)  # noqa: E731
    fast, fb = _global_after(proto, flat=True, store_mode="stack")
    slow, _ = _global_after(proto, flat=False, store_mode="stack")
    np.testing.assert_array_equal(fast, slow)
    assert fb == 0


def test_late_joining_learner_gets_manifest():
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=1)
    ctrl.register_learner(_make_learner(2))  # joins mid-federation
    ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    assert ctrl.upload_fallback_packs == 0
    assert ctrl.arena.total_writes == 2 + 3


def test_driver_plumbs_flat_uploads_knob():
    for flat in (True, False):
        env = FederationEnv(
            protocol="sync", local_steps=1, batch_size=16, flat_uploads=flat,
            termination=TerminationCriteria(max_rounds=1),
        )
        drv = Driver(env)
        drv.initialize({"w": jnp.zeros((4, 1))}, [_make_learner(0)])
        drv.run()
        assert (drv.controller.upload_fallback_packs == 0) == flat


# ---------------------------------------------------------------------------
# measured uplink: byte totals reconcile with round counts on every protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flat", [True, False], ids=["flat", "legacy"])
@pytest.mark.parametrize(
    "proto_fn,secure",
    [
        (lambda: SyncProtocol(local_steps=1, batch_size=8), False),
        (lambda: SemiSyncProtocol(hyperperiod_s=0.05, batch_size=8,
                                  default_steps=1), False),
        (lambda: SyncProtocol(local_steps=1, batch_size=8), True),
    ],
    ids=["sync", "semi_sync", "secure"],
)
def test_uplink_reconciles_with_round_counts(proto_fn, secure, flat):
    """Both wire directions must report nonzero totals that reconcile
    exactly with round counts — on the fast path and on the legacy path
    (where the controller stands in for the learner's send half)."""
    n, rounds = 3, 2
    ctrl = Controller(protocol=proto_fn(), secure=secure, flat_uploads=flat)
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(n):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=rounds)
    ctrl.shutdown()
    stats = ctrl.channel.stats

    uploads = n * rounds
    row_bytes = 4 * ctrl.arena.padded_params  # decoded f32 row per upload
    wire_down = ctrl.manifest.total_bytes
    # uplink: one measured message AND serialization per upload
    assert stats.upload_messages == uploads == stats.upload_serializations
    assert stats.upload_bytes == uploads * row_bytes
    assert stats.upload_virtual_wire_s > 0
    # downlink: one train + one eval envelope per learner per round
    assert stats.messages == 2 * n * rounds
    assert stats.bytes_moved == stats.messages * wire_down
    assert stats.virtual_wire_s > 0
    # every decoded upload landed in the arena, byte for byte
    assert ctrl.arena.bytes_ingested == uploads * row_bytes
    assert stats.total_bytes == stats.bytes_moved + stats.upload_bytes
    assert (ctrl.upload_fallback_packs == 0) == flat


def test_uplink_reconciles_async_executor():
    """The async protocol uploads from concurrent executor threads; totals
    must still reconcile exactly with the number of arena writes."""
    ctrl = Controller(protocol=AsyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(3):
        ctrl.register_learner(_make_learner(i))
    hist = ctrl.engine.run(total_updates=9)
    ctrl.shutdown()  # barrier: in-flight completions drain before we count
    stats = ctrl.channel.stats

    assert len(hist) >= 9
    writes = ctrl.arena.total_writes
    row_bytes = 4 * ctrl.arena.padded_params
    assert writes >= 9
    assert stats.upload_messages == writes == stats.upload_serializations
    assert stats.upload_bytes == writes * row_bytes
    assert ctrl.arena.bytes_ingested == writes * row_bytes
    assert stats.bytes_moved == stats.messages * ctrl.manifest.total_bytes
    assert stats.upload_virtual_wire_s > 0 and stats.virtual_wire_s > 0


def test_uplink_reconciles_stack_store():
    """Stack mode: uploads are unpadded; the hash-map store's ingest bytes
    must equal the channel's decoded uplink volume."""
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8),
                      store_mode="stack")
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    for i in range(2):
        ctrl.register_learner(_make_learner(i))
    ctrl.engine.run(rounds=1)
    ctrl.shutdown()
    stats = ctrl.channel.stats
    row_bytes = 4 * int(ctrl.global_buffer.shape[0])
    assert stats.upload_messages == 2
    assert stats.upload_bytes == 2 * row_bytes
    assert ctrl.store.bytes_ingested == 2 * row_bytes


# ---------------------------------------------------------------------------
# arena host-side cohort check
# ---------------------------------------------------------------------------


def test_arena_num_valid_is_host_side_and_tracks_invalidation():
    arena = ArenaStore(num_params=8, n_max=2, row_align=8)
    assert arena.num_valid() == 0 and arena.num_valid(["a", "b"]) == 0
    arena.write("a", jnp.ones((8,)), weight=1.0)
    arena.write("b", jnp.ones((8,)), weight=2.0)
    assert arena.num_valid() == 2
    assert arena.num_valid(["a"]) == 1
    assert arena.num_valid(["a", "missing"]) == 1
    arena.invalidate("a")
    assert arena.num_valid(["a", "b"]) == 1


def test_empty_cohort_still_raises():
    ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=8))
    ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
    ctrl.register_learner(_make_learner(0))
    with pytest.raises(RuntimeError, match="no local models"):
        ctrl.aggregate_round(["l0"])  # nothing uploaded yet
    ctrl.shutdown()


# ---------------------------------------------------------------------------
# sharded arena (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_flat_upload_parity_sharded_arena():
    """Flat uploads on the mesh-sharded arena match the legacy path exactly,
    with zero controller-side flattening, on sync and async protocols."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    script = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import AsyncProtocol, Controller, Learner, SyncProtocol
        from repro.launch.mesh import make_controller_mesh
        from repro.optim import sgd

        def make_learner(i):
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

        assert jax.device_count() == 8
        for proto_fn, async_updates in (
            (lambda: SyncProtocol(local_steps=2, batch_size=16), 0),
            (lambda: AsyncProtocol(local_steps=2, batch_size=16), 3),
        ):
            outs = {}
            for flat in (True, False):
                mesh = make_controller_mesh()
                n = 1 if async_updates else 3
                ctrl = Controller(protocol=proto_fn(), arena_mesh=mesh,
                                  flat_uploads=flat)
                ctrl.set_initial_model({"w": jnp.zeros((4, 1))})
                for i in range(n):
                    ctrl.register_learner(make_learner(i))
                if async_updates:
                    ctrl.engine.run(total_updates=async_updates)
                else:
                    ctrl.engine.run(rounds=2)
                assert (ctrl.upload_fallback_packs == 0) == flat, flat
                outs[flat] = np.asarray(ctrl.global_params["w"])
                ctrl.shutdown()
            # allclose: arena row order follows arrival order (see the
            # single-device parity test)
            np.testing.assert_allclose(outs[True], outs[False],
                                       rtol=1e-6, atol=1e-7)
        print("SHARDED-FLAT-OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "SHARDED-FLAT-OK" in out.stdout
