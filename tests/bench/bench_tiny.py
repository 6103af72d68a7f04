"""Tiny stand-ins of the benchmark's cells, for CPU tests of the harness.

Each keeps its cell's traffic mix, codec, arena, sharding and limits, and
shrinks the model and the number of learners so that a run takes seconds on
a CPU.  The cells are ``BENCHMARK.json``'s, in file order; each family
module in ``bench/configs/`` gives the shrink of its own stand-in
(``TINY``), so a new cell or family needs no edit here.

A cell on more chips than the test process has devices runs its federation
in a child process with that many CPU devices (:func:`run`).  The faults
that a test plants in the timed path are named in :data:`PLANTS`, so that
the child can find them.

    python tests/bench/bench_tiny.py '{"cell": ..., "seed": ..., "seconds": ..., "plant": ...}'

prints the harness's result for that stand-in as its last line.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

CELLS = tuple(w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"])


def workload(cell: str, **traffic) -> spec.Workload:
    """Cell ``cell`` of ``BENCHMARK.json`` at a size a CPU test can hold."""
    w = spec.load(cell)
    tiny = spec.family(w.family).TINY
    config = dict(w.config, **tiny.get("config", {}))
    t = dict(w.traffic, learners=4, **tiny.get("traffic", {}))
    t["federation"] = dict(t["federation"], **tiny.get("federation", {}))
    t.update(traffic)
    return spec.Workload(name=w.name, chips=w.chips, config=config, traffic=t,
                         limits=w.limits, end_to_end=w.end_to_end,
                         per_layer=w.per_layer)


def pick(*, family: str | None = None, codec: str | None = None) -> str:
    """The first one-chip cell of this family and uplink codec."""
    for cell in CELLS:
        w = spec.load(cell)
        if (w.chips == 1 and family in (None, w.family)
                and codec in (None, spec.federation(w.traffic)["upload_codec"])):
            return cell
    raise LookupError(f"no one-chip cell of family {family!r} and codec {codec!r}")


def sharded(cell: str) -> bool:
    """The cell's arena is split over chips, so its commit gathers across them."""
    return bool(spec.load(cell).traffic["federation"].get("arena_shards"))


def catches_a_left_out_commit(cell: str) -> bool:
    """The cell's ``change_gap`` limit is under 1.

    A round's commit left out leaves each leaf's change at the rounds before
    it, a gap under about 1 of the reference's change; a limit of 1 or more
    passes it.
    """
    limit = spec.load(cell).limits["change_gap"]["limit"]
    return limit is not None and limit < 1


# Faults planted underneath the timed path: each takes the federation.

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


def _exchange_left_out(fed):
    """The committed row takes the first shard's columns only.

    What the other chips reduced never reaches the global model: their
    columns keep the model as it was.
    """
    import jax.numpy as jnp

    c = fed.controller
    commit = c._commit

    def local_only(new_buffer):
        old = c.global_buffer
        n = old.shape[0]
        keep = jnp.arange(n) < c.arena.shard_width
        return commit(jnp.where(keep, new_buffer[:n], old))

    c._commit = local_only


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


PLANTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "upload_altered": _upload_altered, "exchange_left_out": _exchange_left_out,
          "last_commit_left_out": _last_commit_left_out}


def faults(cell: str) -> list[str]:
    """The faults of the timed path that a cell can have and its limits catch.

    A cell's limits file names, under ``not_caught``, a fault that its
    numbers were shown on the chip to pass at the cell's own size; its
    stand-in is not held to catching it.
    """
    out = ["half_batch", "unchanged", "upload_altered"]
    if sharded(cell):
        out.append("exchange_left_out")
    missed = spec.load(cell).limits.get("not_caught", {})
    return [f for f in out if f not in missed]


def run(cell: str, seed: int, seconds: float, plant: str | None = None, **traffic) -> dict:
    """``harness.run`` of ``cell``'s stand-in without a chip, ``plant`` planted.

    Runs in this process where it has the devices the cell asks for, and
    otherwise in a child process given that many CPU devices.
    """
    import jax

    w = workload(cell, **traffic)
    if w.chips <= jax.device_count():
        from bench import harness

        return harness.run(w, seed, seconds, False, require_chip=False,
                           plant=PLANTS[plant] if plant else None, log=lambda m: None)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={w.chips}")
    args = {"cell": cell, "seed": seed, "seconds": seconds, "plant": plant,
            "traffic": traffic}
    p = subprocess.run([sys.executable, __file__, json.dumps(args)], env=env,
                       capture_output=True, text=True, timeout=600)
    if p.returncode != 0:
        raise RuntimeError(f"stand-in of {cell} failed:\n{p.stderr[-4000:]}")
    return json.loads(p.stdout.splitlines()[-1])


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


if __name__ == "__main__":
    a = json.loads(sys.argv[1])
    print(json.dumps(run(a["cell"], a["seed"], a["seconds"], a["plant"], **a["traffic"])))
