"""In-memory model stores for the federation controller.

MetisFL's controller keeps every learner's latest local model in an in-memory
hash map (the paper assumes all local models fit in memory and treats
insert/select as O(1); §5 sketches future on-disk/distributed stores).  Two
backings implement that store:

* :class:`ModelStore` — the hash-map store with per-learner lineage,
  capacity-bounded eviction, and aggregate byte accounting.  Each upload is a
  standalone buffer; aggregation re-stacks them into an ``(N, P)`` array every
  round (the legacy path, kept for parity testing).

* :class:`ArenaStore` — the device-resident aggregation arena.  One persistent
  ``(n_max, P)`` device buffer plus ``weights``/``versions`` vectors and a
  validity mask; every learner owns a row, uploads are donated in-place row
  writes, and aggregation is a single masked reduction straight over the arena
  — the controller hot path never re-packs or re-stacks anything.

  Passing ``mesh=`` puts the arena in **sharded mode**: the buffer is laid out
  column-sharded over the mesh (``P`` split over the data axis, rows
  replication-free), row writes run through a ``shard_map``-ed donated
  ``dynamic_update_slice`` so each device only ever touches its own
  ``(n_max, P/n_shards)`` shard, and the masked reduction happens per shard
  with **zero collectives** — nothing is gathered until the final model
  unpack.  See ``docs/ARENA.md``.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import OrderedDict
from typing import Any, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import Telemetry
from repro.core.packing import round_up

__all__ = ["ModelRecord", "ModelStore", "ArenaStore"]


@dataclasses.dataclass
class ModelRecord:
    """One stored local model plus its aggregation metadata."""

    learner_id: str
    round_id: int
    buffer: Any  # packed numeric buffer (jax.Array) or byte buffer
    num_examples: int  # aggregation weight source (FedAvg)
    metadata: dict = dataclasses.field(default_factory=dict)
    timestamp: float = dataclasses.field(default_factory=time.monotonic)

    @property
    def nbytes(self) -> int:
        """Resident bytes of the stored buffer (eviction accounting)."""
        b = self.buffer
        if hasattr(b, "nbytes"):
            return int(b.nbytes)
        return int(np.asarray(b).nbytes)


class ModelStore:
    """Hash-map model store with per-learner lineage and eviction.

    ``lineage_length`` bounds how many historical models per learner are kept
    (1 = paper's behaviour: latest only).  ``capacity_bytes`` optionally bounds
    total resident bytes; the oldest records across learners are evicted first
    (never the latest record of a learner — the controller must always be able
    to aggregate every registered learner).
    """

    def __init__(
        self,
        lineage_length: int = 1,
        capacity_bytes: int | None = None,
        telemetry: Telemetry | None = None,
    ):
        if lineage_length < 1:
            raise ValueError("lineage_length must be >= 1")
        self._lineage_length = lineage_length
        self._capacity_bytes = capacity_bytes
        self._records: OrderedDict[str, list[ModelRecord]] = OrderedDict()
        self._telemetry = telemetry if telemetry is not None else Telemetry()
        self._register_counters()

    def _register_counters(self) -> None:
        self._c_inserts = self._telemetry.counter("store.model.total_inserts")
        self._c_bytes = self._telemetry.counter("store.model.bytes_ingested")

    def bind_telemetry(self, telemetry: Telemetry) -> None:
        """Re-register this store's counters in a shared registry.

        The controller calls this on a user-supplied store so every counter
        lives behind the one ``controller.telemetry`` handle; current values
        carry over.
        """
        if telemetry is self._telemetry:
            return
        inserts, nbytes = self._c_inserts.value, self._c_bytes.value
        self._telemetry = telemetry
        self._register_counters()
        self._c_inserts.add(inserts)
        self._c_bytes.add(nbytes)

    @property
    def total_inserts(self) -> int:
        """Deprecated shim for ``telemetry.value('store.model.total_inserts')``."""
        return self._c_inserts.value

    @property
    def bytes_ingested(self) -> int:
        """Deprecated shim for ``telemetry.value('store.model.bytes_ingested')``."""
        return self._c_bytes.value

    # -- insertion ---------------------------------------------------------
    def insert(self, record: ModelRecord) -> None:
        """Append to the learner's lineage, trimming history and evicting."""
        lineage = self._records.setdefault(record.learner_id, [])
        lineage.append(record)
        self._c_inserts.add(1)
        # Cumulative ingest accounting (never decremented by eviction):
        # reconciles against the channel's uplink counters in tests.
        self._c_bytes.add(record.nbytes)
        if len(lineage) > self._lineage_length:
            del lineage[: len(lineage) - self._lineage_length]
        self._maybe_evict()

    def _maybe_evict(self) -> None:
        if self._capacity_bytes is None:
            return
        while self.resident_bytes() > self._capacity_bytes:
            victim: ModelRecord | None = None
            for lineage in self._records.values():
                # candidates: everything but the newest record per learner
                for rec in lineage[:-1]:
                    if victim is None or rec.timestamp < victim.timestamp:
                        victim = rec
            if victim is None:
                break  # only latest-per-learner remain; never evict those
            self._records[victim.learner_id].remove(victim)

    # -- selection ---------------------------------------------------------
    def latest(self, learner_id: str) -> ModelRecord:
        """The learner's most recent record (KeyError if never uploaded)."""
        return self._records[learner_id][-1]

    def lineage(self, learner_id: str) -> list[ModelRecord]:
        """Oldest-to-newest stored history for one learner (may be empty)."""
        return list(self._records.get(learner_id, []))

    def discard(self, learner_id: str) -> None:
        """Drop a learner's entire stored lineage (no-op if unknown)."""
        self._records.pop(learner_id, None)

    def select_latest(self, learner_ids: list[str] | None = None) -> list[ModelRecord]:
        """The controller's 'model selection' step before aggregation."""
        ids = learner_ids if learner_ids is not None else list(self._records)
        return [self.latest(i) for i in ids if i in self._records]

    def __contains__(self, learner_id: str) -> bool:
        return learner_id in self._records

    def __iter__(self) -> Iterator[str]:
        return iter(self._records)

    def __len__(self) -> int:
        return len(self._records)

    # -- accounting ---------------------------------------------------------
    def resident_bytes(self) -> int:
        """Total bytes across every stored record (drives eviction)."""
        return sum(rec.nbytes for lin in self._records.values() for rec in lin)

    def num_records(self) -> int:
        """Total stored records across all learners and lineages."""
        return sum(len(lin) for lin in self._records.values())

    # -- checkpointing ------------------------------------------------------
    def export_records(self) -> list[ModelRecord]:
        """Every stored record in insertion order (checkpoint save)."""
        return [rec for lin in self._records.values() for rec in lin]

    def restore_records(self, records: Sequence[ModelRecord]) -> None:
        """Replace the store's contents (checkpoint restore).

        Rebuilds lineages in the given order without touching the cumulative
        ingest counters — restore is not new wire traffic.
        """
        self._records.clear()
        for rec in records:
            self._records.setdefault(rec.learner_id, []).append(rec)


# ---------------------------------------------------------------------------
# Device-resident aggregation arena
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_row(arena: jax.Array, row: jax.Array, buf: jax.Array) -> jax.Array:
    """Donated in-place row write: arena[row, :len(buf)] = buf.

    Donation lets XLA update the persistent buffer without allocating a new
    ``(n_max, P)`` array — the arena's whole point.  ``row`` is a traced
    scalar so every learner's write hits the same compiled executable.
    """
    return jax.lax.dynamic_update_slice(arena, buf[None, :], (row, 0))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _set_row_meta(
    weights: jax.Array, versions: jax.Array, mask: jax.Array,
    row: jax.Array, weight: jax.Array, version: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    return (
        weights.at[row].set(weight),
        versions.at[row].set(version),
        mask.at[row].set(1.0),
    )


def _grown_impl(old: jax.Array, n_new: int) -> jax.Array:
    new = jnp.zeros((n_new,) + old.shape[1:], old.dtype)
    return new.at[: old.shape[0]].set(old)


_grown = jax.jit(_grown_impl, static_argnames=("n_new",))


def _make_sharded_writer(mesh, axes):
    """Build the sharded-arena row writer: a donated ``shard_map``-ed
    ``dynamic_update_slice``.

    Each device holds an ``(n_max, shard_width)`` column shard of the arena
    and the matching ``(shard_width,)`` slice of the incoming upload; the
    write is purely local (the row index is replicated, the column offset is
    0 in every shard's coordinates), so the compiled program contains no
    collectives and — thanks to donation — no ``(n_max, P)`` re-allocation.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def _write(arena, row, buf):
        return jax.lax.dynamic_update_slice(arena, buf[None, :], (row, 0))

    sm = shard_map(
        _write,
        mesh=mesh,
        in_specs=(P(None, axes), P(), P(axes)),
        out_specs=P(None, axes),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=(0,))


class ArenaStore:
    """Device-resident aggregation arena — the controller hot-path store.

    Owns one persistent ``(n_max, padded_params)`` device buffer plus
    ``weights (n_max,)`` (FedAvg example counts), ``versions (n_max,)`` (the
    global-model version each row trained from, for staleness weighting) and a
    float validity ``mask (n_max,)``.  Every learner is assigned a row on
    first upload and *reuses* it on every subsequent upload (a donated
    ``dynamic_update_slice`` — no host round-trip, no re-stack); aggregation
    is a single masked reduction straight over ``buffer``
    (``core/aggregation.masked_weighted_average`` or the Pallas
    ``kernels.ops.masked_fedavg``), sliced to ``num_params``.

    Rows are padded to ``row_align`` elements so the Pallas kernel's VMEM
    tiles stay lane-aligned without per-call padding; the padding columns are
    zero and never escape (aggregation output is sliced to ``num_params``).

    When more learners register than ``n_max`` rows exist, the arena grows
    geometrically (one O(n·P) copy per doubling, amortized O(1) per learner).

    **Sharded mode** (``mesh=`` given): the buffer is created with a
    ``P(None, axes)`` :class:`~jax.sharding.NamedSharding` — columns split
    over the mesh's data axis, rows replication-free — so each device owns a
    ``(n_max, shard_width)`` shard.  Row writes route through a
    ``shard_map``-ed donated ``dynamic_update_slice`` (each device updates
    only its shard; zero collectives) and ``padded_params`` is rounded up to
    ``row_align * n_shards`` so every shard stays lane-aligned for the Pallas
    kernel.  The tiny metadata vectors stay host-driven exactly as in the
    single-device mode.  Growth preserves the sharding (the grown buffer is
    re-laid-out with the same spec; the copy is shard-local).

    Thread-safety: all mutation happens under an internal re-entrant lock.
    Because writes *donate* the previous array object, callers must not hold
    references to ``buffer``/``weights``/``versions``/``mask`` across a
    concurrent write — aggregate inside ``with arena.lock:``.
    """

    def __init__(
        self,
        num_params: int,
        n_max: int = 8,
        row_align: int = 1024,
        dtype: Any = jnp.float32,
        mesh: Any = None,
        axes: Any = None,
        telemetry: Telemetry | None = None,
        arena_dtype: str = "f32",
        qgroup: int | None = None,
        sparse_k: int | None = None,
    ):
        if num_params < 1:
            raise ValueError("num_params must be >= 1")
        if arena_dtype not in ("f32", "int8", "topk"):
            raise ValueError(
                f"arena_dtype must be 'f32', 'int8' or 'topk', "
                f"got {arena_dtype!r}"
            )
        self.num_params = int(num_params)
        self.dtype = jnp.dtype(dtype)
        self.arena_dtype = arena_dtype
        self.lock = threading.RLock()
        self.mesh = mesh
        if mesh is not None:
            from repro.core.aggregation import arena_axes
            from repro.models.sharding import arena_specs

            buf_s, row_s, repl_s = arena_specs(mesh, axes)
            self.axes = arena_axes(mesh, axes)
            self.buffer_sharding, self.row_sharding = buf_s, row_s
            self.n_shards = int(
                np.prod([mesh.shape[a] for a in self.axes], dtype=np.int64)
            )
            self._writer = _make_sharded_writer(mesh, self.axes)
            # One jitted grow program per store (cached across growth events;
            # jit re-specializes per (shape, n_new) but never rebuilds the
            # wrapper, unlike a fresh jax.jit per call).
            self._grower = jax.jit(
                _grown_impl, static_argnames=("n_new",), out_shardings=buf_s
            )
            self.padded_params = round_up(self.num_params, row_align * self.n_shards)
        else:
            self.axes = None
            self.buffer_sharding = self.row_sharding = None
            self.n_shards = 1
            self._writer = None
            self._grower = _grown
            self.padded_params = round_up(self.num_params, row_align)
        if arena_dtype == "int8":
            from repro.kernels.quantize import DEFAULT_GROUP

            self.qgroup = int(qgroup or DEFAULT_GROUP)
            if self.shard_width % self.qgroup:
                raise ValueError(
                    f"int8 arena needs the per-shard row width "
                    f"{self.shard_width} divisible by the quant group "
                    f"{self.qgroup}; raise row_align or shrink the group"
                )
            self.buffer_dtype = jnp.dtype(jnp.int8)
        else:
            self.qgroup = int(qgroup) if qgroup else None
            self.buffer_dtype = self.dtype
        if arena_dtype == "topk":
            if sparse_k is None:
                raise ValueError("arena_dtype='topk' needs sparse_k")
            # Rows hold (sparse_k,) coordinate streams against the padded
            # row width, so k clamps to it exactly like the wire codec.
            self.sparse_k = max(1, min(int(sparse_k), self.padded_params))
        else:
            self.sparse_k = None
        n = max(1, int(n_max))
        self._rows: dict[str, int] = {}
        self._valid = np.zeros((n,), bool)
        self._weights_host = np.zeros((n,), np.float32)
        self._versions_host = np.zeros((n,), np.float32)
        if arena_dtype == "topk":
            # Sparse arena: (n, k) f32 values + (n, k) int32 indices, both
            # deliberately **unsharded** even under a mesh — N·k is small by
            # construction and the sharded scatter-accumulate consumes them
            # replicated (only its (P,) output is column-sharded).
            self.buffer = jnp.zeros((n, self.sparse_k), jnp.float32)
            self.indices = jnp.zeros((n, self.sparse_k), jnp.int32)
        else:
            self.buffer = self._zeros(
                (n, self.padded_params), self.buffer_dtype,
                self.buffer_sharding,
            )
            self.indices = None
        # Per-row per-group f32 dequantization scales of the int8 arena: the
        # quantized row is column-aligned with its scales, so both shard with
        # the same column specs (the scale width padded_params/qgroup stays a
        # multiple of n_shards because shard_width % qgroup == 0).
        self.scales = (
            self._zeros((n, self.padded_params // self.qgroup), jnp.float32,
                        self.buffer_sharding)
            if arena_dtype == "int8" else None
        )
        self.weights = jnp.zeros((n,), jnp.float32)
        self.versions = jnp.zeros((n,), jnp.float32)
        self.mask = jnp.zeros((n,), jnp.float32)
        self._telemetry = telemetry if telemetry is not None else Telemetry()
        self._c_writes = self._telemetry.counter("store.arena.total_writes")
        self._c_bytes = self._telemetry.counter("store.arena.bytes_ingested")
        self._c_grows = self._telemetry.counter("store.arena.grow_events")
        self._g_resident = self._telemetry.gauge("store.arena.bytes_resident")
        self._g_resident.set(self.resident_bytes())

    @property
    def total_writes(self) -> int:
        """Deprecated shim for ``telemetry.value('store.arena.total_writes')``."""
        return self._c_writes.value

    @property
    def bytes_ingested(self) -> int:
        """Deprecated shim for ``telemetry.value('store.arena.bytes_ingested')``."""
        return self._c_bytes.value

    @property
    def grow_events(self) -> int:
        """Deprecated shim for ``telemetry.value('store.arena.grow_events')``."""
        return self._c_grows.value

    @staticmethod
    def _zeros(shape, dtype, sharding):
        """Allocate zeros, directly laid out per ``sharding`` when given."""
        if sharding is None:
            return jnp.zeros(shape, dtype)
        return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)()

    # -- capacity -----------------------------------------------------------
    @property
    def n_max(self) -> int:
        """Current row capacity (grows geometrically on demand)."""
        return self.buffer.shape[0]

    @property
    def sharded(self) -> bool:
        """True when the arena buffer is column-sharded over a device mesh."""
        return self.mesh is not None

    @property
    def shard_width(self) -> int:
        """Per-device column width: ``padded_params / n_shards``."""
        return self.padded_params // self.n_shards

    def _grow(self, n_new: int) -> None:
        if self.arena_dtype == "topk":
            # The sparse arrays are unsharded regardless of mesh, so they
            # grow through the plain jitted grower.
            self.buffer = _grown(self.buffer, n_new)
            self.indices = _grown(self.indices, n_new)
        else:
            self.buffer = self._grower(self.buffer, n_new=n_new)
        if self.scales is not None:
            self.scales = self._grower(self.scales, n_new=n_new)
        self.weights = _grown(self.weights, n_new)
        self.versions = _grown(self.versions, n_new)
        self.mask = _grown(self.mask, n_new)
        pad = n_new - len(self._valid)
        self._valid = np.concatenate([self._valid, np.zeros((pad,), bool)])
        self._weights_host = np.concatenate(
            [self._weights_host, np.zeros((pad,), np.float32)]
        )
        self._versions_host = np.concatenate(
            [self._versions_host, np.zeros((pad,), np.float32)]
        )
        self._c_grows.add(1)
        self._g_resident.set(self.resident_bytes())

    def _assign_row(self, learner_id: str) -> int:
        row = self._rows.get(learner_id)
        if row is None:
            row = len(self._rows)
            if row >= self.n_max:
                self._grow(max(2 * self.n_max, row + 1))
            self._rows[learner_id] = row
        return row

    def ensure_row(self, learner_id: str) -> int:
        """Assign (or return) the learner's arena row without writing it.

        The controller calls this at registration so row order follows
        *registration* order, not first-upload arrival order — making
        arena-mode aggregation order deterministic across runs (the
        kill-and-resume parity contract; see ``docs/OBSERVABILITY.md``).
        The row stays invalid until the first :meth:`write`.
        """
        with self.lock:
            return self._assign_row(learner_id)

    # -- writes -------------------------------------------------------------
    def write(
        self, learner_id: str, buffer: jax.Array, weight: float, version: float = 0.0
    ) -> int:
        """Insert/overwrite a learner's packed update in its arena row.

        The (donated) row write is the entire MarkTaskCompleted store cost:
        O(P) device bytes, zero allocation, no host copy.  Returns the row.
        """
        if self.arena_dtype == "topk":
            raise ValueError(
                "a sparse (arena_dtype='topk') arena has no dense rows; "
                "use write_sparse"
            )
        buf = jnp.ravel(jnp.asarray(buffer)).astype(self.dtype)
        if buf.shape[0] not in (self.num_params, self.padded_params):
            raise ValueError(
                f"buffer has {buf.shape[0]} params, arena rows hold "
                f"{self.num_params} (or {self.padded_params} pre-padded)"
            )
        if self.arena_dtype == "int8":
            # Quantize the f32 upload into the resident layout on device,
            # then land it through the quantized write path.  The padded
            # columns quantize to q=0/scale=1.0 exactly (zero-amax fallback).
            from repro.kernels import ops, quantize as quant

            if buf.shape[0] != self.padded_params:
                buf = jnp.pad(buf, (0, self.padded_params - buf.shape[0]))
            q, s = ops.quantize(
                buf, group=self.qgroup,
                block_rows=quant.effective_block_rows(
                    self.padded_params, self.qgroup
                ),
            )
            return self.write_quantized(
                learner_id, q[: self.padded_params],
                s[: self.padded_params // self.qgroup], weight, version,
            )
        if self.sharded:
            if buf.shape[0] != self.padded_params:
                buf = jnp.pad(buf, (0, self.padded_params - buf.shape[0]))
            # Scatter the upload across the mesh once, then write shard-local.
            buf = jax.device_put(buf, self.row_sharding)
        with self.lock:
            row = self._assign_row(learner_id)
            if self.sharded:
                self.buffer = self._writer(self.buffer, jnp.int32(row), buf)
            else:
                self.buffer = _write_row(self.buffer, jnp.int32(row), buf)
            self.weights, self.versions, self.mask = _set_row_meta(
                self.weights, self.versions, self.mask,
                jnp.int32(row), jnp.float32(weight), jnp.float32(version),
            )
            self._valid[row] = True
            self._weights_host[row] = weight
            self._versions_host[row] = version
            self._c_writes.add(1)
            # Cumulative decoded-row ingest bytes: reconciles against the
            # channel's uplink message count in the dispatch tests.
            self._c_bytes.add(int(buf.nbytes))
            return row

    def write_quantized(
        self, learner_id: str, q: jax.Array, scales: jax.Array,
        weight: float, version: float = 0.0,
    ) -> int:
        """Land an already-quantized row (int8 values + f32 group scales).

        The quantized-resident ingest hot path: an int8 upload decoded by
        ``Channel.recv_upload_quantized`` writes straight into the arena with
        **no** intermediate f32 ``(P,)`` materialization — two donated row
        writes (values + scales), same metadata bookkeeping as :meth:`write`.
        Only valid on an ``arena_dtype="int8"`` arena.
        """
        if self.arena_dtype != "int8":
            raise ValueError(
                "write_quantized requires ArenaStore(arena_dtype='int8'); "
                f"this arena is {self.arena_dtype!r}"
            )
        q = jnp.ravel(jnp.asarray(q))
        if q.dtype != jnp.int8:
            raise ValueError(f"quantized row must be int8, got {q.dtype}")
        n_groups = self.padded_params // self.qgroup
        if q.shape[0] != self.padded_params or scales.shape != (n_groups,):
            raise ValueError(
                f"quantized row holds {q.shape[0]} values / "
                f"{scales.shape} scales; this arena wants "
                f"({self.padded_params},) / ({n_groups},)"
            )
        scales = jnp.asarray(scales, jnp.float32)
        if self.sharded:
            q = jax.device_put(q, self.row_sharding)
            scales = jax.device_put(scales, self.row_sharding)
        with self.lock:
            row = self._assign_row(learner_id)
            writer = self._writer if self.sharded else _write_row
            # The same jitted writer serves both arrays: jit re-specializes
            # per (shape, dtype), so values and scales each get a cached
            # executable.
            self.buffer = writer(self.buffer, jnp.int32(row), q)
            self.scales = writer(self.scales, jnp.int32(row), scales)
            self.weights, self.versions, self.mask = _set_row_meta(
                self.weights, self.versions, self.mask,
                jnp.int32(row), jnp.float32(weight), jnp.float32(version),
            )
            self._valid[row] = True
            self._weights_host[row] = weight
            self._versions_host[row] = version
            self._c_writes.add(1)
            self._c_bytes.add(int(q.nbytes) + int(scales.nbytes))
            return row

    def write_sparse(
        self, learner_id: str, indices: jax.Array, values: jax.Array,
        weight: float, version: float = 0.0,
    ) -> int:
        """Land a sparse ``(indices, values)`` upload in its arena row.

        The direct sparse ingest hot path: a topk upload decoded by
        ``Channel.recv_upload_sparse`` writes straight into the ``(n, k)``
        index/value arena — two donated row writes, no densification, same
        metadata bookkeeping as :meth:`write`.  Rows hold *deltas* against
        the model version recorded per row.  Only valid on an
        ``arena_dtype="topk"`` arena.
        """
        if self.arena_dtype != "topk":
            raise ValueError(
                "write_sparse requires ArenaStore(arena_dtype='topk'); "
                f"this arena is {self.arena_dtype!r}"
            )
        idx = jnp.ravel(jnp.asarray(indices))
        val = jnp.ravel(jnp.asarray(values)).astype(jnp.float32)
        if idx.dtype != jnp.int32:
            raise ValueError(f"sparse indices must be int32, got {idx.dtype}")
        if idx.shape != (self.sparse_k,) or val.shape != (self.sparse_k,):
            raise ValueError(
                f"sparse row holds {idx.shape[0]} indices / "
                f"{val.shape[0]} values; this arena wants "
                f"({self.sparse_k},) each"
            )
        with self.lock:
            row = self._assign_row(learner_id)
            # The same jitted writer serves both arrays: jit re-specializes
            # per (shape, dtype), so indices and values each get a cached
            # executable.
            self.indices = _write_row(self.indices, jnp.int32(row), idx)
            self.buffer = _write_row(self.buffer, jnp.int32(row), val)
            self.weights, self.versions, self.mask = _set_row_meta(
                self.weights, self.versions, self.mask,
                jnp.int32(row), jnp.float32(weight), jnp.float32(version),
            )
            self._valid[row] = True
            self._weights_host[row] = weight
            self._versions_host[row] = version
            self._c_writes.add(1)
            self._c_bytes.add(int(idx.nbytes) + int(val.nbytes))
            return row

    def invalidate(self, learner_id: str) -> None:
        """Drop a learner's contribution (row is kept for reuse)."""
        with self.lock:
            row = self._rows.get(learner_id)
            if row is None or not self._valid[row]:
                return
            self._valid[row] = False
            self.mask = self.mask.at[row].set(0.0)

    # -- selection ----------------------------------------------------------
    def row_of(self, learner_id: str) -> int | None:
        """The learner's assigned arena row (None before first upload)."""
        return self._rows.get(learner_id)

    def weight_of(self, learner_id: str) -> float:
        """Host-mirrored aggregation weight of a learner's current upload."""
        with self.lock:
            row = self._rows[learner_id]
            return float(self._weights_host[row])

    def version_of(self, learner_id: str) -> float:
        """Host-mirrored model version a learner's current upload trained from.

        Mirrors the device ``versions`` vector so staleness weights can be
        derived host-side (the secure async path needs them *before* the
        fixed-point masking) without a device round-trip.
        """
        with self.lock:
            row = self._rows[learner_id]
            return float(self._versions_host[row])

    def row_view(self, learner_id: str) -> jax.Array:
        """Device view of one learner's un-padded packed buffer (always f32).

        On a quantized arena the row is dequantized on the fly (one small
        device program) so callers keep the f32 contract; the resident state
        stays int8.
        """
        with self.lock:
            row = self._rows[learner_id]
            if not self._valid[row]:
                raise KeyError(f"{learner_id} has no valid model in the arena")
            if self.arena_dtype == "int8":
                q = self.buffer[row]
                s = self.scales[row]
                x = (q.astype(jnp.float32)
                     .reshape(-1, self.qgroup) * s[:, None]).reshape(-1)
                return x[: self.num_params]
            if self.arena_dtype == "topk":
                from repro.kernels import topk as topk_kernels

                x = topk_kernels.densify(
                    self.indices[row], self.buffer[row], self.padded_params
                )
                return x[: self.num_params]
            return self.buffer[row, : self.num_params]

    def round_mask(self, learner_ids: Sequence[str] | None = None) -> jax.Array:
        """Validity mask restricted to a selection (the round's cohort).

        ``None`` selects every valid row (async protocol).  The mask is the
        only per-round host→device transfer of the arena path: ``n_max``
        floats, independent of model size.
        """
        with self.lock:
            if learner_ids is None:
                return self.mask
            sel = np.zeros((self.n_max,), np.float32)
            for lid in learner_ids:
                row = self._rows.get(lid)
                if row is not None and self._valid[row]:
                    sel[row] = 1.0
            return jnp.asarray(sel)

    def valid_ids(self) -> list[str]:
        """Learners whose arena row currently holds a valid upload."""
        with self.lock:
            return [lid for lid, row in self._rows.items() if self._valid[row]]

    def num_valid(self, learner_ids: Sequence[str] | None = None) -> int:
        """How many of the given learners hold a valid upload (host-side).

        ``None`` counts every valid row.  Answered entirely from the arena's
        host-side row map — no device read, no sync.  This is how the
        controller detects an empty cohort before aggregating: the previous
        ``float(jnp.sum(mask))`` probe forced a device round-trip onto every
        round's critical path.
        """
        with self.lock:
            if learner_ids is None:
                return int(self._valid.sum())
            count = 0
            for lid in learner_ids:
                row = self._rows.get(lid)
                if row is not None and self._valid[row]:
                    count += 1
            return count

    # -- accounting ---------------------------------------------------------
    def __contains__(self, learner_id: str) -> bool:
        with self.lock:
            row = self._rows.get(learner_id)
            return row is not None and bool(self._valid[row])

    def __len__(self) -> int:
        with self.lock:
            return int(self._valid.sum())

    def resident_bytes(self) -> int:
        """Global device bytes held by the arena (buffer + scales + metadata).

        Also published as the ``store.arena.bytes_resident`` gauge after
        every capacity change — the observable half of the int8 arena's ~4x
        resident shrink (int8 values + f32 scales ≈ ``(1 + 4/group)``
        bytes/param vs 4 for f32) and of the sparse arena's k-proportional
        footprint (8 bytes per kept coordinate instead of 4 per parameter).
        """
        scales = self.scales.nbytes if self.scales is not None else 0
        indices = self.indices.nbytes if self.indices is not None else 0
        return int(
            self.buffer.nbytes + scales + indices + self.weights.nbytes
            + self.versions.nbytes + self.mask.nbytes
        )

    # -- checkpointing ------------------------------------------------------
    def export_state(self) -> dict:
        """Host-side copy of the arena's full state (checkpoint save).

        Returns ``buffer`` (the full ``(n_max, padded_params)`` array —
        f32, or int8 for a quantized arena — gathered if sharded), the host
        ``weights``/``versions``/``valid`` mirrors, and the ``rows``
        learner→row map.  A quantized arena additionally returns ``scales``
        (the ``(n_max, padded_params/group)`` f32 array).  Both the f32 and
        the int8+scales round-trips through ``.npz`` are bit-exact, so a
        restored arena aggregates bit-identically.
        """
        with self.lock:
            state = {
                "buffer": np.asarray(jax.device_get(self.buffer)),
                "weights": self._weights_host.copy(),
                "versions": self._versions_host.copy(),
                "valid": self._valid.copy(),
                "rows": dict(self._rows),
            }
            if self.scales is not None:
                state["scales"] = np.asarray(jax.device_get(self.scales))
            if self.indices is not None:
                state["indices"] = np.asarray(jax.device_get(self.indices))
            return state

    def restore_state(
        self,
        buffer: np.ndarray,
        weights: np.ndarray,
        versions: np.ndarray,
        valid: np.ndarray,
        rows: dict[str, int],
        scales: np.ndarray | None = None,
        indices: np.ndarray | None = None,
    ) -> None:
        """Reload a checkpointed arena state (inverse of :meth:`export_state`).

        The arena must have been constructed with the same ``num_params``
        and row alignment (``padded_params`` must match).  Capacity adapts:
        the restored state is padded (or the arena grown) to cover both the
        saved rows and any already-assigned ones.  A quantized arena
        requires ``scales`` (the checkpointed scale matrix); a sparse arena
        requires ``indices`` and the same ``sparse_k`` — restoring across
        arena layouts is a mismatch the caller surfaces via the checkpoint
        fingerprint.
        """
        host_dt = np.int8 if self.arena_dtype == "int8" else np.float32
        row_width = (
            self.sparse_k if self.arena_dtype == "topk" else self.padded_params
        )
        buffer = np.asarray(buffer, host_dt)
        if buffer.ndim != 2 or buffer.shape[1] != row_width:
            raise ValueError(
                f"checkpointed arena rows hold {buffer.shape[-1]} params, "
                f"this arena holds {row_width}"
            )
        if self.arena_dtype == "topk":
            if indices is None:
                raise ValueError(
                    "restoring a sparse arena needs the checkpointed indices"
                )
            indices = np.asarray(indices, np.int32)
            if indices.shape != buffer.shape:
                raise ValueError(
                    f"checkpointed sparse indices have shape {indices.shape}, "
                    f"values have {buffer.shape}"
                )
        if self.arena_dtype == "int8":
            if scales is None:
                raise ValueError(
                    "restoring an int8 arena needs the checkpointed scales"
                )
            scales = np.asarray(scales, np.float32)
            n_groups = self.padded_params // self.qgroup
            if scales.ndim != 2 or scales.shape[1] != n_groups:
                raise ValueError(
                    f"checkpointed scales hold {scales.shape[-1]} groups, "
                    f"this arena wants {n_groups}"
                )
        with self.lock:
            n = max(self.n_max, buffer.shape[0], len(rows))
            full = np.zeros((n, row_width), host_dt)
            full[: buffer.shape[0]] = buffer
            self._valid = np.zeros((n,), bool)
            self._valid[: len(valid)] = np.asarray(valid, bool)
            self._weights_host = np.zeros((n,), np.float32)
            self._weights_host[: len(weights)] = np.asarray(weights, np.float32)
            self._versions_host = np.zeros((n,), np.float32)
            self._versions_host[: len(versions)] = np.asarray(
                versions, np.float32
            )
            self._rows = {str(k): int(v) for k, v in rows.items()}
            if self.buffer_sharding is not None and self.arena_dtype != "topk":
                self.buffer = jax.device_put(full, self.buffer_sharding)
            else:
                self.buffer = jnp.asarray(full)
            if self.arena_dtype == "topk":
                full_i = np.zeros((n, row_width), np.int32)
                full_i[: indices.shape[0]] = indices
                self.indices = jnp.asarray(full_i)
            if self.arena_dtype == "int8":
                full_s = np.zeros(
                    (n, self.padded_params // self.qgroup), np.float32
                )
                full_s[: scales.shape[0]] = scales
                if self.buffer_sharding is not None:
                    self.scales = jax.device_put(full_s, self.buffer_sharding)
                else:
                    self.scales = jnp.asarray(full_s)
            self.weights = jnp.asarray(self._weights_host)
            self.versions = jnp.asarray(self._versions_host)
            self.mask = jnp.asarray(self._valid.astype(np.float32))
            self._g_resident.set(self.resident_bytes())
