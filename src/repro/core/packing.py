"""Flat tensor transport: the MetisFL wire format, JAX-native.

MetisFL ships a model over the network as a sequence of *flattened byte
tensors* plus a small structural proto (shape, dtype, byte order) that lets the
receiver reconstruct the original tensors.  This module is the JAX analogue:

* :func:`pack_bytes` / :func:`unpack_bytes` — the wire format.  A pytree of
  arrays becomes one contiguous ``uint8`` buffer plus a :class:`Manifest`.
  This is what the (simulated) transport layer moves and measures.

* :func:`pack_numeric` / :func:`unpack_numeric` — the aggregation format.  All
  leaves are flattened, cast to a common accumulation dtype and concatenated
  into a single 1-D buffer.  The federation controller aggregates *these*
  buffers: a weighted reduction over ``(n_learners, n_params)`` that is
  embarrassingly parallel across params — the TPU-native statement of the
  paper's one-OpenMP-thread-per-tensor design (Fig. 4).

The manifest is a plain, picklable Python object (no closures), so it can be
generated once by the driver and shipped to every participant, exactly like
MetisFL's proto descriptors.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TensorSpec",
    "Manifest",
    "build_manifest",
    "pack_numeric",
    "unpack_numeric",
    "pack_bytes",
    "pack_bytes_from_numeric",
    "unpack_bytes",
    "WireRun",
    "wire_runs",
    "pack_row_bytes",
    "unpack_row_bytes",
    "num_params",
    "round_up",
]


def round_up(n: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``n``."""
    if multiple <= 0:
        raise ValueError("multiple must be positive")
    return ((n + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Structural descriptor of one tensor on the wire (a proto-tensor)."""

    name: str
    shape: tuple[int, ...]
    dtype: str  # numpy dtype string, e.g. "float32", "bfloat16"
    offset: int  # element offset into the numeric buffer
    size: int  # number of elements

    @property
    def nbytes(self) -> int:
        """Wire size of this tensor in bytes (original dtype)."""
        return self.size * np.dtype(jnp.dtype(self.dtype)).itemsize


@dataclasses.dataclass(frozen=True)
class Manifest:
    """Full structural description of a packed model.

    ``specs`` are ordered by traversal order of the original pytree;
    ``treedef`` reconstructs the container structure.  ``byteorder`` is
    recorded the way MetisFL's proto does, so a receiver on different
    endianness could byteswap (JAX is little-endian everywhere; we record it
    for wire fidelity).
    """

    specs: tuple[TensorSpec, ...]
    treedef: Any
    byteorder: str = "little"

    @property
    def total_elements(self) -> int:
        """Total scalar element count across every packed tensor."""
        return sum(s.size for s in self.specs)

    @property
    def total_bytes(self) -> int:
        """Total wire bytes across every packed tensor."""
        return sum(s.nbytes for s in self.specs)

    def spec_by_name(self, name: str) -> TensorSpec:
        """Look up one tensor's spec by its pytree key-path name."""
        for s in self.specs:
            if s.name == name:
                return s
        raise KeyError(name)


def _leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def build_manifest(params: Any) -> Manifest:
    """Build the structural manifest for a parameter pytree.

    The numeric offsets index into the *accumulation-dtype* buffer produced by
    :func:`pack_numeric` (one element per original element, regardless of the
    original dtype).
    """
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    offset = 0
    for path, leaf in leaves_with_path:
        leaf = jnp.asarray(leaf)
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        specs.append(
            TensorSpec(
                name=_leaf_name(path),
                shape=tuple(int(d) for d in leaf.shape),
                dtype=str(leaf.dtype),
                offset=offset,
                size=size,
            )
        )
        offset += size
    return Manifest(specs=tuple(specs), treedef=treedef)


def num_params(params: Any) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(np.prod(jnp.shape(l)) or 1) for l in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Numeric packing (aggregation format)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dtype", "pad_to"))
def pack_numeric(
    params: Any, dtype: jnp.dtype = jnp.float32, pad_to: int | None = None
) -> jax.Array:
    """Flatten a pytree into one 1-D buffer in the accumulation dtype.

    One jitted program per tree structure, so the buffer is written once,
    with no per-leaf or partial-concatenation copies; under ``pjit`` the
    output buffer inherits a sharding over the flattened dimension, so the
    downstream aggregation reduce is local to every device (no collectives)
    — see ``core/aggregation.py``.

    ``pad_to`` zero-pads the buffer length up to the next multiple — the
    VPU-lane alignment the arena store (``core/store.ArenaStore``) and the
    Pallas kernels tile on, so an aligned upload is one full-row write with no
    per-call padding downstream.  ``unpack_numeric`` is oblivious: the
    manifest records the logical offsets and the zero tail never escapes.
    """
    flat = [jnp.ravel(jnp.asarray(l)).astype(dtype)
            for l in jax.tree_util.tree_leaves(params)]
    n = sum(int(f.shape[0]) for f in flat)
    if pad_to is not None and n % pad_to:
        flat.append(jnp.zeros((round_up(n, pad_to) - n,), dtype))
    if not flat:
        return jnp.zeros((0,), dtype=dtype)
    return jnp.concatenate(flat, axis=0)


def unpack_numeric(buffer: jax.Array, manifest: Manifest) -> Any:
    """Inverse of :func:`pack_numeric`: restore shapes, dtypes and structure."""
    leaves = []
    for spec in manifest.specs:
        seg = jax.lax.slice(buffer, (spec.offset,), (spec.offset + spec.size,))
        leaves.append(seg.reshape(spec.shape).astype(jnp.dtype(spec.dtype)))
    return jax.tree_util.tree_unflatten(manifest.treedef, leaves)


# ---------------------------------------------------------------------------
# Byte packing (wire format)
# ---------------------------------------------------------------------------


def pack_bytes(params: Any) -> tuple[np.ndarray, Manifest]:
    """Serialize a pytree to one contiguous byte buffer (host-side).

    This is the transport representation: it preserves the original dtypes
    bit-exactly (bf16 stays 2 bytes on the wire).  Single-copy: each tensor's
    bytes are written directly into a preallocated wire buffer — the fast
    (de)serialization MetisFL attributes its dispatch-time win to.  Not
    jit-compatible by design; serialization is a controller-edge operation.
    """
    manifest = build_manifest(params)
    out = np.empty((manifest.total_bytes,), np.uint8)
    cursor = 0
    for leaf in jax.tree_util.tree_leaves(params):
        arr = np.ascontiguousarray(np.asarray(leaf))
        n = arr.nbytes
        out[cursor : cursor + n] = arr.reshape(-1).view(np.uint8)
        cursor += n
    return out, manifest


def pack_bytes_from_numeric(buffer: Any, manifest: Manifest) -> np.ndarray:
    """Wire bytes straight off a flat numeric buffer — no pytree walk.

    The serialize-once broadcast path (``core/transport.Channel.broadcast``)
    feeds the controller's already-maintained ``global_buffer`` here instead
    of re-flattening ``global_params`` leaf by leaf: one device→host transfer
    of the whole buffer, then a single ``astype``/byte view when the model is
    dtype-homogeneous (the common case), or one cast per spec otherwise.  A
    zero-padded tail (``pack_numeric(pad_to=...)``) is sliced off.

    Bit-identical to ``pack_bytes(unpack_numeric(buffer, manifest))[0]`` —
    i.e. to serializing exactly the pytree the controller's numeric state
    decodes to.  The wire bytes are always *materialized* (one O(P) copy,
    like ``pack_bytes``), never a zero-copy alias of ``buffer``: the channel
    contract is to perform the real serialization work it accounts for, and
    on accelerator backends the host transfer is unavoidable anyway.
    """
    if not manifest.specs:
        return np.empty((0,), np.uint8)
    host = np.asarray(buffer)[: manifest.total_elements]
    dtypes = {s.dtype for s in manifest.specs}
    if len(dtypes) == 1:
        dt = jnp.dtype(next(iter(dtypes)))
        wire = host.astype(dt, copy=True)
        return wire.reshape(-1).view(np.uint8)
    out = np.empty((manifest.total_bytes,), np.uint8)
    cursor = 0
    for spec in manifest.specs:
        seg = host[spec.offset : spec.offset + spec.size]
        raw = np.ascontiguousarray(seg.astype(jnp.dtype(spec.dtype)))
        out[cursor : cursor + spec.nbytes] = raw.reshape(-1).view(np.uint8)
        cursor += spec.nbytes
    return out


def pack_row_bytes(buffer: Any, dtype: Any = jnp.float32) -> np.ndarray:
    """Wire bytes of one flat ``(P,)`` numeric buffer (the upload row format).

    The uplink mirror of :func:`pack_bytes_from_numeric` for a *single* flat
    buffer with no manifest: one device→host transfer plus one cast/copy,
    then a zero-copy byte view.  Like the downlink path, the wire bytes are
    always *materialized* (one O(P) copy, never an alias of the caller's
    buffer): the channel's contract is to perform the serialization work it
    accounts for, and an aliased envelope would mutate if the caller's buffer
    did.  This is what the transport's ``raw`` upload codec puts on the wire
    — ``P * itemsize`` bytes, bit-identical to the numeric buffer.
    """
    dt = np.dtype(jnp.dtype(dtype))
    host = np.asarray(buffer)
    return host.reshape(-1).astype(dt, copy=True).view(np.uint8)


def wire_view(wire: np.ndarray, start: int, count: int, dtype: Any) -> np.ndarray:
    """``count`` elements of ``dtype`` at byte ``start`` of a wire buffer.

    A zero-copy host view.  Wire bytes are reinterpreted here, on the host,
    and never by a device bitcast: a TPU lays out the ``(count, itemsize)``
    bytes such a bitcast needs with the last axis padded to 128 lanes, so
    decoding a 74M-element f32 row on the device asks for 38 GB.
    """
    dt = np.dtype(jnp.dtype(dtype))
    flat = np.ascontiguousarray(wire).reshape(-1)
    return flat[start : start + count * dt.itemsize].view(dt)


def unpack_row_bytes(wire: np.ndarray, num_elements: int, dtype: Any = "float32") -> jax.Array:
    """Inverse of :func:`pack_row_bytes`: a host view of the wire bytes as
    the ``(P,)`` row, then **one** ``device_put``.

    A controller ingesting N uploads per round pays N single O(P) transfers
    and zero host-side numeric work, regardless of model depth.
    """
    dt = jnp.dtype(dtype)
    if int(np.size(wire)) != int(num_elements) * dt.itemsize:
        raise ValueError(
            f"row payload holds {int(np.size(wire))} bytes, expected "
            f"{int(num_elements) * dt.itemsize} for {num_elements} "
            f"{dt.name} elements"
        )
    return jax.device_put(wire_view(wire, 0, int(num_elements), dt))


class WireRun(NamedTuple):
    """Consecutive wire leaves of one dtype: contiguous bytes, one transfer."""

    start: int  # byte offset of the run in the wire buffer
    count: int  # elements in the run
    dtype: str  # the leaves' dtype; a bool run moves as uint8
    shapes: tuple[tuple[int, ...], ...]  # the run's leaves, in wire order


@functools.lru_cache(maxsize=64)
def wire_runs(manifest: Manifest) -> tuple[WireRun, ...]:
    """The manifest's leaves grouped into runs of one dtype, in wire order.

    Cached by the manifest's value, so every model version and every
    recipient of one model structure reads the same runs.
    """
    runs = []
    start = 0
    for dtype, group in itertools.groupby(manifest.specs, key=lambda s: s.dtype):
        specs = tuple(group)
        runs.append(WireRun(start, sum(s.size for s in specs), dtype,
                            tuple(s.shape for s in specs)))
        start += sum(s.nbytes for s in specs)
    return tuple(runs)


@functools.partial(jax.jit, static_argnums=1)
def _split_runs(arrays: list[jax.Array], runs: tuple[WireRun, ...]) -> list[jax.Array]:
    """Every leaf of the ``(count,)`` run arrays: static slices and reshapes."""
    leaves = []
    for array, run in zip(arrays, runs):
        start = 0
        for shape in run.shapes:
            size = math.prod(shape)
            leaf = jax.lax.slice(array, (start,), (start + size,)).reshape(shape)
            leaves.append(leaf != 0 if run.dtype == "bool" else leaf)
            start += size
    return leaves


def unpack_bytes(buffer: np.ndarray, manifest: Manifest) -> Any:
    """Inverse of :func:`pack_bytes`: one ``device_put`` per dtype run, then
    **one** jitted split into the tree's leaves.

    Consecutive leaves of one dtype (:func:`wire_runs`) are contiguous on the
    wire, so each run is one zero-copy host view (:func:`wire_view`) moved
    as a ``(count,)`` array of its dtype; bool runs move as ``uint8`` and
    compare ``!= 0`` in the split.  The split program is compiled once per
    run layout, so a new model version of the same structure, and every
    other recipient, reuses it.  A float32 model is one transfer and one
    split dispatch, however many leaves it has.
    """
    runs = wire_runs(manifest)
    # The run arrays are not kept: the second copy of the model lives only
    # while the split runs.
    leaves = _split_runs(jax.device_put([
        wire_view(buffer, r.start, r.count, np.uint8 if r.dtype == "bool" else r.dtype)
        for r in runs
    ]), runs)
    return jax.tree_util.tree_unflatten(manifest.treedef, leaves)
