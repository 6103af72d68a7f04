"""Simulated transport layer with measured (de)serialization and byte counts.

MetisFL moves models between controller and learners over gRPC as flat byte
buffers.  This repo has no RPC runtime (DESIGN.md §2), so the transport is an
in-process channel that performs the *real* serialization work
(``core/packing.pack_bytes``), counts bytes, and optionally accounts virtual
wire time from a bandwidth/latency model — so benchmarks can separate compute
cost from modeled network cost without sleeping.

The channel is **full duplex** — both wire directions are measured:

* :meth:`Channel.send` — the legacy point-to-point downlink half: one
  serialization per recipient (kept for parity testing and single-recipient
  messages).
* :meth:`Channel.broadcast` — the downlink fan-out half: serialize **once**
  into a shared read-only byte buffer, then stamp per-recipient envelopes
  with :meth:`Broadcast.to`.  Each ``to()`` charges that recipient's bytes
  and virtual wire time but never re-serializes, so dispatch cost is
  O(P + N) instead of O(N·P).  When the caller already maintains the flat
  numeric buffer (the controller's ``global_buffer``), the wire bytes come
  straight off it (``packing.pack_bytes_from_numeric``) — no pytree walk at
  all.
* :meth:`Channel.upload` / :meth:`Channel.recv_upload` — the **uplink** half.
  A learner's flat ``(P,)`` update buffer is encoded through a pluggable
  upload codec (``raw`` passthrough — 4 bytes/param; ``int8`` blockwise
  quantization via ``kernels/quantize`` — ~3.9x fewer wire bytes) into an
  :class:`UploadEnvelope`, with per-send byte/time accounting; the controller
  decodes it back to a device-resident row from host views of the wire
  bytes (``packing.wire_view``): one ``device_put`` plus, for ``int8``, a
  jitted dequant program, ready for a straight arena row write.
  Uplink is the dominant wire direction (N uploads vs 1 broadcast per round),
  so this is where the codec pays off.

All stats mutation is lock-guarded: the controller's async protocol calls
``send``/``recv``/``upload``/``recv_upload``/``Broadcast.to`` concurrently
from executor threads.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.metrics import Telemetry

__all__ = [
    "ChannelStats", "Channel", "Envelope", "Broadcast",
    "UploadEnvelope", "RawUploadCodec", "Int8UploadCodec",
    "TopkUploadCodec", "UPLOAD_CODECS", "get_upload_codec",
]


#: The channel's telemetry counter names (registered as ``channel.<field>``).
_STAT_FIELDS = (
    "messages", "bytes_moved", "serializations", "serialize_s",
    "deserialize_s", "recv_transfers", "virtual_wire_s", "upload_messages",
    "upload_bytes", "upload_meta_bytes", "upload_serializations",
    "upload_serialize_s", "upload_deserialize_s", "upload_virtual_wire_s",
)


class ChannelStats:
    """Transport accounting for one channel — a **view** over its telemetry.

    Deprecated read shim: every field that used to be a dataclass attribute
    is now a property reading the ``channel.<field>`` counter from the
    channel's :class:`~repro.core.metrics.Telemetry` registry, so existing
    call sites (``ch.stats.upload_bytes``) keep working while the registry
    (``controller.telemetry`` / ``channel.telemetry``) is the documented
    surface.

    Downlink (controller → learners): ``messages``/``bytes_moved``/
    ``virtual_wire_s`` count per *recipient* (a broadcast to N learners
    counts N); ``serializations``/``serialize_s`` count actual serialization
    work (the same broadcast counts 1).

    Uplink (learners → controller): ``upload_messages``/``upload_bytes``/
    ``upload_virtual_wire_s`` count one per :meth:`Channel.upload`
    (``upload_bytes`` is the codec *payload*; the envelope's serialized
    header — codec id, element count, metadata, codec params — is counted
    separately in ``upload_meta_bytes``, and virtual wire time covers
    both, so the accounting is envelope-exact even for variable-length
    sparse payloads);
    ``upload_serializations``/``upload_serialize_s`` count the codec encode
    work and ``upload_deserialize_s`` the controller-side decode.  Every
    upload is its own serialization (no fan-in sharing), so
    ``upload_messages == upload_serializations`` always.

    Counters are mutated only by :class:`Channel` under its stats lock —
    safe to read from tests after joining worker threads.
    """

    def __init__(self, telemetry: Telemetry | None = None):
        self._telemetry = telemetry if telemetry is not None else Telemetry()

    @property
    def total_bytes(self) -> int:
        """Bytes moved across both wire directions (downlink + uplink)."""
        return self.bytes_moved + self.upload_bytes

    @property
    def total_virtual_wire_s(self) -> float:
        """Modeled wire time across both directions."""
        return self.virtual_wire_s + self.upload_virtual_wire_s

    def __repr__(self) -> str:
        fields = ", ".join(f"{f}={getattr(self, f)!r}" for f in _STAT_FIELDS)
        return f"ChannelStats({fields})"


def _stats_view_property(field: str) -> property:
    """Build one deprecated ChannelStats read property over ``channel.<field>``."""

    def _get(self: ChannelStats):
        return self._telemetry.value(f"channel.{field}", 0)

    _get.__name__ = field
    _get.__doc__ = (
        f"Deprecated shim for ``telemetry.value('channel.{field}')``."
    )
    return property(_get)


for _field in _STAT_FIELDS:
    setattr(ChannelStats, _field, _stats_view_property(_field))
del _field


# ---------------------------------------------------------------------------
# Upload codecs (uplink wire formats)
# ---------------------------------------------------------------------------


class RawUploadCodec:
    """Passthrough upload codec: f32 row bytes on the wire (4 bytes/param).

    Bit-transparent: ``decode(encode(x)) == x`` for any float32 buffer, so
    protocols that assert bit-identical parity run through it unchanged.
    """

    codec_id = "raw"

    def wire_params(self) -> dict:
        """Codec parameters a receiver needs to decode (none for raw)."""
        return {}

    def wire_nbytes(self, num_elements: int) -> int:
        """Modeled wire payload size for a buffer of ``num_elements``."""
        return 4 * int(num_elements)

    def encode(self, buffer: Any) -> np.ndarray:
        """Flat ``(P,)`` numeric buffer → its f32 wire bytes (one copy)."""
        return packing.pack_row_bytes(buffer, jnp.float32)

    def decode(self, payload: np.ndarray, num_elements: int) -> jax.Array:
        """Wire bytes → device-resident f32 ``(P,)`` row (one transfer)."""
        return packing.unpack_row_bytes(payload, num_elements, "float32")

    def decode_with_norm(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[jax.Array, jax.Array]:
        """Decode, then the L2 norm as the upload's one device program.

        The admission-screen fast path: the norm comes back as a device
        scalar enqueued behind the transfer, so the controller's only host
        sync per upload is reading the already-materialized float.
        """
        row = self.decode(payload, num_elements)
        return row, _row_norm(row)


@jax.jit
def _row_norm(row: jax.Array) -> jax.Array:
    """Device-side L2 norm of a decoded row."""
    return jnp.linalg.norm(row.astype(jnp.float32))


def _put_quant_wire(payload: np.ndarray, n_q: int, n_scales: int, group: int):
    """One int8 upload payload as device ``(q int8 (n_q,), scales f32)``.

    Split on the host by zero-copy views (``packing.wire_view``), then one
    batched ``device_put``.  The wire carries only the
    ``n_scales = ceil(n/group)`` informative scales
    (``kernels/quantize.wire_layout`` trims pure-padding groups); the
    remaining trailing groups are re-synthesized here as exactly 1.0 — the
    quantize kernel's zero-amax fallback — so the round-trip stays
    bit-identical to an untrimmed wire.
    """
    q = packing.wire_view(payload, 0, n_q, np.int8)
    scales = packing.wire_view(payload, n_q, n_scales, np.float32)
    n_pad = n_q // group - n_scales
    if n_pad > 0:
        scales = np.concatenate([scales, np.ones((n_pad,), np.float32)])
    return jax.device_put((q, scales))


@functools.partial(jax.jit, static_argnames=("num_elements", "group", "block_rows"))
def _int8_decode_norm(q, scales, num_elements, group, block_rows):
    """One jitted program: dequantize + L2 norm.

    The decode and the admission norm compile into a single cached
    executable per wire layout, so ingest enqueues one device program and
    never blocks.
    """
    from repro.kernels import ops as kops
    from repro.kernels import quantize as quant

    row = quant.dequantize_pallas(
        q, scales, group, block_rows, interpret=kops.interpret_mode()
    )[:num_elements]
    return row, jnp.linalg.norm(row)


@functools.partial(jax.jit, static_argnames=("out_params", "group"))
def _decode_quant_resident(q, scales, out_params, group):
    """Land one int8 upload in quantized form: (q int8, scales f32, norm).

    The quantized-resident arena's ingest program: slice the wire's values
    and scales to the arena row width — **no f32 (P,) row is ever
    materialized**.  The admission norm is computed from the quantized
    form directly, ``sqrt(Σ_g scale_g² · Σ_i q_{g,i}²)``, which equals the
    L2 norm of the dequantized row exactly (dequantization is a per-group
    scalar multiply), so screening decisions match the f32 path bit-for-bit
    up to f32 summation order.
    """
    q = jax.lax.slice(q, (0,), (out_params,))
    scales = jax.lax.slice(scales, (0,), (out_params // group,))
    qf = q.astype(jnp.float32).reshape(out_params // group, group)
    norm = jnp.sqrt(jnp.sum(scales * scales * jnp.sum(qf * qf, axis=1)))
    return q, scales, norm


class Int8UploadCodec:
    """Blockwise-int8 upload codec (``kernels/quantize``): ~3.9x fewer bytes.

    Encode runs the jitted Pallas quantize kernel over the learner's flat
    ``(P,)`` buffer (symmetric per-group scales, group a multiple of 128 so
    VPU lanes stay full) and concatenates ``int8`` values + ``f32`` scales
    into one wire payload.  The kernel block height adapts to the buffer
    (``kernels/quantize.effective_block_rows``): buffers under one tile pad
    zero rows and larger buffers pad at most ~6.25% of their rows, so the
    compression ratio is ≈3.94x at block-aligned sizes and never drops below
    ~3.6x once P reaches one group — there is no size band where the pad to
    the next whole tile halves the saving.  Decode is a host split of the
    payload, one ``device_put`` and the Pallas dequant kernel — the
    decoded f32 row is ready for a straight arena row write with zero
    host-side numeric work.  Lossy to the int8 step (~0.4% relative); use
    ``raw`` where bit-identity matters.
    """

    codec_id = "int8"

    def __init__(self, group: int | None = None, block_rows: int | None = None):
        from repro.kernels import quantize as quant

        self.group = int(group or quant.DEFAULT_GROUP)
        self.block_rows = int(block_rows or quant.DEFAULT_BLOCK_ROWS)

    def wire_params(self) -> dict:
        """Codec parameters the receiver needs to derive the wire layout."""
        return {"group": self.group, "block_rows": self.block_rows}

    def wire_nbytes(self, num_elements: int) -> int:
        """Modeled wire payload size: int8 values + f32 scales."""
        from repro.kernels import quantize as quant

        return quant.wire_layout(int(num_elements), self.group, self.block_rows)[2]

    def encode(self, buffer: Any) -> np.ndarray:
        """Quantize a flat ``(P,)`` buffer into int8 values + f32 scales.

        Only the ``ceil(P/group)`` informative scales go on the wire
        (``wire_layout``); trailing pure-padding groups carry ``q == 0``
        with scale exactly 1.0, which the decoder re-synthesizes from ``P``
        alone, so trimming them is lossless *and* byte-exact.
        """
        from repro.kernels import ops, quantize as quant

        flat = jnp.asarray(buffer, jnp.float32).reshape(-1)
        q, scales = ops.quantize(
            flat, group=self.group,
            block_rows=quant.effective_block_rows(
                flat.shape[0], self.group, self.block_rows
            ),
        )
        n_scales = quant.wire_layout(
            int(flat.shape[0]), self.group, self.block_rows
        )[1]
        qb = np.asarray(q).view(np.uint8).reshape(-1)
        sb = np.asarray(scales)[:n_scales].view(np.uint8).reshape(-1)
        out = np.empty((qb.size + sb.size,), np.uint8)
        out[: qb.size] = qb
        out[qb.size:] = sb
        return out

    def _checked_layout(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[int, int]:
        """Validate payload size against the wire layout; return (n_q, n_scales)."""
        from repro.kernels import quantize as quant

        n_q, n_scales, nbytes = quant.wire_layout(
            num_elements, self.group, self.block_rows
        )
        if int(payload.size) != nbytes:
            raise ValueError(
                f"int8 payload holds {int(payload.size)} bytes, expected "
                f"{nbytes} for {num_elements} elements"
            )
        return n_q, n_scales

    def decode(self, payload: np.ndarray, num_elements: int) -> jax.Array:
        """Dequantize an int8 payload back to the f32 ``(P,)`` row."""
        return self.decode_with_norm(payload, num_elements)[0]

    def decode_with_norm(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[jax.Array, jax.Array]:
        """Decode + L2 norm in one jitted device program (no host sync).

        Same contract as :meth:`RawUploadCodec.decode_with_norm`: one
        ``device_put``, one cached executable, norm as a device scalar.
        """
        from repro.kernels import quantize as quant

        n_q, n_scales = self._checked_layout(payload, num_elements)
        q, scales = _put_quant_wire(payload, n_q, n_scales, self.group)
        return _int8_decode_norm(
            q, scales, int(num_elements), self.group,
            quant.effective_block_rows(
                int(num_elements), self.group, self.block_rows
            ),
        )

    def decode_quantized(
        self, payload: np.ndarray, num_elements: int, out_params: int
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Decode an int8 payload straight into arena-resident quantized form.

        Returns ``(q int8 (out_params,), scales f32 (out_params//group,),
        norm)`` from one jitted program — no intermediate f32 ``(P,)`` row.
        ``out_params`` (the arena's padded row width) must be a multiple of
        ``group`` and at most the payload's padded element count.
        """
        n_q, n_scales = self._checked_layout(payload, num_elements)
        out_params = int(out_params)
        if out_params % self.group or out_params > n_q:
            raise ValueError(
                f"out_params={out_params} must be a multiple of "
                f"group={self.group} and <= the payload's {n_q} padded "
                "elements"
            )
        q, scales = _put_quant_wire(payload, n_q, n_scales, self.group)
        return _decode_quant_resident(q, scales, out_params, self.group)


def _split_topk_wire(payload, k_eff, n_scales, group, value_dtype):
    """Split one topk payload into device (idx int32, val f32, norm).

    The index block and the value block (f32, or int8 values plus their
    group scales) are host views of the wire (``packing.wire_view``) moved
    by one batched ``device_put``; one cached executable per wire layout
    dequantizes the values and computes the sparse L2 norm.  Top-k indices
    are unique within one upload, so ``‖val‖₂`` **is** the L2 norm of the
    densified row — the admission screen reads the same scalar the dense
    codecs produce, without ever materializing the ``(P,)`` row.
    """
    idx = packing.wire_view(payload, 0, k_eff, np.int32)
    if value_dtype == "f32":
        vals = (packing.wire_view(payload, 4 * k_eff, k_eff, np.float32),)
    else:
        vals = (packing.wire_view(payload, 4 * k_eff, k_eff, np.int8),
                packing.wire_view(payload, 5 * k_eff, n_scales, np.float32))
    idx, vals = jax.device_put((idx, vals))
    return (idx, *_topk_values_norm(vals, group))


@functools.partial(jax.jit, static_argnames=("group",))
def _topk_values_norm(vals, group):
    """f32 values (dequantized when int8-grouped) and their L2 norm."""
    from repro.kernels import topk as topk_kernels

    val = vals[0] if len(vals) == 1 else topk_kernels.dequantize_values(*vals, group)
    return val, jnp.linalg.norm(val)


@functools.partial(jax.jit, static_argnames=("num_elements",))
def _densify(idx, val, num_elements):
    """Scatter a sparse upload into a dense ``(P,)`` delta row.

    The densify fallback for consumers that need a dense row (the
    ``densify`` sparse mode, the stack store, median/trimmed_mean);
    the direct sparse path never calls this.
    """
    return jnp.zeros((num_elements,), jnp.float32).at[idx].add(val)


class TopkUploadCodec:
    """Magnitude top-k upload codec (``kernels/topk``): the 10-100x regime.

    Encodes the ``k`` largest-|x| coordinates of the learner's flat ``(P,)``
    **delta** buffer as ``(indices:int32, values:f32|int8-grouped)`` — at
    ``k = P/64`` with f32 values the payload is ``P/8`` bytes, 32x below
    raw and ~8x below int8.  Lossy per upload by construction; the learner's
    error-feedback residual (``core/learner.py``) carries the unsent mass
    forward, so the scheme is unbiased over rounds.  ``k`` clamps per
    buffer to ``[1, P]`` (tiny layers ship everything they have) while the
    envelope's ``codec_params`` stay constant — ``k_eff`` is re-derived
    from ``num_elements`` on the decode side, so variable-length payloads
    need no extra wire state.

    Unlike ``raw``/``int8`` this codec moves *deltas*, not parameters: the
    decoded row is the learner's sparsified update against the model it
    received, and the controller adds the aggregated delta onto the global
    buffer at commit.
    """

    codec_id = "topk"

    def __init__(
        self, k: int = 64, value_dtype: str = "f32",
        group: int | None = None,
    ):
        from repro.kernels import topk as topk_kernels

        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"topk codec needs k >= 1, got {k!r}")
        if value_dtype not in topk_kernels.VALUE_DTYPES:
            raise ValueError(
                f"value_dtype must be one of {topk_kernels.VALUE_DTYPES}, "
                f"got {value_dtype!r}"
            )
        self.value_dtype = str(value_dtype)
        self.group = int(group or topk_kernels.DEFAULT_VALUE_GROUP)
        if self.group < 1:
            raise ValueError(f"topk codec needs group >= 1, got {group!r}")

    def wire_params(self) -> dict:
        """Codec parameters the receiver needs to derive the wire layout."""
        return {
            "k": self.k, "value_dtype": self.value_dtype, "group": self.group,
        }

    def wire_nbytes(self, num_elements: int) -> int:
        """Modeled wire payload size: int32 indices + (f32|int8+scale) values."""
        from repro.kernels import topk as topk_kernels

        return topk_kernels.wire_layout_topk(
            int(num_elements), self.k, self.value_dtype, self.group
        )[2]

    def encode(self, buffer: Any) -> np.ndarray:
        """Select top-k by magnitude and pack ``(indices, values)`` bytes."""
        from repro.kernels import topk as topk_kernels

        flat = jnp.asarray(buffer, jnp.float32).reshape(-1)
        k_eff = topk_kernels.effective_k(int(flat.shape[0]), self.k)
        idx, val = topk_kernels.topk_select(flat, k_eff)
        parts = [np.asarray(idx).view(np.uint8).reshape(-1)]
        if self.value_dtype == "f32":
            parts.append(np.asarray(val).view(np.uint8).reshape(-1))
        else:
            q, scales = topk_kernels.quantize_values(val, self.group)
            parts.append(np.asarray(q).view(np.uint8).reshape(-1))
            parts.append(np.asarray(scales).view(np.uint8).reshape(-1))
        return np.concatenate(parts)

    def _checked_layout(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[int, int]:
        """Validate payload size against the layout; return (k_eff, n_scales)."""
        from repro.kernels import topk as topk_kernels

        k_eff, n_scales, nbytes = topk_kernels.wire_layout_topk(
            int(num_elements), self.k, self.value_dtype, self.group
        )
        if int(payload.size) != nbytes:
            raise ValueError(
                f"topk payload holds {int(payload.size)} bytes, expected "
                f"{nbytes} for {num_elements} elements at k={self.k}"
            )
        return k_eff, n_scales

    def unpack_coords(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[jax.Array, jax.Array]:
        """Wire bytes → ``(indices int32, values f32)`` device pair.

        The learner-side half of the error-feedback subtraction: values
        come back *dequantized*, i.e. exactly what the controller will
        see, so ``residual -= sent`` carries the quantization error too.
        """
        k_eff, n_scales = self._checked_layout(payload, num_elements)
        idx, val, _ = _split_topk_wire(
            payload, k_eff, n_scales, self.group, self.value_dtype
        )
        return idx, val

    def decode(self, payload: np.ndarray, num_elements: int) -> jax.Array:
        """Densify a sparse payload into the f32 ``(P,)`` delta row."""
        return self.decode_with_norm(payload, num_elements)[0]

    def decode_with_norm(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[jax.Array, jax.Array]:
        """Densify + L2 norm as device programs (no host sync)."""
        idx, val, norm = self.decode_sparse(payload, num_elements)
        return _densify(idx, val, int(num_elements)), norm

    def decode_sparse(
        self, payload: np.ndarray, num_elements: int
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Decode to sparse ``(indices, values, norm)`` — no densification.

        The direct sparse arena's ingest half: one ``device_put`` plus one
        cached split program; the norm is the sparse L2 (== the dense
        row's norm, indices being unique) as an unread device scalar.
        """
        k_eff, n_scales = self._checked_layout(payload, num_elements)
        return _split_topk_wire(
            payload, k_eff, n_scales, self.group, self.value_dtype
        )


UPLOAD_CODECS = {
    "raw": RawUploadCodec, "int8": Int8UploadCodec, "topk": TopkUploadCodec,
}


def _codec_params(codec: Any) -> dict:
    """The codec's self-describing wire parameters ({} if it declares none)."""
    wire_params = getattr(codec, "wire_params", None)
    return wire_params() if wire_params is not None else {}


def get_upload_codec(spec: Any) -> Any:
    """Resolve an upload codec: a registry id (``"raw"``/``"int8"``), an
    already-constructed codec object, or ``None`` (→ raw).

    A codec object must declare a string ``codec_id`` (stamped on every
    envelope).  Note that envelopes of codecs *outside* the registry can only
    be decoded by a channel configured with an equivalent codec — see
    :class:`UploadEnvelope` for the exact contract.
    """
    if spec is None:
        return RawUploadCodec()
    if isinstance(spec, str):
        try:
            return UPLOAD_CODECS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown upload codec {spec!r}; known: {sorted(UPLOAD_CODECS)}"
            ) from None
    if not isinstance(getattr(spec, "codec_id", None), str):
        raise ValueError(
            "an upload codec object must define a string `codec_id` "
            f"attribute; got {type(spec).__name__}"
        )
    return spec


@dataclasses.dataclass(frozen=True)
class UploadEnvelope:
    """One learner→controller message on the wire.

    ``payload`` is the codec's byte buffer (read-only); ``codec`` names the
    encoding and ``codec_params`` carries its layout parameters (e.g. the
    int8 group/block sizes); ``num_elements`` is the logical ``(P,)`` length
    the payload decodes to (codec-internal padding is derivable from it).
    Envelopes of **registry** codecs (``UPLOAD_CODECS``: raw, int8) are fully
    self-describing — any channel decodes them with no out-of-band state.  An
    envelope minted by a custom codec *object* decodes only on a channel
    whose configured codec has the same ``codec_id`` and wire params (the
    registry cannot reconstruct a class it does not know).
    """

    codec: str
    payload: np.ndarray
    num_elements: int
    metadata: dict
    codec_params: dict = dataclasses.field(default_factory=dict)

    @property
    def meta_nbytes(self) -> int:
        """Serialized size of the envelope header (everything but payload).

        Canonical JSON (sorted keys, no whitespace) over the codec id,
        element count, metadata and codec params — the bytes a real RPC
        framing would spend on the envelope around the payload.  Counted
        in ``channel.upload_meta_bytes`` so uplink accounting reconciles
        envelope-exactly even when payload sizes vary per upload.
        """
        return len(json.dumps(
            {
                "codec": self.codec,
                "num_elements": int(self.num_elements),
                "metadata": self.metadata,
                "codec_params": self.codec_params,
            },
            sort_keys=True, separators=(",", ":"), default=str,
        ).encode("utf-8"))

    @property
    def wire_nbytes(self) -> int:
        """Total uplink bytes this envelope occupies: payload + header."""
        return int(self.payload.nbytes) + self.meta_nbytes


@dataclasses.dataclass(frozen=True)
class Envelope:
    """One message on the wire: byte buffer + manifest + metadata.

    Envelopes minted by :meth:`Broadcast.to` share one read-only buffer and
    manifest across all recipients; only ``metadata`` is per-recipient.
    """

    buffer: np.ndarray
    manifest: packing.Manifest
    metadata: dict


class Broadcast:
    """One serialized payload fanned out to many recipients.

    Created by :meth:`Channel.broadcast`.  The byte buffer and manifest are
    serialized exactly once and shared read-only; :meth:`to` mints a
    per-recipient :class:`Envelope` and charges that recipient's bytes and
    virtual wire time on the owning channel.  Thread-safe: ``to`` may be
    called concurrently from dispatch executor threads.
    """

    def __init__(
        self,
        channel: "Channel",
        buffer: np.ndarray,
        manifest: packing.Manifest,
        metadata: dict,
    ):
        try:
            buffer.flags.writeable = False  # shared across recipients
        except ValueError:
            pass  # already a read-only view (e.g. of a jax host buffer)
        self._channel = channel
        self.buffer = buffer
        self.manifest = manifest
        self._metadata = metadata
        self._lock = threading.Lock()
        self.recipients = 0

    def to(self, metadata: dict | None = None) -> Envelope:
        """Mint one recipient's envelope: shared bytes, fresh metadata.

        Per-recipient accounting (message count, bytes, virtual wire time)
        happens here; serialization happened once, at broadcast creation.
        """
        md = dict(self._metadata)
        if metadata:
            md.update(metadata)
        self._channel._account_send(
            int(self.buffer.nbytes), md.get("learner_id")
        )
        with self._lock:
            self.recipients += 1
        return Envelope(buffer=self.buffer, manifest=self.manifest, metadata=md)


class Channel:
    """A measured full-duplex channel (controller <-> learner).

    ``bandwidth_gbps``/``latency_ms`` feed the *virtual* wire-time account;
    they never block real execution.  ``quantize_codec`` optionally compresses
    the downlink pytree payload (beyond-paper int8 transport,
    ``kernels/quantize``); ``upload_codec`` selects the uplink wire format for
    flat ``(P,)`` update buffers (``"raw"`` default, ``"int8"`` blockwise
    quantization, or a codec object).

    All wire accounting lives as ``channel.*`` counters in ``telemetry``
    (the channel's own :class:`~repro.core.metrics.Telemetry` registry by
    default; the controller adopts it as ``controller.telemetry``).
    ``stats`` is the deprecated :class:`ChannelStats` read view over the
    same counters.
    """

    def __init__(
        self,
        bandwidth_gbps: float = 10.0,
        latency_ms: float = 0.5,
        quantize_codec: Any | None = None,
        upload_codec: Any = "raw",
        telemetry: Telemetry | None = None,
    ):
        self.bandwidth_gbps = bandwidth_gbps
        self.latency_ms = latency_ms
        self.learner_bandwidth_gbps: dict[str, float] = {}
        self.codec = quantize_codec
        self.upload_codec = get_upload_codec(upload_codec)
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._c = {
            f: self.telemetry.counter(f"channel.{f}") for f in _STAT_FIELDS
        }
        self.stats = ChannelStats(self.telemetry)
        self._stats_lock = threading.Lock()

    def set_learner_bandwidth(self, learner_id: str, gbps: float) -> None:
        """Cap one learner's modeled bandwidth (both wire halves).

        Sends and uploads stamped with that ``learner_id`` charge virtual
        wire time against the per-learner cap instead of the channel-wide
        ``bandwidth_gbps``; the stress harness uses this to model
        heterogeneous last-mile links.  Idempotent; purely virtual.
        """
        if gbps <= 0:
            raise ValueError(f"bandwidth cap must be positive, got {gbps}")
        self.learner_bandwidth_gbps[learner_id] = float(gbps)

    # -- accounting ---------------------------------------------------------
    def _wire_time(self, nbytes: int, learner_id: str | None = None) -> float:
        gbps = self.learner_bandwidth_gbps.get(learner_id, self.bandwidth_gbps)
        return self.latency_ms / 1e3 + nbytes * 8 / (gbps * 1e9)

    def round_trip_s(
        self, down_nbytes: int, up_nbytes: int,
        learner_id: str | None = None,
    ) -> float:
        """Modeled round-trip wire time for one dispatch + one upload.

        The per-learner estimate the wire-cost-aware semi-sync sizing
        consumes (``Controller.wire_time_s``): the downlink broadcast
        envelope and the uplink codec payload each pay the channel's
        latency plus their serialization time at the modeled bandwidth.
        Purely virtual — it never sleeps, exactly like the per-send
        ``ChannelStats`` accounting it mirrors.
        """
        return (self._wire_time(int(down_nbytes), learner_id)
                + self._wire_time(int(up_nbytes), learner_id))

    def _account_send(self, nbytes: int, learner_id: str | None = None) -> None:
        with self._stats_lock:
            self._c["messages"].add(1)
            self._c["bytes_moved"].add(nbytes)
            self._c["virtual_wire_s"].add(self._wire_time(nbytes, learner_id))

    def _account_serialize(self, dt: float) -> None:
        with self._stats_lock:
            self._c["serializations"].add(1)
            self._c["serialize_s"].add(dt)

    def _span(self, name: str, metadata: dict | None):
        """The channel's span ``name``, carrying the message's round and learner."""
        md = metadata or {}
        return self.telemetry.span(
            name, round=md.get("round_id"), learner=md.get("learner_id")
        )

    # -- send halves --------------------------------------------------------
    def send(self, params: Any, metadata: dict | None = None) -> Envelope:
        """Serialize a pytree for one recipient (the legacy per-send half).

        Timed by the ``channel.send`` span into ``serialize_s``.
        """
        with self._span("channel.send", metadata) as span:
            if self.codec is not None:
                params = self.codec.encode(params)
            buf, manifest = packing.pack_bytes(params)
        self._account_serialize(span.seconds)
        self._account_send(int(buf.nbytes))
        return Envelope(buffer=buf, manifest=manifest, metadata=dict(metadata or {}))

    def broadcast(
        self,
        params: Any = None,
        metadata: dict | None = None,
        *,
        buffer: Any = None,
        manifest: packing.Manifest | None = None,
    ) -> Broadcast:
        """Serialize **once** for a fan-out; recipients pay only wire time.

        With ``buffer=``/``manifest=`` (the controller's flat numeric
        ``global_buffer`` plus its cached manifest) and no codec, the wire
        bytes come straight off the flat buffer
        (``packing.pack_bytes_from_numeric``) — zero pytree flattening.
        Otherwise falls back to ``pack_bytes(params)`` (the codec, when set,
        is applied to ``params``) — still exactly one serialization.

        Per-recipient byte/wire-time accounting happens at each
        :meth:`Broadcast.to`; this call accounts only the serialization,
        timed by the ``channel.broadcast`` span (it waits for the device
        to hand over the bytes).
        """
        with self._span("channel.broadcast", metadata) as span:
            if (buffer is not None and manifest is not None
                    and self.codec is None):
                wire = packing.pack_bytes_from_numeric(buffer, manifest)
                m = manifest
            else:
                src = params if self.codec is None else self.codec.encode(params)
                wire, m = packing.pack_bytes(src)
        self._account_serialize(span.seconds)
        return Broadcast(self, wire, m, dict(metadata or {}))

    # -- receive ------------------------------------------------------------
    def recv(self, envelope: Envelope) -> Any:
        """Deserialize at the receiver half.

        ``packing.unpack_bytes`` moves each run of same-dtype leaves with one
        host-to-device transfer and splits them into the tree with one
        cached program; ``recv_transfers`` counts the transfers (1 for a
        dtype-homogeneous model).  Timed by the ``channel.recv`` span into
        ``deserialize_s``: the transfers and the enqueue of the split and of
        the codec's decode, as far as they block the receiving thread.
        """
        with self._span("channel.recv", envelope.metadata) as span:
            params = packing.unpack_bytes(envelope.buffer, envelope.manifest)
            if self.codec is not None:
                params = self.codec.decode(params)
        with self._stats_lock:
            self._c["deserialize_s"].add(span.seconds)
            self._c["recv_transfers"].add(len(packing.wire_runs(envelope.manifest)))
        return params

    # -- upload half (learner -> controller) --------------------------------
    def _resolve_upload_codec(self, envelope: UploadEnvelope) -> Any:
        # The channel's own codec decodes its own envelopes; anything else is
        # reconstructed from the envelope's self-describing codec id + params.
        own = self.upload_codec
        if (envelope.codec == own.codec_id
                and envelope.codec_params == _codec_params(own)):
            return own
        try:
            cls = UPLOAD_CODECS[envelope.codec]
        except KeyError:
            raise ValueError(
                f"cannot decode upload codec {envelope.codec!r}; "
                f"known: {sorted(UPLOAD_CODECS)}"
            ) from None
        return cls(**envelope.codec_params)

    def upload(
        self, buffer: Any, metadata: dict | None = None, codec: Any = None
    ) -> UploadEnvelope:
        """Learner half of the uplink: encode one flat ``(P,)`` update buffer.

        The buffer is encoded through the channel's upload codec (or an
        explicit ``codec=`` override) into a wire payload; encode time is
        accounted as upload serialization work and the envelope's bytes and
        virtual wire time are charged per send, under the stats lock (the
        async protocol uploads concurrently from executor threads).
        Accounting is **envelope-exact**: ``upload_bytes`` counts this
        payload's actual size (variable-length codecs like ``topk`` differ
        per upload when k clamps at tiny buffers) and ``upload_meta_bytes``
        the serialized envelope header; virtual wire time covers both.
        The encode, the row's device-to-host copy included, is timed by the
        ``channel.upload`` span into ``upload_serialize_s``.
        """
        c = self.upload_codec if codec is None else get_upload_codec(codec)
        n = int(np.shape(buffer)[0])
        with self._span("channel.upload", metadata) as span:
            payload = c.encode(buffer)
        dt = span.seconds
        payload.flags.writeable = False  # wire bytes are immutable
        envelope = UploadEnvelope(
            codec=c.codec_id, payload=payload, num_elements=n,
            metadata=dict(metadata or {}), codec_params=_codec_params(c),
        )
        nbytes = int(payload.nbytes)
        meta_nbytes = envelope.meta_nbytes
        with self._stats_lock:
            self._c["upload_serializations"].add(1)
            self._c["upload_serialize_s"].add(dt)
            self._c["upload_messages"].add(1)
            self._c["upload_bytes"].add(nbytes)
            self._c["upload_meta_bytes"].add(meta_nbytes)
            self._c["upload_virtual_wire_s"].add(
                self._wire_time(
                    nbytes + meta_nbytes, (metadata or {}).get("learner_id")
                )
            )
        return envelope

    def recv_upload(
        self, envelope: UploadEnvelope, with_norm: bool = False
    ) -> jax.Array | tuple[jax.Array, jax.Array]:
        """Controller half of the uplink: decode wire bytes to a device row.

        One ``device_put`` of host views of the payload, plus for ``int8`` a
        jitted Pallas dequant program cached per wire layout — the returned
        f32 ``(P,)`` row feeds a straight arena row write with zero
        host-side numeric work.

        With ``with_norm=True`` returns ``(row, norm)`` where ``norm`` is the
        row's L2 norm as a **device scalar** fused into (or enqueued behind)
        the decode program — the admission screen's non-blocking readback.
        Registry codecs compute it in their one decode program; a custom codec
        without ``decode_with_norm`` pays one extra enqueued jit, still with
        zero host syncs.  The decode is timed by the ``channel.recv_upload``
        span into ``upload_deserialize_s`` (the transfer and the enqueue).
        """
        c = self._resolve_upload_codec(envelope)
        with self._span("channel.recv_upload", envelope.metadata) as span:
            if with_norm:
                fused = getattr(c, "decode_with_norm", None)
                if fused is not None:
                    row, norm = fused(envelope.payload, envelope.num_elements)
                else:
                    row = c.decode(envelope.payload, envelope.num_elements)
                    norm = _row_norm(row)
            else:
                row = c.decode(envelope.payload, envelope.num_elements)
        self._account_upload_decode(span.seconds)
        return (row, norm) if with_norm else row

    def _account_upload_decode(self, dt: float) -> None:
        with self._stats_lock:
            self._c["upload_deserialize_s"].add(dt)

    def recv_upload_quantized(
        self, envelope: UploadEnvelope, out_params: int
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Decode an int8 upload straight into arena-resident quantized form.

        Returns ``(q int8 (out_params,), scales f32 (out_params//group,),
        norm)`` — the quantized-resident arena's ingest half: one
        ``device_put`` plus one jitted split/slice/norm program, with **no**
        intermediate f32 ``(P,)`` materialization and the admission norm as
        a device scalar.  Only valid for envelopes whose codec decodes to
        the int8 wire format; accounted as upload deserialization work like
        :meth:`recv_upload`.
        """
        c = self._resolve_upload_codec(envelope)
        decode_q = getattr(c, "decode_quantized", None)
        if decode_q is None:
            raise ValueError(
                f"codec {envelope.codec!r} cannot land quantized rows; "
                "use recv_upload for f32 decode"
            )
        with self._span("channel.recv_upload", envelope.metadata) as span:
            q, scales, norm = decode_q(
                envelope.payload, envelope.num_elements, out_params
            )
        self._account_upload_decode(span.seconds)
        return q, scales, norm

    def recv_upload_sparse(
        self, envelope: UploadEnvelope
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Decode a topk upload in sparse form — densification never happens.

        Returns ``(indices int32 (k,), values f32 (k,), norm)`` — the
        direct sparse arena's ingest half: one ``device_put`` plus one
        cached split program, with the admission norm fused as a device
        scalar (top-k indices are unique, so the sparse L2 equals the
        dense row's norm — the same single-host-readback contract as
        :meth:`recv_upload` with ``with_norm=True``).  Only valid for
        envelopes whose codec declares ``decode_sparse``; accounted as
        upload deserialization work like :meth:`recv_upload`.
        """
        c = self._resolve_upload_codec(envelope)
        decode_s = getattr(c, "decode_sparse", None)
        if decode_s is None:
            raise ValueError(
                f"codec {envelope.codec!r} cannot land sparse rows; "
                "use recv_upload for dense decode"
            )
        with self._span("channel.recv_upload", envelope.metadata) as span:
            idx, val, norm = decode_s(envelope.payload, envelope.num_elements)
        self._account_upload_decode(span.seconds)
        return idx, val, norm
