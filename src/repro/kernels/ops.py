"""jit'd public wrappers around the Pallas kernels (padding + dispatch).

:func:`interpret_mode` decides, when a wrapper is traced, whether the kernels
compile to Mosaic (a TPU backend) or run in the Pallas interpreter (any other
backend: the CPU that the tests use).  Importing this module initializes no
backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fedavg as _fedavg
from repro.kernels import fused_agg as _fused
from repro.kernels import quantize as _quant
from repro.kernels import robust as _robust

__all__ = [
    "interpret_mode", "fedavg", "masked_fedavg", "masked_fedavg_sharded",
    "masked_fedavg_q8", "masked_fedavg_q8_sharded",
    "masked_trimmed_mean", "masked_trimmed_mean_sharded",
    "quantize", "dequantize", "QuantCodec",
]


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU.

    Read at trace time, so the first traced kernel call (not the import)
    initializes the backend.  Code that must run on the chip checks it is
    False; it never silently turns a TPU run into an interpreted one.
    """
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, multiple: int, axis: int = -1) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, size
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("block_p",))
def fedavg(stack: jax.Array, weights: jax.Array,
           block_p: int | None = None) -> jax.Array:
    """Kernel-backed FedAvg over a packed (N, P) stack.

    block_p defaults to the largest VMEM-fitting tile for this N
    (``fedavg.choose_block_p``)."""
    if block_p is None:
        block_p = _fedavg.choose_block_p(stack.shape[0])
    padded, p = _pad_to(stack, block_p, axis=1)
    out = _fedavg.fedavg_pallas(padded, weights, block_p=block_p, interpret=interpret_mode())
    return out[:p]


@functools.partial(jax.jit, static_argnames=("block_p",))
def masked_fedavg(arena: jax.Array, weights: jax.Array, mask: jax.Array,
                  block_p: int | None = None) -> jax.Array:
    """Kernel-backed masked FedAvg over a device-resident arena.

    The aggregation step of the arena store (``core/store.ArenaStore``):
    invalid rows are skipped via the mask, so the same compiled kernel serves
    every round regardless of how many learners reported.  The default block
    size *divides* the arena's lane-aligned row width, so the hot path runs
    with zero re-padding (``_pad_to`` is a no-op); only ad-hoc non-aligned
    shapes pay the pad copy."""
    if block_p is None:
        block_p = _fedavg.choose_block_p_dividing(arena.shape[1], arena.shape[0])
    padded, p = _pad_to(arena, block_p, axis=1)
    out = _fedavg.masked_fedavg_pallas(
        padded, weights, mask, block_p=block_p, interpret=interpret_mode()
    )
    return out[:p]


@functools.partial(jax.jit, static_argnames=("group", "block_p"))
def masked_fedavg_q8(arena_q: jax.Array, scales: jax.Array,
                     weights: jax.Array, mask: jax.Array,
                     group: int = _quant.DEFAULT_GROUP,
                     block_p: int | None = None) -> jax.Array:
    """Kernel-backed fused dequant-into-aggregate over a quantized arena.

    The int8-arena analogue of :func:`masked_fedavg`: one fused pass reads
    the resident ``(N, P)`` int8 rows plus their ``(N, P//group)`` f32
    scales and emits the masked weighted mean — no f32 ``(N, P)`` stack is
    ever materialized.  The default block divides the arena's lane-aligned
    row width (which ``ArenaStore`` keeps a multiple of lcm(1024, group)),
    so the hot path runs with zero re-padding; ad-hoc non-aligned shapes pay
    a pad copy on both the values and the scales (padding with scale 0.0 —
    the padded tail dequantizes to exact zeros and the extra columns are
    sliced off)."""
    if block_p is None:
        block_p = _fused.choose_block_p_q8_dividing(
            arena_q.shape[1], arena_q.shape[0], group
        )
    padded, p = _pad_to(arena_q, block_p, axis=1)
    spad, _ = _pad_to(scales, block_p // group, axis=1)
    out = _fused.masked_fedavg_q8_pallas(
        padded, spad, weights, mask, group=group, block_p=block_p,
        interpret=interpret_mode(),
    )
    return out[:p]


def masked_fedavg_q8_sharded(mesh, axes=None, group: int = _quant.DEFAULT_GROUP):
    """Fused dequant-into-aggregate over a mesh-sharded quantized arena.

    Returns a jitted ``(arena_q (N,P) int8, scales (N,P//group), weights,
    mask) -> (P,)`` running :func:`masked_fedavg_q8` per column shard under
    ``shard_map``.  Values and scales carry the same ``P(None, axes)``
    column sharding (``ArenaStore(arena_dtype="int8", mesh=...)`` keeps the
    shard width a whole number of groups), weight normalization reduces only
    over the replicated ``(N,)`` vectors, and the compiled program contains
    zero collectives, exactly like :func:`masked_fedavg_sharded`.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.aggregation import arena_axes

    ax = arena_axes(mesh, axes)
    n_shards = int(np.prod([mesh.shape[a] for a in ax], dtype=np.int64))

    def _local(arena_q, scales, weights, mask):
        block_p = _fused.choose_block_p_q8_for_shard(
            arena_q.shape[1] * n_shards, arena_q.shape[0], n_shards, group
        )
        return masked_fedavg_q8(arena_q, scales, weights, mask,
                                group=group, block_p=block_p)

    sm = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(None, ax), P(None, ax), P(), P()),
        out_specs=P(ax),
        check_vma=False,
    )
    return jax.jit(sm)


@functools.partial(jax.jit, static_argnames=("trim_k", "block_p"))
def masked_trimmed_mean(arena: jax.Array, weights: jax.Array, mask: jax.Array,
                        trim_k: int = 1, block_p: int | None = None) -> jax.Array:
    """Kernel-backed masked trimmed mean over a device-resident arena.

    The robust-rule hot path (``kernels/robust.py`` rank-select kernel):
    signature-compatible with ``core/aggregation.masked_trimmed_mean`` —
    ``weights`` is accepted and ignored, order statistics being deliberately
    weight-blind.  The default block divides the arena's lane-aligned width
    under the robust kernel's tighter VMEM budget, so the hot path runs with
    zero re-padding; ad-hoc shapes pay the pad copy."""
    del weights  # order statistics are weight-blind by design
    if block_p is None:
        block_p = _fedavg.choose_block_p_dividing(
            arena.shape[1], arena.shape[0],
            budget=_robust.ROBUST_VMEM_BUDGET_BYTES,
        )
    padded, p = _pad_to(arena, block_p, axis=1)
    out = _robust.masked_trimmed_mean_pallas(
        padded, mask, trim_k=trim_k, block_p=block_p, interpret=interpret_mode()
    )
    return out[:p]


def masked_trimmed_mean_sharded(mesh, axes=None, trim_k: int = 1):
    """Kernel-backed masked trimmed mean over a mesh-sharded arena.

    Returns a jitted ``(arena (N_max,P), weights, mask) -> (P,)`` running
    :func:`masked_trimmed_mean` per column shard under ``shard_map`` — the
    rule is coordinate-wise, so each device rank-selects within its own
    ``(N_max, P/n_shards)`` slice and the compiled program contains zero
    collectives, exactly like :func:`masked_fedavg_sharded`.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.aggregation import arena_axes

    ax = arena_axes(mesh, axes)
    n_shards = int(np.prod([mesh.shape[a] for a in ax], dtype=np.int64))

    def _local(arena, weights, mask):
        block_p = _fedavg.choose_block_p_for_shard(
            arena.shape[1] * n_shards, arena.shape[0], n_shards,
            budget=_robust.ROBUST_VMEM_BUDGET_BYTES,
        )
        return masked_trimmed_mean(arena, weights, mask, trim_k=trim_k,
                                   block_p=block_p)

    sm = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(None, ax), P(), P()),
        out_specs=P(ax),
        check_vma=False,
    )
    return jax.jit(sm)


@functools.partial(jax.jit, static_argnames=("group", "block_rows"))
def quantize(x: jax.Array, group: int = _quant.DEFAULT_GROUP,
             block_rows: int = _quant.DEFAULT_BLOCK_ROWS):
    """Returns (q, scales); the caller keeps x.shape[0] for dequantize."""
    padded, _ = _pad_to(x, group * block_rows)
    return _quant.quantize_pallas(padded, group, block_rows, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("group", "block_rows", "orig_size"))
def dequantize(q: jax.Array, scales: jax.Array, orig_size: int,
               group: int = _quant.DEFAULT_GROUP,
               block_rows: int = _quant.DEFAULT_BLOCK_ROWS) -> jax.Array:
    """Inverse of :func:`quantize`, sliced back to ``orig_size`` elements."""
    x = _quant.dequantize_pallas(q, scales, group, block_rows, interpret=interpret_mode())
    return x[:orig_size]


def masked_fedavg_sharded(mesh, axes=None):
    """Kernel-backed masked FedAvg over a mesh-sharded arena.

    Returns a jitted ``(arena (N_max,P), weights, mask) -> (P,)`` that runs
    :func:`masked_fedavg` **per column shard** under ``shard_map``: each
    device's Pallas call sees only its local ``(N_max, P/n_shards)`` shard
    (so ``choose_block_p_dividing`` picks a block that divides the *shard*
    width — see ``kernels.fedavg.choose_block_p_for_shard``), the weight
    normalization reduces only over the replicated ``(N_max,)`` vectors, and
    the compiled program contains zero collectives.  The output keeps the
    ``P(axes)`` column sharding of ``core/store.ArenaStore(mesh=...)``.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.aggregation import arena_axes

    ax = arena_axes(mesh, axes)
    n_shards = int(np.prod([mesh.shape[a] for a in ax], dtype=np.int64))

    def _local(arena, weights, mask):
        # arena here is the device-local (N, P/n_shards) shard; size the
        # block from the global width so the choice is explicit and testable.
        block_p = _fedavg.choose_block_p_for_shard(
            arena.shape[1] * n_shards, arena.shape[0], n_shards
        )
        return masked_fedavg(arena, weights, mask, block_p=block_p)

    sm = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(None, ax), P(), P()),
        out_specs=P(ax),
        check_vma=False,
    )
    return jax.jit(sm)


_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2, "float64": 3}
_DTYPE_NAMES = {v: k for k, v in _DTYPE_CODES.items()}


class QuantCodec:
    """Transport codec for ``core/transport.Channel``: pytree -> int8 + scales.

    Encodes every float leaf; integer leaves pass through.  Stateless: shape
    and dtype ride along in the encoded leaf, so any receiver can decode
    (lossy to the int8 step, ~0.4% relative error — measured in
    EXPERIMENTS.md and acceptable for FL model shipping).
    """

    @staticmethod
    def encode(params):
        """Quantize every float leaf to int8 + scales (ints pass through)."""
        def enc(leaf):
            leaf = jnp.asarray(leaf)
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            flat = leaf.astype(jnp.float32).reshape(-1)
            q, s = quantize(flat)
            return {
                "__quant__": jnp.asarray(
                    [flat.shape[0], _DTYPE_CODES[str(leaf.dtype)]] + list(leaf.shape),
                    jnp.int64,
                ),
                "q": q,
                "s": s,
            }

        return jax.tree_util.tree_map(enc, params)

    @staticmethod
    def decode(encoded):
        """Reconstruct the pytree encoded by :meth:`encode` (lossy to int8)."""
        def is_q(x):
            return isinstance(x, dict) and "__quant__" in x

        def dec(leaf):
            if not is_q(leaf):
                return leaf
            meta = [int(v) for v in leaf["__quant__"]]
            size, dtc, shape = meta[0], meta[1], tuple(meta[2:])
            x = dequantize(leaf["q"], leaf["s"], size)
            return x.reshape(shape).astype(_DTYPE_NAMES[dtc])

        return jax.tree_util.tree_map(dec, encoded, is_leaf=is_q)
