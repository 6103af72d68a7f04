"""Pallas TPU kernel: fused weighted model aggregation.

The paper's hot operation (Fig. 4) restated for the TPU memory hierarchy:
instead of one OpenMP thread per model tensor, the packed ``(N, P)`` learner
stack is tiled along ``P`` into MXU/VPU-aligned VMEM blocks; each grid step
streams one ``(N, block_p)`` tile HBM→VMEM, reduces it against the
``(N,)`` weight vector held in VMEM, and writes the ``(block_p,)`` slice of
the aggregate.

Arithmetic intensity is ~1 FLOP per 2 bytes for f32 inputs (2·N·P FLOPs over
N·P·4 bytes), so the kernel is HBM-bandwidth-bound; the tiling's only job is
to keep the block resident and the lanes full (block_p a multiple of
8·128 = 1024 f32 lanes) inside the compiler's scoped VMEM.  Numerics are
checked in interpret mode against ``ref.fedavg_ref``, and the TPU lowering
by compiling for a described v5e (``tests/test_tpu_compile.py``); the jit
wrapper lives in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "fedavg_pallas",
    "masked_fedavg_pallas",
    "choose_block_p",
    "choose_block_p_dividing",
    "choose_block_p_for_shard",
    "DEFAULT_BLOCK_P",
]

# 8 sublanes x 128 lanes x 16 vregs worth of f32 per tile step
DEFAULT_BLOCK_P = 16384

# Mosaic lets a kernel use 16 MiB of scoped VMEM on v5e unless the call
# raises ``vmem_limit_bytes`` (the core has 128 MiB in all).  Tiles are sized
# to a budget inside that default, so no kernel passes the override; an
# (8, 262144) f32 tile (8 MiB, 16 MiB double-buffered) is already refused.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def choose_block_p(n_learners: int, dtype_bytes: int = 4,
                   budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest lane-aligned block_p whose working set fits VMEM.

    Working set per grid step ≈ 2·N·block_p·dtype_bytes (the pipeliner
    double-buffers the input tile) + N·block_p·4 (the kernel's masked f32
    copy of the tile) + 2·block_p·4 (double-buffered f32 out) + N·4
    (weights).  Solving for block_p and rounding down to a multiple of 1024
    (8 sublanes × 128 lanes) keeps the VPU lanes full while staying inside
    the compiler's scoped VMEM: N=8 → 120,832 elements (f32); N=200 → 4,096.
    """
    per_elem = 2 * n_learners * dtype_bytes + 4 * n_learners + 8
    raw = (budget - 4 * n_learners) // per_elem
    aligned = max(1024, (raw // 1024) * 1024)
    return int(min(aligned, 1 << 20))


def choose_block_p_dividing(p: int, n_learners: int, lane_multiple: int = 1024,
                            budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest lane-aligned *divisor* of ``p`` whose working set fits VMEM.

    The arena hot path must not pad: re-padding the whole ``(N, P)`` arena to
    a non-dividing block size would re-introduce exactly the O(N·P) copy the
    arena eliminates.  ``ArenaStore`` pads rows to a ``lane_multiple``
    boundary at allocation, so a lane-aligned divisor always exists; for a
    non-aligned ad-hoc P there may be none, in which case we return
    :func:`choose_block_p` and the caller pads (legacy behaviour).
    """
    cap = choose_block_p(n_learners, budget=budget)
    if p <= 0 or p % lane_multiple:
        return cap
    if p <= cap:
        return p  # single grid step
    k = p // lane_multiple
    best = 0
    for m in range(1, int(k**0.5) + 1):
        if k % m == 0:
            for cand in (m, k // m):
                if lane_multiple * cand <= cap and cand > best:
                    best = cand
    return lane_multiple * best if best else cap


def choose_block_p_for_shard(
    p: int, n_learners: int, n_shards: int, lane_multiple: int = 1024,
    budget: int = VMEM_BUDGET_BYTES,
) -> int:
    """Block size for one column shard of a mesh-sharded arena.

    Under ``shard_map`` the kernel sees the **local** ``(N, p / n_shards)``
    shard, so the block must divide the *shard* width, not the global row —
    a block sized for the global ``P`` would force every device to re-pad its
    shard, reintroducing the O(N·P) copy the arena exists to avoid.
    ``ArenaStore(mesh=...)`` pads rows to ``row_align * n_shards``, so the
    shard width is always lane-aligned and a dividing block exists; a
    non-dividing ad-hoc ``p`` falls back to :func:`choose_block_p` (the
    caller pads, legacy behaviour).
    """
    if n_shards <= 1:
        return choose_block_p_dividing(p, n_learners, lane_multiple, budget)
    if p % n_shards:
        return choose_block_p(n_learners, budget=budget)
    return choose_block_p_dividing(p // n_shards, n_learners, lane_multiple,
                                   budget)


def _fedavg_kernel(w_ref, stack_ref, out_ref):
    """One grid step: out[bp] = sum_n w[n] * stack[n, bp].

    w_ref: (N, 1) f32 in VMEM; stack_ref: (N, BP); out_ref: (1, BP).
    The reduce is expressed as a (1,N)x(N,BP) matmul so the MXU can take it
    when N is large; for small N the VPU handles it as a broadcast-multiply.
    """
    w = w_ref[:, 0]  # (N,)
    block = stack_ref[...].astype(jnp.float32)  # (N, BP)
    acc = jax.lax.dot_general(
        w[None, :], block,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # not one bf16 pass
        preferred_element_type=jnp.float32,
    )  # (1, BP)
    out_ref[...] = acc


def fedavg_pallas(
    stack: jax.Array,
    weights: jax.Array,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool = False,
) -> jax.Array:
    """(N, P) x (N,) -> (P,) weighted mean.  P must be a multiple of block_p
    (ops.py pads).  Weights are normalized inside (f32)."""
    n, p = stack.shape
    assert p % block_p == 0, (p, block_p)
    w = weights.astype(jnp.float32)
    w = (w / jnp.sum(w))[:, None]  # (N, 1)

    grid = (p // block_p,)
    out = pl.pallas_call(
        _fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # weights: same block each step
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(w, stack)
    return out[0]


# ---------------------------------------------------------------------------
# Masked variant: aggregation straight off the device-resident arena
# ---------------------------------------------------------------------------


def _masked_fedavg_kernel(w_ref, mask_ref, arena_ref, out_ref):
    """One grid step: out[bp] = sum_n w[n] * mask[n] * arena[n, bp].

    ``w`` arrives pre-masked and pre-normalized, so invalid rows already
    carry zero weight; the explicit ``where`` on the data additionally zeroes
    the row *values* so a dead row containing non-finite garbage (a learner
    that never reported, an invalidated upload) cannot produce 0 * NaN = NaN
    in the aggregate.  The reduce stays a (1,N)x(N,BP) matmul for the MXU.
    """
    w = w_ref[:, 0]  # (N,) masked+normalized
    m = mask_ref[:, 0]  # (N,) 1.0/0.0 validity
    block = arena_ref[...].astype(jnp.float32)  # (N, BP)
    block = jnp.where(m[:, None] > 0, block, 0.0)
    acc = jax.lax.dot_general(
        w[None, :], block,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # not one bf16 pass
        preferred_element_type=jnp.float32,
    )  # (1, BP)
    out_ref[...] = acc


def masked_fedavg_pallas(
    arena: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool = False,
) -> jax.Array:
    """(N_max, P) x (N_max,) x (N_max,) -> (P,) masked weighted mean.

    The arena-store hot path: the full (possibly part-empty) arena streams
    through VMEM exactly like :func:`fedavg_pallas`, with validity folded into
    the weight vector.  P must be a multiple of ``block_p`` — use
    :func:`choose_block_p_dividing` (as ``ops.masked_fedavg`` does) to pick a
    dividing block for an arena-aligned P without re-padding; ops.py pads for
    ad-hoc shapes.  If every mask entry is zero the weights fall back to
    uniform-over-valid = all-zero, returning a zero buffer (the controller
    raises before that happens).
    """
    from repro.core.aggregation import masked_normalize

    n, p = arena.shape
    assert p % block_p == 0, (p, block_p)
    m = mask.astype(jnp.float32)
    w = masked_normalize(weights, m)

    grid = (p // block_p,)
    out = pl.pallas_call(
        _masked_fedavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(w[:, None], m[:, None], arena)
    return out[0]
