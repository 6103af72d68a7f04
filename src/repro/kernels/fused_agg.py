"""Pallas TPU kernel: fused dequant-into-aggregate over a quantized arena.

The quantized-resident arena (``core/store.ArenaStore(arena_dtype="int8")``)
keeps each learner row as int8 groups plus per-group f32 scales — 4x fewer
resident HBM bytes than the f32 arena.  The naive way to aggregate it is
dequantize-then-reduce: materialize the f32 ``(N, P)`` stack (write 4·N·P
bytes, read them back) and run ``masked_fedavg`` — three passes over the
dominant traffic.  This kernel fuses the two: each grid step streams one
``(N, block_p)`` int8 tile plus its ``(N, block_p/group)`` scale tile
HBM→VMEM, dequantizes in registers (``q.astype(f32) * scale`` broadcast per
group), masks dead rows and reduces against the normalized weight vector —
**one pass** over the quantized bytes, ~N·P + 4·N·P/group + 4·P bytes moved
instead of ~9·N·P.

Tiling follows ``kernels/fedavg.py``: ``block_p`` is VMEM-budgeted, lane-
aligned, a multiple of the quant group (so every tile holds whole groups)
and — on the arena hot path — an exact divisor of the padded row width, so
nothing is ever re-padded.  Numerics are checked in interpret mode against
the f64 ``ref.masked_fedavg_q8_ref`` oracle, and the TPU lowering by
compiling for a described v5e (``tests/test_tpu_compile.py``); the jit
wrapper and the column-sharded ``shard_map`` variant (zero collectives)
live in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg import VMEM_BUDGET_BYTES
from repro.kernels.quantize import DEFAULT_GROUP

__all__ = [
    "masked_fedavg_q8_pallas",
    "choose_block_p_q8",
    "choose_block_p_q8_dividing",
    "choose_block_p_q8_for_shard",
]

# block_p must be both VPU-lane-aligned (1024 = 8 sublanes x 128 lanes of
# f32) and a whole number of quant groups; group is a multiple of 128 by
# the quantize kernel's contract, so aligning to lcm keeps both.
_LANE_MULTIPLE = 1024


def _lcm(a: int, b: int) -> int:
    import math

    return a * b // math.gcd(a, b)


def choose_block_p_q8(
    n_learners: int, group: int = DEFAULT_GROUP,
    budget: int = VMEM_BUDGET_BYTES,
) -> int:
    """Largest aligned block_p whose fused working set fits VMEM.

    Working set per grid step ≈ 2·N₃₂·block_p (double-buffered int8 tile,
    its rows padded to the 32-row int8 tiling) + 2·N₈·block_p·4 (the
    in-kernel f32 dequantized block and its per-group view, rows padded to
    8) + 2·N·(block_p/group)·4 (scale tiles) + 2·block_p·4
    (double-buffered out).  Solving for block_p and rounding down to a
    multiple of lcm(1024, group) keeps the lanes full and every tile a
    whole number of groups.
    """
    n32 = -(-n_learners // 32) * 32
    n8 = -(-n_learners // 8) * 8
    per_elem = 2 * n32 + 8 * n8 + (8 * n_learners) // group + 8
    raw = (budget - 8 * n_learners) // per_elem
    align = _lcm(_LANE_MULTIPLE, group)
    aligned = max(align, (raw // align) * align)
    return int(min(aligned, 1 << 20))


def choose_block_p_q8_dividing(
    p: int, n_learners: int, group: int = DEFAULT_GROUP,
    budget: int = VMEM_BUDGET_BYTES,
) -> int:
    """Largest aligned *divisor* of ``p`` whose working set fits VMEM.

    The quantized-arena analogue of ``fedavg.choose_block_p_dividing``: the
    hot path must not pad (re-padding the resident ``(N, P)`` int8 buffer
    would reintroduce the O(N·P) copy the arena eliminates), and every tile
    must hold whole quant groups so the scale tile stays rectangular.
    ``ArenaStore`` pads rows to ``row_align`` (a multiple of
    lcm(1024, group) for the defaults), so an aligned divisor always
    exists; a non-aligned ad-hoc ``p`` falls back to
    :func:`choose_block_p_q8` and the caller pads (legacy behaviour).
    """
    cap = choose_block_p_q8(n_learners, group, budget)
    align = _lcm(_LANE_MULTIPLE, group)
    if p <= 0 or p % align:
        return cap
    if p <= cap:
        return p  # single grid step
    k = p // align
    best = 0
    for m in range(1, int(k**0.5) + 1):
        if k % m == 0:
            for cand in (m, k // m):
                if align * cand <= cap and cand > best:
                    best = cand
    return align * best if best else cap


def choose_block_p_q8_for_shard(
    p: int, n_learners: int, n_shards: int, group: int = DEFAULT_GROUP,
    budget: int = VMEM_BUDGET_BYTES,
) -> int:
    """Block size for one column shard of a mesh-sharded quantized arena.

    Under ``shard_map`` the kernel sees the **local** ``(N, p / n_shards)``
    int8 shard (and the matching scale shard), so the block must divide the
    shard width — exactly the contract of
    ``fedavg.choose_block_p_for_shard``, restated for the group-aligned
    quantized layout.
    """
    if n_shards <= 1:
        return choose_block_p_q8_dividing(p, n_learners, group, budget)
    if p % n_shards:
        return choose_block_p_q8(n_learners, group, budget)
    return choose_block_p_q8_dividing(p // n_shards, n_learners, group, budget)


def _masked_fedavg_q8_kernel(w_ref, mask_ref, q_ref, s_ref, out_ref, *,
                             group: int):
    """One grid step: out[bp] = sum_n w[n]·mask[n]·q[n,bp]·s[n,bp/group].

    ``w`` arrives pre-masked and pre-normalized; the explicit ``where``
    additionally zeroes dead-row *values* so garbage scales (e.g. a NaN
    scale from a never-finalized row) cannot produce 0·NaN = NaN in the
    aggregate.  Dequantization is a per-group broadcast multiply in
    registers — the f32 block never round-trips through HBM — and the
    reduce stays a (1,N)x(N,BP) matmul for the MXU.
    """
    w = w_ref[:, 0]  # (N,) masked+normalized
    m = mask_ref[:, 0]  # (N,) 1.0/0.0 validity
    q = q_ref[...].astype(jnp.float32)  # (N, BP)
    s = s_ref[...]  # (N, BP/group) f32
    n, bp = q.shape
    block = (q.reshape(n, bp // group, group) * s[:, :, None]).reshape(n, bp)
    block = jnp.where(m[:, None] > 0, block, 0.0)
    acc = jax.lax.dot_general(
        w[None, :], block,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # not one bf16 pass
        preferred_element_type=jnp.float32,
    )  # (1, BP)
    out_ref[...] = acc


def masked_fedavg_q8_pallas(
    q: jax.Array,
    scales: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    *,
    group: int = DEFAULT_GROUP,
    block_p: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """(N,P) int8 x (N,P/group) f32 x (N,) x (N,) -> (P,) masked weighted mean.

    The quantized-arena hot path: one fused pass that dequantizes and
    reduces tile by tile.  ``P`` must be a multiple of ``block_p`` and
    ``block_p`` a multiple of ``group`` — use
    :func:`choose_block_p_q8_dividing` (as ``ops.masked_fedavg_q8`` does)
    for an arena-aligned P; ops.py pads ad-hoc shapes.  All-zero masks fall
    back to the zero buffer exactly like ``masked_fedavg_pallas``.
    """
    from repro.core.aggregation import masked_normalize

    n, p = q.shape
    if block_p is None:
        block_p = choose_block_p_q8_dividing(p, n, group)
    if p % block_p or block_p % group:
        raise ValueError(
            f"masked_fedavg_q8_pallas needs P={p} divisible by "
            f"block_p={block_p} and block_p divisible by group={group}"
        )
    if scales.shape != (n, p // group):
        raise ValueError(
            f"scales shape {scales.shape} does not match {n} rows of "
            f"{p}//{group}={p // group} groups"
        )
    m = mask.astype(jnp.float32)
    w = masked_normalize(weights, m)

    nblk, sblock = p // block_p, block_p // group
    # A (N, block_p/group) window of the (N, P/group) scales breaks the TPU
    # (8, 128) tiling rule unless block_p/group is a multiple of 128.  Laid
    # out as (P/block_p, N, block_p/group), each grid step's scale tile is a
    # whole trailing (N, block_p/group) slab, legal for every block_p.  The
    # relayout copies the scales once per call (4/group of the int8 bytes).
    scales_t = scales.reshape(n, nblk, sblock).transpose(1, 0, 2)
    out = pl.pallas_call(
        functools.partial(_masked_fedavg_q8_kernel, group=group),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
            pl.BlockSpec((None, n, sblock), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(w[:, None], m[:, None], q, scales_t)
    return out[0]
