"""Pallas TPU kernel: blocked masked trimmed mean (rank select, no gather).

The byzantine-robust hot path restated for the TPU memory hierarchy: like
``kernels/fedavg.py`` the packed ``(N, P)`` arena is tiled along ``P`` into
VMEM blocks, but the per-column reduction is an order statistic instead of a
dot product.  A full column sort would serialize badly on the VPU, so the
kernel *selects* instead of sorting: for each row ``i`` it computes the
row's per-column rank with one broadcast comparison against the whole block
(ties broken by row index, so ranks are a permutation and the result is
exactly the sort-then-trim answer), then accumulates the row into the mean
iff its rank lands in the surviving band ``[trim_k, n_valid - trim_k)``.
That is O(N^2 · block_p) elementwise VPU work with O(N · block_p) VMEM — no
gather, no scratch permutation, and invalid arena rows are pushed to ``+inf``
so they always rank past the band.

Degenerate cohorts (``n_valid <= 2 * trim_k``) fall back to the untrimmed
masked mean of the valid rows, matching
``core/aggregation.masked_trimmed_mean`` (the pure-jnp production rule this
kernel is benchmarked against).  Numerics are checked in interpret mode
against ``ref.masked_trimmed_mean_ref``, and the TPU lowering by compiling
for a described v5e (``tests/test_tpu_compile.py``); the jit wrapper lives
in ``ops.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg import VMEM_BUDGET_BYTES

__all__ = ["masked_trimmed_mean_pallas", "ROBUST_VMEM_BUDGET_BYTES"]

# The rank-select loop keeps several (N, block_p) f32 temporaries live
# (masked values, iota, comparison masks) on top of the double-buffered input
# tile, so the robust kernel budgets a quarter of the fedavg kernel's VMEM.
ROBUST_VMEM_BUDGET_BYTES = VMEM_BUDGET_BYTES // 4


def _masked_trimmed_mean_kernel(mask_ref, arena_ref, out_ref, *, trim_k):
    """One grid step: out[bp] = trimmed mean over valid rows of arena[:, bp].

    mask_ref: (N, 1) f32 validity; arena_ref: (N, BP); out_ref: (1, BP).
    """
    m = mask_ref[...]  # (N, 1)
    block = arena_ref[...].astype(jnp.float32)  # (N, BP)
    n = block.shape[0]
    # Invalid rows float to +inf: they rank >= n_valid in every column, so
    # the band test below can never admit them (and their garbage — even
    # NaN — never touches the accumulator).
    x = jnp.where(m > 0, block, jnp.inf)
    n_valid = jnp.sum(m)  # f32 scalar
    row_ids = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)  # (N, BP)
    zeros = jnp.zeros((1, x.shape[1]), jnp.float32)

    def body(i, acc):
        s, c = acc
        # Row i is re-read from the refs: a dynamic slice of a value has no
        # TPU lowering, a dynamic sublane offset into a ref does.
        xi = arena_ref[pl.ds(i, 1), :].astype(jnp.float32)  # (1, BP)
        xi = jnp.where(mask_ref[pl.ds(i, 1), :] > 0, xi, jnp.inf)
        less = jnp.sum(jnp.where(x < xi, 1.0, 0.0), axis=0, keepdims=True)
        ties = jnp.sum(
            jnp.where((x == xi) & (row_ids < i), 1.0, 0.0),
            axis=0, keepdims=True,
        )
        rank = less + ties  # distinct per column: a permutation of 0..N-1
        inband = (rank >= trim_k) & (rank < n_valid - trim_k)
        s = s + jnp.where(inband, xi, 0.0)
        c = c + jnp.where(inband, 1.0, 0.0)
        return (s, c)

    s, c = jax.lax.fori_loop(0, n, body, (zeros, zeros))
    trimmed = s / jnp.maximum(c, 1.0)
    # Degenerate cohort: untrimmed masked mean of the valid rows (finite by
    # construction — invalid rows were zeroed, not inf'd, on this path).
    fb_rows = jnp.where(m > 0, block, 0.0)
    fallback = jnp.sum(fb_rows, axis=0, keepdims=True) / jnp.maximum(n_valid, 1.0)
    out_ref[...] = jnp.where(
        c > 0, trimmed, jnp.where(n_valid > 0, fallback, 0.0)
    )


def masked_trimmed_mean_pallas(
    arena: jax.Array,
    mask: jax.Array,
    *,
    trim_k: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """(N_max, P) x (N_max,) -> (P,) masked trimmed mean, f32 output.

    P must be a multiple of ``block_p`` (ops.py pads ad-hoc shapes; the
    arena's lane-aligned width admits a dividing block so the hot path never
    re-pads).  ``trim_k`` is static and validated at trace time against the
    arena capacity; a merely-small live cohort falls back at run time.
    """
    n, p = arena.shape
    assert p % block_p == 0, (p, block_p)
    if trim_k < 0 or 2 * trim_k >= n:
        raise ValueError(f"trim_k={trim_k} invalid for N={n}")
    m = mask.astype(jnp.float32)

    grid = (p // block_p,)
    out = pl.pallas_call(
        functools.partial(_masked_trimmed_mean_kernel, trim_k=trim_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(m[:, None], arena)
    return out[0]
