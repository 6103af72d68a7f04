"""Masked scatter-accumulate: weighted sparse rows → one dense row.

The sparse-arena aggregation kernel.  Each valid arena row is a
``(k,)`` stream of ``(index, value)`` pairs; the reduce scatters every
stream's weighted values straight into a ``(P,)`` f32 accumulator —
the dense ``(N, P)`` stack of ``masked_weighted_average`` is never
built, so the reduce moves ``~N·k + P`` floats instead of ``N·P``.

Lowering: one ``jnp.zeros(P).at[idx].add(contrib)`` under jit.  XLA
compiles scatter-add to the TPU's native combining scatter (and to a
serial loop on CPU — the interpret-mode fallback is the same program
under the CPU backend).  A hand-written Pallas scatter would need
per-element dynamic stores or an O(N·k·P) one-hot matmul; the XLA op
*is* the right kernel here, so this module is deliberately plain jnp.

The column-sharded variant buckets indices per shard inside
``shard_map``: every device receives the full (small) index/value
arena replicated, keeps only the coordinates that land in its column
slice, and scatters locally — zero collectives, same trick as the
column-sharded dense reduce (``aggregation.*_sharded``).

Invalid rows are masked with a ``where`` *before* the weight multiply,
so NaN/Inf garbage in never-written arena rows cannot poison the sum
(the same guard as ``aggregation.masked_weighted_average``).  Under
jit, out-of-range scatter indices are dropped by XLA's default clamp
semantics; masked rows additionally rewrite their indices to 0 with a
zero contribution.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

__all__ = ["scatter_accumulate", "scatter_accumulate_sharded"]


@partial(jax.jit, static_argnames=("out_width",))
def scatter_accumulate(
    indices: jax.Array,
    values: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    out_width: int,
) -> jax.Array:
    """Sum masked, weighted sparse rows into a dense ``(out_width,)`` row.

    ``indices``/``values`` are the ``(N, k)`` sparse arena; ``weights``
    is the ``(N,)`` *normalized* weight vector (zero at masked rows);
    ``mask`` is the ``(N,)`` validity mask.  Within one row the indices
    are unique (top-k output), across rows they collide freely — the
    scatter combines with ``add``, which is exactly the weighted sum.
    """
    contrib = jnp.where(mask[:, None] > 0, values, 0.0).astype(jnp.float32)
    contrib = contrib * weights.astype(jnp.float32)[:, None]
    idx = jnp.where(mask[:, None] > 0, indices, 0)
    return (
        jnp.zeros((out_width,), jnp.float32)
        .at[idx.reshape(-1)]
        .add(contrib.reshape(-1))
    )


def scatter_accumulate_sharded(mesh, axes, out_width: int):
    """Build a column-sharded scatter-accumulate over ``mesh``.

    The returned jitted fn has the :func:`scatter_accumulate` signature
    minus ``out_width``.  Inputs are replicated (the sparse arena is
    ``N·k``-small by construction); the output is a ``(out_width,)`` row
    sharded over ``axes``.  Each shard computes its linearized shard id
    from ``axis_index`` (row-major over ``axes``, matching the
    ``PartitionSpec`` linearization), rebases the global indices into
    its local column window, and scatters only the coordinates that fall
    inside it — no ``psum``, no all-gather.
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes_t]))
    if out_width % n_shards != 0:
        raise ValueError(
            f"out_width {out_width} not divisible by {n_shards} shards"
        )
    local_w = out_width // n_shards

    def _local(indices, values, weights, mask):
        sid = jnp.int32(0)
        for a in axes_t:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        local_idx = indices - sid * local_w
        ok = (local_idx >= 0) & (local_idx < local_w) & (mask[:, None] > 0)
        contrib = jnp.where(ok, values, 0.0).astype(jnp.float32)
        contrib = contrib * weights.astype(jnp.float32)[:, None]
        local_idx = jnp.where(ok, local_idx, 0)
        return (
            jnp.zeros((local_w,), jnp.float32)
            .at[local_idx.reshape(-1)]
            .add(contrib.reshape(-1))
        )

    return jax.jit(shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(), P(), P()),
        out_specs=P(axes_t),
        check_vma=False,
    ))
