"""Parallelized model aggregation — the paper's core contribution, TPU-native.

MetisFL aggregates a federated model of ``k`` tensors from ``N`` learners with
one OpenMP thread per tensor (paper Fig. 4).  The TPU-native restatement packs
the model into one flat buffer (``core/packing.py``) and performs the whole
aggregation as a single fused weighted reduction over an ``(N, P)`` stack:

* elementwise over ``P`` → embarrassingly parallel across VPU lanes and, under
  ``pjit``/``shard_map``, across every chip of the mesh (each chip reduces its
  1/``mesh_size`` slice of all ``N`` buffers with **zero collectives**);
* the reduction over ``N`` is tiny (N ≤ a few hundred) and lives in registers.

Three execution paths, benchmarked against each other in
``benchmarks/bench_agg.py``:

1. :func:`fedavg` — fused XLA reduction (the production path);
2. ``kernels/fedavg.py`` — the Pallas TPU kernel (explicit VMEM tiling);
3. ``core/naive.py`` — the per-tensor Python-loop baseline (the paper's
   "no parallelization" / old-Python-controller comparison point).

Beyond FedAvg the module provides the robust rules a production controller
ships (coordinate median, trimmed mean) and staleness weighting for the
asynchronous protocol.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A TPU runs an f32 contraction as one bf16 pass unless asked otherwise; the
# community model must be the f32 weighted mean, so every reduce asks.
F32_EXACT = jax.lax.Precision.HIGHEST

__all__ = [
    "fedavg",
    "weighted_average",
    "masked_normalize",
    "masked_weighted_average",
    "masked_fedavg",
    "masked_fedavg_q8",
    "masked_fedavg_topk",
    "masked_staleness_average",
    "masked_staleness_q8",
    "masked_staleness_topk",
    "coordinate_median",
    "trimmed_mean",
    "masked_coordinate_median",
    "masked_trimmed_mean",
    "staleness_weights",
    "fedavg_sharded",
    "hierarchical_fedavg",
    "masked_fedavg_sharded",
    "masked_fedavg_q8_sharded",
    "masked_fedavg_topk_sharded",
    "masked_staleness_sharded",
    "masked_staleness_q8_sharded",
    "masked_staleness_topk_sharded",
    "masked_median_sharded",
    "masked_trimmed_mean_sharded",
    "arena_axes",
]


def _normalize(weights: jax.Array) -> jax.Array:
    weights = jnp.asarray(weights, jnp.float32)
    total = jnp.sum(weights)
    # Guard the empty/zero-weight federation: fall back to uniform.
    safe = jnp.where(total > 0, total, 1.0)
    n = weights.shape[0]
    return jnp.where(total > 0, weights / safe, jnp.full((n,), 1.0 / max(n, 1)))


@jax.jit
def weighted_average(stack: jax.Array, weights: jax.Array) -> jax.Array:
    """``(N, P) × (N,) -> (P,)`` normalized weighted mean.

    This single einsum is the entire FedAvg aggregation for an arbitrarily
    deep model: tensor boundaries were erased by packing, so XLA sees one
    perfectly regular reduction it can tile across all cores/chips.
    """
    w = _normalize(weights)
    return jnp.einsum("n,np->p", w, stack.astype(jnp.float32), precision=F32_EXACT)


# FedAvg is a weighted average with example counts as weights.
fedavg = weighted_average


def masked_normalize(weights: jax.Array, mask: jax.Array) -> jax.Array:
    """Normalize ``weights * mask``; uniform over valid rows if all zero."""
    w = jnp.asarray(weights, jnp.float32) * jnp.asarray(mask, jnp.float32)
    total = jnp.sum(w)
    n_valid = jnp.sum(jnp.asarray(mask, jnp.float32))
    uniform = jnp.asarray(mask, jnp.float32) / jnp.maximum(n_valid, 1.0)
    return jnp.where(total > 0, w / jnp.where(total > 0, total, 1.0), uniform)


@jax.jit
def masked_weighted_average(
    arena: jax.Array, weights: jax.Array, mask: jax.Array
) -> jax.Array:
    """``(N, P) × (N,) × (N,) -> (P,)`` weighted mean over valid rows only.

    The arena-store statement of FedAvg: ``arena`` is the persistent
    device-resident buffer (``core/store.ArenaStore``) whose rows may include
    stale or never-written learners; ``mask`` (1.0 valid / 0.0 invalid) folds
    row selection into the weight vector so the reduction stays one fused
    einsum — no gather, no re-stack, no host round-trip.  Invalid rows are
    zeroed before the reduce so even garbage (e.g. NaN) in a dead row cannot
    poison the aggregate.
    """
    m = jnp.asarray(mask, jnp.float32)
    w = masked_normalize(weights, m)
    rows = jnp.where(m[:, None] > 0, arena.astype(jnp.float32), 0.0)
    return jnp.einsum("n,np->p", w, rows, precision=F32_EXACT)


# Masked FedAvg is a masked weighted average with example counts as weights.
masked_fedavg = masked_weighted_average


@jax.jit
def masked_staleness_average(
    arena: jax.Array,
    num_examples: jax.Array,
    versions: jax.Array,
    current_version: jax.Array,
    mask: jax.Array,
    alpha: float = 0.5,
) -> jax.Array:
    """Asynchronous-protocol aggregation straight off the arena.

    Staleness is derived on device from the per-row ``versions`` vector the
    arena maintains (``s_i = current_version - v_i``), damped by the
    polynomial discount of :func:`staleness_weights`, masked, normalized and
    reduced — one fused kernel per community update instead of a host-side
    stack rebuild per arrival.
    """
    m = jnp.asarray(mask, jnp.float32)
    stal = jnp.maximum(jnp.float32(current_version) - versions, 0.0)
    w = staleness_weights(num_examples, stal, alpha)
    w = masked_normalize(w, m)
    rows = jnp.where(m[:, None] > 0, arena.astype(jnp.float32), 0.0)
    return jnp.einsum("n,np->p", w, rows, precision=F32_EXACT)


def _dequant_rows(q: jax.Array, scales: jax.Array, group: int) -> jax.Array:
    """Dequantize ``(N, P)`` int8 rows with ``(N, P//group)`` f32 scales."""
    n, p = q.shape
    return (
        q.astype(jnp.float32).reshape(n, p // group, group)
        * scales[:, :, None]
    ).reshape(n, p)


@functools.partial(jax.jit, static_argnames=("group",))
def masked_fedavg_q8(
    q: jax.Array,
    scales: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    group: int = 256,
) -> jax.Array:
    """Masked FedAvg straight off a quantized arena — one fused XLA program.

    ``(N, P)`` int8 × ``(N, P//group)`` f32 × ``(N,)`` × ``(N,)`` -> ``(P,)``:
    the int8-arena statement of :func:`masked_weighted_average`.  Dequantize
    (a per-group broadcast multiply), mask and reduce compile into a single
    program, so the f32 ``(N, P)`` stack exists only as a fusion-internal
    temporary XLA can tile away — never a second resident copy of the arena.
    The controller's default dispatch for ``arena_dtype="int8"``; the Pallas
    statement with explicit VMEM tiling is ``kernels/ops.masked_fedavg_q8``.
    """
    m = jnp.asarray(mask, jnp.float32)
    w = masked_normalize(weights, m)
    rows = jnp.where(m[:, None] > 0, _dequant_rows(q, scales, group), 0.0)
    return jnp.einsum("n,np->p", w, rows, precision=F32_EXACT)


@functools.partial(jax.jit, static_argnames=("group",))
def masked_staleness_q8(
    q: jax.Array,
    scales: jax.Array,
    num_examples: jax.Array,
    versions: jax.Array,
    current_version: jax.Array,
    mask: jax.Array,
    alpha: float = 0.5,
    group: int = 256,
) -> jax.Array:
    """Asynchronous-protocol aggregation straight off a quantized arena.

    The int8-arena statement of :func:`masked_staleness_average`: staleness
    discount on the tiny replicated vectors, fused dequantize-mask-reduce on
    the ``(N, P)`` int8 rows — numerically identical to dequantizing and
    calling the f32 path, without ever materializing the f32 stack.
    """
    m = jnp.asarray(mask, jnp.float32)
    stal = jnp.maximum(jnp.float32(current_version) - versions, 0.0)
    w = masked_normalize(staleness_weights(num_examples, stal, alpha), m)
    rows = jnp.where(m[:, None] > 0, _dequant_rows(q, scales, group), 0.0)
    return jnp.einsum("n,np->p", w, rows, precision=F32_EXACT)


@functools.partial(jax.jit, static_argnames=("out_width",))
def masked_fedavg_topk(
    indices: jax.Array,
    values: jax.Array,
    weights: jax.Array,
    mask: jax.Array,
    out_width: int,
) -> jax.Array:
    """Masked FedAvg straight off a sparse (top-k) arena — scatter, not stack.

    ``(N, k)`` int32 × ``(N, k)`` f32 × ``(N,)`` × ``(N,)`` -> ``(P,)``: the
    sparse-arena statement of :func:`masked_weighted_average`.  The weight
    normalization runs on the tiny replicated vectors; the reduce is one
    combining scatter-add (``kernels/sparse_agg.scatter_accumulate``) of
    every valid row's weighted ``(index, value)`` stream into the dense
    output — the ``(N, P)`` stack is never built, so the reduce moves
    ``~N·k + P`` floats instead of ``N·P``.  Rows hold *deltas* (the topk
    codec sparsifies updates, not parameters); the controller adds the
    aggregated delta onto the global buffer at commit.
    """
    from repro.kernels import sparse_agg

    m = jnp.asarray(mask, jnp.float32)
    w = masked_normalize(weights, m)
    return sparse_agg.scatter_accumulate(indices, values, w, m, out_width)


@functools.partial(jax.jit, static_argnames=("out_width",))
def masked_staleness_topk(
    indices: jax.Array,
    values: jax.Array,
    num_examples: jax.Array,
    versions: jax.Array,
    current_version: jax.Array,
    mask: jax.Array,
    out_width: int,
    alpha: float = 0.5,
) -> jax.Array:
    """Asynchronous-protocol aggregation straight off a sparse arena.

    The sparse statement of :func:`masked_staleness_average`: the staleness
    discount damps the replicated weight vector, then one masked
    scatter-accumulate folds every valid sparse row into the ``(P,)`` delta.
    """
    from repro.kernels import sparse_agg

    m = jnp.asarray(mask, jnp.float32)
    stal = jnp.maximum(jnp.float32(current_version) - versions, 0.0)
    w = masked_normalize(staleness_weights(num_examples, stal, alpha), m)
    return sparse_agg.scatter_accumulate(indices, values, w, m, out_width)


def _robust_out_dtype(stack: jax.Array) -> jnp.dtype:
    """The dtype a robust rule returns: the input's, if it is a float.

    Order statistics are computed in float32 for a stable sort/mean, but the
    result is cast back so a bf16 arena aggregates to a bf16 model instead of
    silently widening every round.  Integer stacks (e.g. quantized codecs
    aggregated pre-dequantize in tests) still come back float32 because their
    mean is not representable in the input dtype.
    """
    dt = jnp.asarray(stack).dtype
    return dt if jnp.issubdtype(dt, jnp.floating) else jnp.dtype(jnp.float32)


@jax.jit
def coordinate_median(stack: jax.Array) -> jax.Array:
    """Coordinate-wise median — a byzantine-robust aggregation rule."""
    out = jnp.median(stack.astype(jnp.float32), axis=0)
    return out.astype(_robust_out_dtype(stack))


@functools.partial(jax.jit, static_argnames=("trim_k",))
def trimmed_mean(stack: jax.Array, trim_k: int) -> jax.Array:
    """Coordinate-wise trimmed mean dropping the ``trim_k`` extremes per side."""
    n = stack.shape[0]
    if 2 * trim_k >= n:
        raise ValueError(f"trim_k={trim_k} too large for N={n}")
    s = jnp.sort(stack.astype(jnp.float32), axis=0)
    out = jnp.mean(s[trim_k : n - trim_k], axis=0)
    return out.astype(_robust_out_dtype(stack))


@jax.jit
def masked_coordinate_median(
    arena: jax.Array, weights: jax.Array, mask: jax.Array
) -> jax.Array:
    """``(N, P) × (N,) × (N,) -> (P,)`` coordinate median over valid rows.

    The arena-store statement of :func:`coordinate_median`: invalid rows are
    pushed to ``+inf`` and a single column-wise sort floats every valid value
    to the top ``n_valid`` positions, so the median is one dynamic gather of
    the two middle ranks — no re-stack, no host round-trip, and garbage
    (even NaN) in a dead row can never reach the reduce.  ``weights`` is
    accepted for signature parity with :func:`masked_weighted_average` but
    ignored: order statistics are deliberately weight-blind, which is exactly
    what makes them robust to a poisoned example count.
    """
    del weights  # order statistics are weight-blind by design
    m = jnp.asarray(mask, jnp.float32)
    rows = jnp.where(m[:, None] > 0, arena.astype(jnp.float32), jnp.inf)
    s = jnp.sort(rows, axis=0)
    n_valid = jnp.sum(m).astype(jnp.int32)
    lo = jnp.maximum((n_valid - 1) // 2, 0)
    hi = jnp.maximum(n_valid // 2, 0)
    med = (jnp.take(s, lo, axis=0) + jnp.take(s, hi, axis=0)) * 0.5
    out = jnp.where(n_valid > 0, med, 0.0)
    return out.astype(_robust_out_dtype(arena))


@functools.partial(jax.jit, static_argnames=("trim_k",))
def masked_trimmed_mean(
    arena: jax.Array, weights: jax.Array, mask: jax.Array, trim_k: int
) -> jax.Array:
    """``(N, P) × (N,) × (N,) -> (P,)`` trimmed mean over valid rows.

    Invalid rows sort to the bottom as ``+inf``; the surviving band is rows
    ``[trim_k, n_valid - trim_k)`` of the sorted arena, selected with a rank
    mask so the whole rule stays one fused sort + masked mean regardless of
    how many arena rows are live.  ``trim_k`` is static: an impossible trim
    against the arena capacity is a clear trace-time ``ValueError``, while
    a cohort that is merely *currently* too small (``n_valid <= 2*trim_k``)
    yields an empty band and falls back to the masked mean of the valid rows
    rather than producing inf/NaN.  ``weights`` is ignored (see
    :func:`masked_coordinate_median`).
    """
    del weights  # order statistics are weight-blind by design
    n = arena.shape[0]
    if 2 * trim_k >= n:
        raise ValueError(f"trim_k={trim_k} too large for N={n}")
    m = jnp.asarray(mask, jnp.float32)
    rows = jnp.where(m[:, None] > 0, arena.astype(jnp.float32), jnp.inf)
    s = jnp.sort(rows, axis=0)
    n_valid = jnp.sum(m).astype(jnp.int32)
    ranks = jnp.arange(n, dtype=jnp.int32)
    band = (ranks >= trim_k) & (ranks < n_valid - trim_k)
    count = jnp.sum(band.astype(jnp.float32))
    safe_rows = jnp.where(band[:, None], s, 0.0)
    trimmed = jnp.sum(safe_rows, axis=0) / jnp.maximum(count, 1.0)
    # Degenerate cohort (n_valid <= 2*trim_k): untrimmed masked mean instead.
    fallback_band = ranks < n_valid
    fb_rows = jnp.where(fallback_band[:, None], s, 0.0)
    fallback = jnp.sum(fb_rows, axis=0) / jnp.maximum(
        jnp.sum(fallback_band.astype(jnp.float32)), 1.0
    )
    out = jnp.where(count > 0, trimmed, jnp.where(n_valid > 0, fallback, 0.0))
    return out.astype(_robust_out_dtype(arena))


def staleness_weights(
    num_examples: jax.Array, staleness: jax.Array, alpha: float = 0.5
) -> jax.Array:
    """Asynchronous-protocol weights: FedAvg weights damped by staleness.

    ``w_i ∝ n_i * (1 + s_i)^(-alpha)`` — the polynomial staleness discount used
    by async FL controllers; ``s_i`` is how many global updates happened since
    learner *i* pulled the model it trained from.
    """
    n = jnp.asarray(num_examples, jnp.float32)
    s = jnp.asarray(staleness, jnp.float32)
    return n * (1.0 + s) ** (-alpha)


# ---------------------------------------------------------------------------
# Mesh-sharded aggregation
# ---------------------------------------------------------------------------


def fedavg_sharded(mesh: Mesh, stack: jax.Array, weights: jax.Array) -> jax.Array:
    """Paper-faithful aggregation on a device mesh.

    The ``(N, P)`` stack is sharded over *all* mesh axes along ``P`` (the
    flattened-parameter dimension) and replicated along ``N``.  Every chip
    reduces its own parameter slice — one worker per shard, the generalization
    of MetisFL's one-thread-per-tensor.  The compiled HLO contains **no
    collectives**; this is verified by ``tests/test_aggregation.py`` and the
    dry-run roofline.
    """
    axes = tuple(mesh.axis_names)
    in_spec = NamedSharding(mesh, P(None, axes))
    out_spec = NamedSharding(mesh, P(axes))
    fn = jax.jit(weighted_average, in_shardings=(in_spec, NamedSharding(mesh, P())),
                 out_shardings=out_spec)
    return fn(stack, weights)


def arena_axes(mesh: Mesh, axes=None) -> tuple[str, ...]:
    """Resolve the arena column-sharding axes for ``mesh``.

    The single source of truth for the default — the ``"data"`` axis if the
    mesh has one, else every axis — shared by ``models.sharding.arena_specs``
    (the store's buffer layout), the sharded reductions below, and
    ``kernels/ops.masked_fedavg_sharded``, so the arena's layout and the
    jitted reductions' shardings can never silently disagree.
    """
    if axes is None:
        return ("data",) if "data" in mesh.axis_names else tuple(mesh.axis_names)
    return (axes,) if isinstance(axes, str) else tuple(axes)




def masked_fedavg_sharded(mesh: Mesh, axes=None):
    """Masked FedAvg over a column-sharded arena — zero collectives.

    Returns a jitted ``(arena (N_max,P), weights (N_max,), mask (N_max,)) ->
    (P,)`` closed over the mesh: the arena arrives (and stays) sharded
    ``P(None, axes)``, the tiny metadata vectors are replicated, and the
    output keeps the ``P(axes)`` column sharding — every device reduces its
    own ``(N_max, P/n_shards)`` shard and nothing is gathered until the
    caller unpacks the model.  The per-shard math is exactly
    :func:`masked_weighted_average` (the weight normalization only reduces
    over the replicated ``(N_max,)`` vectors), so the result is numerically
    identical to the single-device arena path.
    """
    ax = arena_axes(mesh, axes)
    return jax.jit(
        masked_weighted_average,
        in_shardings=(
            NamedSharding(mesh, P(None, ax)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def masked_fedavg_q8_sharded(mesh: Mesh, axes=None, group: int = 256):
    """Masked FedAvg over a column-sharded *quantized* arena — zero collectives.

    Returns a jitted ``(q (N,P) int8, scales (N,P//group), weights, mask) ->
    (P,)``: values and scales carry the same ``P(None, axes)`` column
    sharding (``ArenaStore(arena_dtype="int8", mesh=...)`` keeps every shard
    a whole number of groups), so each device fuses dequantize-mask-reduce
    over its own slice and only the replicated ``(N,)`` vectors are reduced
    globally — the same contract as :func:`masked_fedavg_sharded`.
    """
    ax = arena_axes(mesh, axes)

    def _agg(q, scales, weights, mask):
        return masked_fedavg_q8(q, scales, weights, mask, group)

    repl = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, ax))
    return jax.jit(
        _agg,
        in_shardings=(col, col, repl, repl),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def masked_staleness_q8_sharded(mesh: Mesh, axes=None, alpha: float = 0.5,
                                group: int = 256):
    """Sharded statement of :func:`masked_staleness_q8` for async int8 arenas.

    Same sharding contract as :func:`masked_fedavg_q8_sharded`; the staleness
    discount runs on the replicated ``(N,)`` vectors so the per-shard fused
    dequantize-reduce stays collective-free.
    """
    ax = arena_axes(mesh, axes)

    def _agg(q, scales, num_examples, versions, current_version, mask):
        return masked_staleness_q8(
            q, scales, num_examples, versions, current_version, mask,
            alpha, group,
        )

    repl = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, ax))
    return jax.jit(
        _agg,
        in_shardings=(col, col, repl, repl, repl, repl),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def masked_fedavg_topk_sharded(mesh: Mesh, axes=None, out_width: int = 0):
    """Masked sparse FedAvg over a column-sharded output — zero collectives.

    Returns a jitted ``(indices (N,k) int32, values (N,k) f32, weights (N,),
    mask (N,)) -> (P,)`` closed over the mesh and the (static) output width.
    Unlike the dense sharded reductions, the *inputs* stay replicated — the
    sparse arena is ``N·k``-small by construction — and only the ``(P,)``
    output is column-sharded: inside ``shard_map`` each device buckets the
    global indices into its own column window and scatters locally
    (``kernels/sparse_agg.scatter_accumulate_sharded``), so the compiled
    HLO stays collective-free.
    """
    from repro.kernels import sparse_agg

    ax = arena_axes(mesh, axes)
    scatter = sparse_agg.scatter_accumulate_sharded(mesh, ax, int(out_width))

    def _agg(indices, values, weights, mask):
        m = jnp.asarray(mask, jnp.float32)
        w = masked_normalize(weights, m)
        return scatter(indices, values, w, m)

    return jax.jit(_agg)


def masked_staleness_topk_sharded(mesh: Mesh, axes=None, out_width: int = 0,
                                  alpha: float = 0.5):
    """Sharded statement of :func:`masked_staleness_topk` for async sparse
    arenas — same replicated-input / sharded-output contract as
    :func:`masked_fedavg_topk_sharded`, with the staleness discount on the
    replicated ``(N,)`` vectors.
    """
    from repro.kernels import sparse_agg

    ax = arena_axes(mesh, axes)
    scatter = sparse_agg.scatter_accumulate_sharded(mesh, ax, int(out_width))

    def _agg(indices, values, num_examples, versions, current_version, mask):
        m = jnp.asarray(mask, jnp.float32)
        stal = jnp.maximum(jnp.float32(current_version) - versions, 0.0)
        w = masked_normalize(staleness_weights(num_examples, stal, alpha), m)
        return scatter(indices, values, w, m)

    return jax.jit(_agg)


def masked_staleness_sharded(mesh: Mesh, axes=None, alpha: float = 0.5):
    """Sharded statement of :func:`masked_staleness_average` for async FL.

    Returns a jitted ``(arena, num_examples, versions, current_version,
    mask) -> (P,)`` with the same column sharding contract as
    :func:`masked_fedavg_sharded`; the staleness discount is computed on the
    replicated ``(N_max,)`` vectors so the sharded reduction stays
    collective-free.
    """
    ax = arena_axes(mesh, axes)

    def _agg(arena, num_examples, versions, current_version, mask):
        return masked_staleness_average(
            arena, num_examples, versions, current_version, mask, alpha
        )

    repl = NamedSharding(mesh, P())
    return jax.jit(
        _agg,
        in_shardings=(NamedSharding(mesh, P(None, ax)), repl, repl, repl, repl),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def masked_median_sharded(mesh: Mesh, axes=None):
    """Masked coordinate median over a column-sharded arena — zero collectives.

    Returns a jitted ``(arena (N_max,P), weights (N_max,), mask (N_max,)) ->
    (P,)`` with the same sharding contract as :func:`masked_fedavg_sharded`.
    The median is coordinate-wise, so each device sorts and selects within its
    own ``(N_max, P/n_shards)`` column slice independently; the only
    cross-row reductions (``n_valid``) run on the replicated mask vector, so
    the compiled HLO stays collective-free.
    """
    ax = arena_axes(mesh, axes)
    repl = NamedSharding(mesh, P())
    return jax.jit(
        masked_coordinate_median,
        in_shardings=(NamedSharding(mesh, P(None, ax)), repl, repl),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def masked_trimmed_mean_sharded(mesh: Mesh, axes=None, trim_k: int = 1):
    """Masked trimmed mean over a column-sharded arena — zero collectives.

    Same sharding contract as :func:`masked_median_sharded`; ``trim_k`` is
    closed over (static) so the rank-band selection compiles once per trim.
    """
    ax = arena_axes(mesh, axes)

    def _agg(arena, weights, mask):
        return masked_trimmed_mean(arena, weights, mask, trim_k)

    repl = NamedSharding(mesh, P())
    return jax.jit(
        _agg,
        in_shardings=(NamedSharding(mesh, P(None, ax)), repl, repl),
        out_shardings=NamedSharding(mesh, P(ax)),
    )


def hierarchical_fedavg(mesh: Mesh, pod_axis: str = "pod"):
    """Beyond-paper: in-network aggregation over the ``pod`` mesh axis.

    Each pod *is* a learner silo: the global stack has shape
    ``(n_pods, P)`` with learner ``i``'s buffer living entirely on pod ``i``,
    sharded over the in-pod (``data``,``model``) axes.  The federation average
    is then a single ``psum`` over ``pod`` — in-network aggregation whose
    bandwidth scales with ICI links instead of a single controller-host NIC.

    Returns a jit-able function ``(stack (n_pods,P), weights (n_pods,)) ->
    (P,)`` built on ``shard_map`` over the full mesh.
    """

    other_axes = tuple(a for a in mesh.axis_names if a != pod_axis)

    def agg(local_buffer: jax.Array, local_weight: jax.Array) -> jax.Array:
        # local_buffer: (1, P / prod(other_axes)) — this pod's slice of its
        # own learner's buffer.  local_weight: (1,).
        wsum = jax.lax.psum(jnp.sum(local_weight), pod_axis)
        contrib = local_buffer[0].astype(jnp.float32) * local_weight[0]
        agg = jax.lax.psum(contrib, pod_axis) / jnp.maximum(wsum, 1e-12)
        return agg

    from jax import shard_map

    return shard_map(
        agg,
        mesh=mesh,
        in_specs=(P(pod_axis, other_axes), P(pod_axis)),
        out_specs=P(other_axes),
        check_vma=False,
    )
