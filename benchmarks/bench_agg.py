"""Paper §4.2 aggregation claim: parallelized aggregation ~10x over the
sequential per-tensor controller (Figs. 5c/6c/7c, 'MetisFL gRPC + OpenMP' vs
'MetisFL gRPC').

Arms (``run``):
  naive   — per-tensor, per-learner Python-loop FedAvg (the old controller)
  fused   — packed (N,P) single-reduction XLA FedAvg (this repo's controller)
  kernel  — the Pallas fedavg kernel (interpret mode on CPU: correctness-
            representative, not timing-representative; reported separately)
  secure  — masked secure aggregation (overhead of the privacy path)

Model sizes follow the paper: 100k / 1M / 10M params as 100-layer MLPs, so
the naive arm pays the per-tensor Python overhead ~200x per aggregation.

Arena-vs-stack comparison (``run_compare``, ``--compare``): the controller's
per-round aggregation latency with the legacy path (rebuild the ``(N, P)``
stack with ``jnp.stack``, then reduce) against the device-resident arena
(rows were written in place at arrival — off the critical path — so the
round's aggregation is just one masked reduction).  Also reports the arena's
per-upload row-write cost, which the stack path pays *again* as part of every
aggregation.  JSON output via ``--json`` for the CI nightly artifact.

Robust-rule arm (``run_robust``, ``--robust``): fedavg vs coordinate median
vs trimmed mean as masked reductions straight off the arena, plus the blocked
Pallas trimmed-mean kernel (interpret mode on CPU) with an allclose parity
check against the jnp rule — tracks the sort-vs-sum "robustness premium" a
byzantine-tolerant controller pays per round.

Fused dequant-into-aggregate (``run_fused``, ``--fused``): the int8-resident
arena's aggregation paths — the fused single-pass reduction
(``aggregation.masked_fedavg_q8``: read int8 rows + f32 group scales once,
never build the f32 ``(N, P)`` stack) against the two-program
dequantize-then-reduce alternative (materialize the f32 stack, then reduce —
the stack crosses memory twice) and against the plain f32 arena, plus the
blocked Pallas fused kernel (interpret mode on CPU) with an allclose parity
check.  Bytes moved: ``~N·P·(1 + 4/group) + 4P`` fused vs ``~9·N·P``
dequant-then-reduce; see ``benchmarks/roofline_table.py`` and docs/ARENA.md.

Sparse top-k aggregation (``run_sparse``, ``--sparse``): the topk-resident
arena's masked scatter-accumulate (``aggregation.masked_fedavg_topk``: read
the ``(N, k)`` index/value streams once, never build the dense ``(N, P)``
stack) against densify-then-reduce (materialize the f32 stack, then reduce)
and against the int8 arena's fused dequant-into-aggregate over the same
rows — the two wire-compression paths' per-round costs side by side, with
per-shape parity checks.  Bytes moved: ``~8·N·k + 4·P`` scatter vs
``~8·N·P`` densify-then-reduce; see ``benchmarks/roofline_table.py``.

Sharded-vs-single-device arena (``run_sharded``, ``--sharded``): the same
masked reduction and row write on a mesh-sharded arena
(``ArenaStore(mesh=...)``, every visible device) against the single-device
arena.  Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on
CPU (as the CI nightly does) for an 8-shard layout; on real hardware the
mesh spans the accelerators.  Includes an allclose parity check per shape so
the bench doubles as a smoke test.  See ``benchmarks/README.md``.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmarks.timing import bench
from repro.launch.compile_cache import enable_compile_cache
from repro.configs import housing_mlp
from repro.core import aggregation, naive, packing
from repro.core.secure import secure_fedavg
from repro.core.store import ArenaStore
from repro.models import mlp as mlp_model


def _models(size: str, n_learners: int):
    cfg = housing_mlp.config(size)
    base = mlp_model.init_params(jax.random.key(0), cfg)
    models = [
        jax.tree_util.tree_map(lambda x, i=i: x + 0.01 * i, base)
        for i in range(n_learners)
    ]
    return cfg, models


def run(sizes=("100k", "1m", "10m"), learner_counts=(10, 25, 50), iters=3):
    rows = []
    for size in sizes:
        for n in learner_counts:
            cfg, models = _models(size, n)
            weights = [100.0] * n
            stack = jnp.stack([packing.pack_numeric(m) for m in models])
            w = jnp.asarray(weights)
            jax.block_until_ready(stack)

            t_naive = bench(lambda: naive.naive_aggregate(models, weights),
                            warmup=1, iters=iters, block=False)
            t_fused = bench(lambda: aggregation.fedavg(stack, w), iters=iters)
            from repro.kernels import ops as kops
            t_kernel = bench(lambda: kops.fedavg(stack, w), warmup=1, iters=2)
            bufs = [stack[i] for i in range(min(n, 10))]
            t_secure = bench(
                lambda: secure_fedavg(bufs, [1.0] * len(bufs)),
                warmup=1, iters=2,
            )

            speedup = t_naive / t_fused
            rows.append({
                "bench": "aggregation", "size": size, "learners": n,
                "naive_s": t_naive, "fused_s": t_fused,
                "kernel_interpret_s": t_kernel, "secure_s(10)": t_secure,
                "speedup_fused_vs_naive": speedup,
            })
            print(
                f"agg,{size},{n},naive={t_naive*1e3:.2f}ms,"
                f"fused={t_fused*1e3:.3f}ms,kernel(interp)={t_kernel*1e3:.2f}ms,"
                f"secure10={t_secure*1e3:.2f}ms,speedup={speedup:.1f}x",
                flush=True,
            )
    return rows


def run_compare(learner_counts=(8, 32, 64), param_counts=(1 << 20, 1 << 22),
                iters=10):
    """Arena-vs-stack per-round aggregation latency.

    Both arms aggregate the same N fresh learner uploads:

    * **stack** — what ``Controller._aggregate(store_mode="stack")`` runs per
      round: ``jnp.stack`` over the N stored buffers (the O(N·P) rebuild)
      followed by the fused reduction.
    * **arena** — what ``store_mode="arena"`` runs per round: one masked
      reduction straight over the persistent device buffer.  Uploads were
      written in place at arrival (overlapped with the training round);
      ``arena_write_s`` reports that per-upload cost for honesty — the stack
      path pays the equivalent copy *inside* the timed aggregation instead.
    """
    rows = []
    for p in param_counts:
        for n in learner_counts:
            buffers = [
                jax.random.normal(jax.random.key(i), (p,), jnp.float32)
                for i in range(n)
            ]
            jax.block_until_ready(buffers)
            weights = [float(10 * (i + 1)) for i in range(n)]
            w = jnp.asarray(weights, jnp.float32)

            def stack_round():
                stack = jnp.stack(buffers, axis=0)
                return aggregation.fedavg(stack, w)

            t_stack = bench(stack_round, warmup=2, iters=iters)

            arena = ArenaStore(num_params=p, n_max=n, row_align=1024)
            for i, buf in enumerate(buffers):
                arena.write(f"l{i}", buf, weight=weights[i])

            def arena_round():
                with arena.lock:
                    return aggregation.masked_weighted_average(
                        arena.buffer, arena.weights, arena.mask
                    )[: arena.num_params]

            t_arena = bench(arena_round, warmup=2, iters=iters)

            # per-upload in-place row write (amortized at arrival, off the
            # aggregation critical path) — blocked on the device copy so the
            # reported cost is the real O(P) write, not dispatch overhead
            def arena_write():
                arena.write("l0", buffers[0], weight=weights[0])
                jax.block_until_ready(arena.buffer)

            t_write = bench(arena_write, warmup=2, iters=iters, block=False)

            speedup = t_stack / t_arena
            row = {
                "bench": "arena_vs_stack", "params": p, "learners": n,
                "stack_round_s": t_stack, "arena_round_s": t_arena,
                "arena_write_s": t_write,
                "speedup_arena_vs_stack": speedup,
            }
            rows.append(row)
            print(
                f"compare,P={p},N={n},stack={t_stack*1e3:.2f}ms,"
                f"arena={t_arena*1e3:.2f}ms,write={t_write*1e3:.3f}ms,"
                f"speedup={speedup:.2f}x",
                flush=True,
            )
            del arena, buffers
    return rows


def run_robust(learner_counts=(8, 32, 64), param_counts=(1 << 20, 1 << 22),
               iters=10, trim_k=2):
    """Robust-rule aggregation latency off the arena (``--robust``).

    The same masked-reduction shape as ``run_compare``'s arena arm, across
    the three aggregation rules a controller can run: fedavg (the weighted
    mean baseline), coordinate median, and trimmed mean — all straight off
    the device-resident arena, no re-stack — plus the blocked Pallas
    trimmed-mean kernel (interpret mode on CPU: correctness-representative,
    not timing-representative; reported separately).  A per-shape allclose
    parity check between the jnp rule and the kernel keeps the bench
    honest.  The robust premium (sort vs sum) is the price of byzantine
    tolerance; docs/STRESS.md shows what it buys.
    """
    import numpy as np

    from repro.kernels import ops as kops

    rows = []
    for p in param_counts:
        for n in learner_counts:
            arena = ArenaStore(num_params=p, n_max=n, row_align=1024)
            for i in range(n):
                arena.write(
                    f"l{i}",
                    jax.random.normal(jax.random.key(i), (p,), jnp.float32),
                    weight=float(10 * (i + 1)),
                )

            def fedavg_round():
                with arena.lock:
                    return aggregation.masked_weighted_average(
                        arena.buffer, arena.weights, arena.mask
                    )[: arena.num_params]

            def median_round():
                with arena.lock:
                    return aggregation.masked_coordinate_median(
                        arena.buffer, arena.weights, arena.mask
                    )[: arena.num_params]

            def trimmed_round():
                with arena.lock:
                    return aggregation.masked_trimmed_mean(
                        arena.buffer, arena.weights, arena.mask, trim_k
                    )[: arena.num_params]

            def kernel_round():
                with arena.lock:
                    return kops.masked_trimmed_mean(
                        arena.buffer, arena.weights, arena.mask, trim_k=trim_k
                    )[: arena.num_params]

            np.testing.assert_allclose(
                np.asarray(trimmed_round()), np.asarray(kernel_round()),
                rtol=1e-5, atol=1e-6,
            )
            t_fedavg = bench(fedavg_round, warmup=2, iters=iters)
            t_median = bench(median_round, warmup=2, iters=iters)
            t_trimmed = bench(trimmed_round, warmup=2, iters=iters)
            t_kernel = bench(kernel_round, warmup=1, iters=2)

            row = {
                "bench": "robust_rules", "params": p, "learners": n,
                "trim_k": trim_k,
                "fedavg_s": t_fedavg, "median_s": t_median,
                "trimmed_mean_s": t_trimmed,
                "kernel_interpret_s": t_kernel,
                "robust_premium_median": t_median / t_fedavg,
                "robust_premium_trimmed": t_trimmed / t_fedavg,
            }
            rows.append(row)
            print(
                f"robust,P={p},N={n},fedavg={t_fedavg*1e3:.2f}ms,"
                f"median={t_median*1e3:.2f}ms,"
                f"trimmed={t_trimmed*1e3:.2f}ms,"
                f"kernel(interp)={t_kernel*1e3:.2f}ms,"
                f"premium={t_trimmed/t_fedavg:.2f}x",
                flush=True,
            )
            del arena
    return rows


def run_fused(shapes=((1 << 22, 8), (1 << 22, 32), (1 << 22, 64),
                      (1 << 24, 32)),
              iters=10):
    """Fused dequant-into-aggregate vs dequantize-then-reduce (``--fused``).

    Every arm aggregates the same N uploads resident in an int8
    :class:`ArenaStore` (plus an f32 twin for the baseline):

    * **fused** — ``aggregation.masked_fedavg_q8``: one program reads the
      int8 rows and their per-group f32 scales and emits the masked weighted
      mean; the f32 ``(N, P)`` stack is never materialized.
    * **dequant_reduce** — what an int8-resident arena costs *without* the
      fused path: program 1 dequantizes into an f32 ``(N, P)`` stack, program
      2 reduces it.  The stack is written and re-read — ``~9·N·P`` bytes vs
      the fused pass's ``~N·P·(1 + 4/group) + 4P``.
    * **f32_arena** — the plain f32 arena reduction, for the residency-vs-
      latency trade-off (4 bytes/param resident vs ~1.016).
    * **kernel** — the blocked Pallas fused kernel
      (``kernels/ops.masked_fedavg_q8``; interpret mode on CPU:
      correctness-representative, not timing-representative).

    Per-shape allclose parity (fused vs dequant-then-reduce vs the Pallas
    kernel) keeps the bench honest; ``shapes`` is ``(P, N)`` pairs rather
    than a cross product so the big-P row doesn't multiply against big N
    (the dequant arm's f32 stack is the memory hog).
    """
    import functools

    import numpy as np

    from repro.kernels import ops as kops

    @functools.partial(jax.jit, static_argnames=("group",))
    def dequant_rows(q, scales, group):
        n, p = q.shape
        rows = q.astype(jnp.float32).reshape(n, p // group, group)
        return (rows * scales[:, :, None]).reshape(n, p)

    out_rows = []
    for p, n in shapes:
        arena = ArenaStore(num_params=p, n_max=n, row_align=1024,
                           arena_dtype="int8")
        f32 = ArenaStore(num_params=p, n_max=n, row_align=1024)
        for i in range(n):
            buf = jax.random.normal(jax.random.key(i), (p,), jnp.float32)
            arena.write(f"l{i}", buf, weight=float(10 * (i + 1)))
            f32.write(f"l{i}", buf, weight=float(10 * (i + 1)))
            del buf
        group = arena.qgroup

        def fused_round():
            with arena.lock:
                return aggregation.masked_fedavg_q8(
                    arena.buffer, arena.scales, arena.weights, arena.mask,
                    group,
                )[: arena.num_params]

        def dequant_reduce_round():
            with arena.lock:
                stack = dequant_rows(arena.buffer, arena.scales, group)
                jax.block_until_ready(stack)  # two programs, like real code
                return aggregation.masked_weighted_average(
                    stack, arena.weights, arena.mask
                )[: arena.num_params]

        def f32_round():
            with f32.lock:
                return aggregation.masked_weighted_average(
                    f32.buffer, f32.weights, f32.mask
                )[: f32.num_params]

        def kernel_round():
            with arena.lock:
                return kops.masked_fedavg_q8(
                    arena.buffer, arena.scales, arena.weights, arena.mask,
                    group,
                )[: arena.num_params]

        want = np.asarray(dequant_reduce_round())
        np.testing.assert_allclose(np.asarray(fused_round()), want,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(kernel_round()), want,
                                   rtol=2e-5, atol=2e-5)
        t_fused = bench(fused_round, warmup=2, iters=iters)
        t_dq = bench(dequant_reduce_round, warmup=2, iters=iters)
        t_f32 = bench(f32_round, warmup=2, iters=iters)
        t_kernel = bench(kernel_round, warmup=1, iters=2)

        speedup = t_dq / t_fused
        resident_q8 = arena.buffer.nbytes + arena.scales.nbytes
        row = {
            "bench": "fused_q8", "params": p, "learners": n, "group": group,
            "fused_s": t_fused, "dequant_reduce_s": t_dq,
            "f32_arena_s": t_f32, "kernel_interpret_s": t_kernel,
            "resident_bytes_int8": resident_q8,
            "resident_bytes_f32": f32.buffer.nbytes,
            "shrink_resident": f32.buffer.nbytes / resident_q8,
            "speedup_fused_vs_dequant": speedup,
        }
        out_rows.append(row)
        print(
            f"fused,P={p},N={n},fused={t_fused*1e3:.2f}ms,"
            f"dequant_reduce={t_dq*1e3:.2f}ms,f32={t_f32*1e3:.2f}ms,"
            f"kernel(interp)={t_kernel*1e3:.2f}ms,"
            f"shrink={row['shrink_resident']:.2f}x,speedup={speedup:.2f}x",
            flush=True,
        )
        del arena, f32
    return out_rows


def run_sparse(shapes=((1 << 22, 8), (1 << 22, 32), (1 << 22, 64),
                       (1 << 24, 32)),
               k_divisor=64, iters=10):
    """Sparse top-k aggregation: scatter-accumulate vs alternatives
    (``--sparse``).

    Every arm aggregates the *same* N sparse top-k uploads (k = P /
    ``k_divisor`` coordinates per row, the ``sparse_mode="direct"``
    resident layout):

    * **scatter** — ``aggregation.masked_fedavg_topk``: one program scatters
      the ``(N, k)`` weighted value streams into the f32 output row; the
      dense ``(N, P)`` stack is never materialized (``~8·N·k + 4·P`` bytes).
    * **densify_reduce** — what ``sparse_mode="densify"`` costs at
      aggregation time if the densified rows were *not* arena-resident:
      program 1 scatters each row into a dense f32 ``(N, P)`` stack, program
      2 runs the masked reduction.  The stack is written and re-read —
      ``~8·N·P`` bytes.
    * **fused_q8** — the int8-resident arena's fused dequant-into-aggregate
      (``aggregation.masked_fedavg_q8``) over the same densified rows,
      quantized: the other wire-compression path's per-round cost, for the
      codec trade-off table in docs/ARENA.md.

    Per-shape parity: scatter must match densify-then-reduce to f32
    tolerance (both are exact reorderings of the same sum), and fused_q8
    must land inside the per-group quantization bound of that target.
    ``shapes`` is ``(P, N)`` pairs, same convention as :func:`run_fused`.
    """
    import functools

    import numpy as np

    @functools.partial(jax.jit, static_argnames=("width",))
    def densify_rows(idx, val, width):
        n = idx.shape[0]
        dense = jnp.zeros((n, width), jnp.float32)
        return dense.at[jnp.arange(n)[:, None], idx].add(val)

    out_rows = []
    for p, n in shapes:
        k = max(1, p // k_divisor)
        arena = ArenaStore(num_params=p, n_max=n, row_align=1024,
                           arena_dtype="topk", sparse_k=k)
        q8 = ArenaStore(num_params=p, n_max=n, row_align=1024,
                        arena_dtype="int8")
        amax = 0.0
        for i in range(n):
            kidx, kkey = jax.random.split(jax.random.key(i))
            idx = jax.random.choice(kidx, p, shape=(k,), replace=False)
            val = jax.random.normal(kkey, (k,), jnp.float32)
            arena.write_sparse(f"l{i}", idx.astype(jnp.int32), val,
                               weight=float(10 * (i + 1)))
            q8.write(f"l{i}",
                     densify_rows(idx[None, :].astype(jnp.int32),
                                  val[None, :], arena.padded_params)[0],
                     weight=float(10 * (i + 1)))
            amax = max(amax, float(jnp.max(jnp.abs(val))))
        group = q8.qgroup
        width = arena.padded_params

        def scatter_round():
            with arena.lock:
                return aggregation.masked_fedavg_topk(
                    arena.indices, arena.buffer, arena.weights, arena.mask,
                    width,
                )[: arena.num_params]

        def densify_reduce_round():
            with arena.lock:
                stack = densify_rows(arena.indices, arena.buffer, width)
                jax.block_until_ready(stack)  # two programs, like real code
                return aggregation.masked_weighted_average(
                    stack, arena.weights, arena.mask
                )[: arena.num_params]

        def fused_q8_round():
            with q8.lock:
                return aggregation.masked_fedavg_q8(
                    q8.buffer, q8.scales, q8.weights, q8.mask, group,
                )[: q8.num_params]

        want = np.asarray(densify_reduce_round())
        np.testing.assert_allclose(np.asarray(scatter_round()), want,
                                   rtol=2e-5, atol=2e-5)
        # fused_q8 aggregates the quantized twin of the same rows: the
        # weighted mean can drift at most one group scale (amax/127) off.
        np.testing.assert_allclose(np.asarray(fused_q8_round()), want,
                                   atol=amax / 127 + 1e-6)
        t_scatter = bench(scatter_round, warmup=2, iters=iters)
        t_dense = bench(densify_reduce_round, warmup=2, iters=iters)
        t_q8 = bench(fused_q8_round, warmup=2, iters=iters)

        speedup = t_dense / t_scatter
        resident = arena.buffer.nbytes + arena.indices.nbytes
        row = {
            "bench": "sparse_topk", "params": p, "learners": n, "k": k,
            "scatter_s": t_scatter, "densify_reduce_s": t_dense,
            "fused_q8_s": t_q8,
            "resident_bytes_topk": resident,
            "resident_bytes_f32": 4 * n * width,
            "shrink_resident": 4 * n * width / resident,
            "speedup_scatter_vs_densify": speedup,
        }
        out_rows.append(row)
        print(
            f"sparse,P={p},N={n},k={k},scatter={t_scatter*1e3:.2f}ms,"
            f"densify_reduce={t_dense*1e3:.2f}ms,fused_q8={t_q8*1e3:.2f}ms,"
            f"shrink={row['shrink_resident']:.1f}x,speedup={speedup:.2f}x",
            flush=True,
        )
        del arena, q8
    return out_rows


def run_sharded(learner_counts=(8, 32), param_counts=(1 << 20, 1 << 22),
                iters=10):
    """Sharded-vs-single-device arena: masked reduction + row-write latency.

    Both arms hold the same N uploads in an :class:`ArenaStore`; the sharded
    arm lays the buffer out column-sharded over a 1-D ``("data",)`` mesh of
    every visible device (``launch/mesh.make_controller_mesh``) and reduces
    per shard with zero collectives.  On CPU with forced host devices the
    sharded arm mostly demonstrates *layout correctness* (host "devices"
    share one socket); on real accelerators each shard reduces on its own
    chip's HBM.  A per-shape allclose parity assert keeps the bench honest.
    """
    import numpy as np

    from repro.launch.mesh import make_controller_mesh

    n_dev = jax.device_count()
    if n_dev == 1:
        print("sharded: only 1 device visible — layout is a no-op; set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU",
              flush=True)
    mesh = make_controller_mesh()

    rows = []
    for p in param_counts:
        for n in learner_counts:
            buffers = [
                jax.random.normal(jax.random.key(i), (p,), jnp.float32)
                for i in range(n)
            ]
            jax.block_until_ready(buffers)
            weights = [float(10 * (i + 1)) for i in range(n)]

            single = ArenaStore(num_params=p, n_max=n, row_align=1024)
            sharded = ArenaStore(num_params=p, n_max=n, row_align=1024, mesh=mesh)
            for i, buf in enumerate(buffers):
                single.write(f"l{i}", buf, weight=weights[i])
                sharded.write(f"l{i}", buf, weight=weights[i])

            def single_round():
                with single.lock:
                    return aggregation.masked_weighted_average(
                        single.buffer, single.weights, single.mask
                    )[: single.num_params]

            sharded_fn = aggregation.masked_fedavg_sharded(mesh)

            def sharded_round():
                with sharded.lock:
                    return sharded_fn(
                        sharded.buffer, sharded.weights, sharded.mask
                    )[: sharded.num_params]

            np.testing.assert_allclose(
                np.asarray(single_round()), np.asarray(sharded_round()),
                rtol=1e-5, atol=1e-6,
            )
            t_single = bench(single_round, warmup=2, iters=iters)
            t_sharded = bench(sharded_round, warmup=2, iters=iters)

            def sharded_write():
                sharded.write("l0", buffers[0], weight=weights[0])
                jax.block_until_ready(sharded.buffer)

            t_write = bench(sharded_write, warmup=2, iters=iters, block=False)

            row = {
                "bench": "arena_sharded", "params": p, "learners": n,
                "n_shards": sharded.n_shards,
                "shard_width": sharded.shard_width,
                "single_round_s": t_single, "sharded_round_s": t_sharded,
                "sharded_write_s": t_write,
                "speedup_sharded_vs_single": t_single / t_sharded,
            }
            rows.append(row)
            print(
                f"sharded,P={p},N={n},shards={sharded.n_shards},"
                f"single={t_single*1e3:.2f}ms,sharded={t_sharded*1e3:.2f}ms,"
                f"write={t_write*1e3:.3f}ms,"
                f"speedup={t_single/t_sharded:.2f}x",
                flush=True,
            )
            del single, sharded, buffers
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare", action="store_true",
                    help="arena-vs-stack per-round aggregation latency")
    ap.add_argument("--sharded", action="store_true",
                    help="mesh-sharded vs single-device arena aggregation")
    ap.add_argument("--robust", action="store_true",
                    help="robust rules (median / trimmed mean) vs fedavg "
                         "off the arena, incl. the Pallas kernel")
    ap.add_argument("--fused", action="store_true",
                    help="int8 arena: fused dequant-into-aggregate vs "
                         "dequantize-then-reduce vs the f32 arena")
    ap.add_argument("--sparse", action="store_true",
                    help="top-k arena: masked scatter-accumulate vs "
                         "densify-then-reduce vs the fused int8 path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump result rows as JSON")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.sparse:
        if args.smoke:
            rows = run_sparse(shapes=((1 << 16, 4), (1 << 16, 32)),
                              k_divisor=64, iters=3)
        else:
            rows = run_sparse()
    elif args.fused:
        if args.smoke:
            rows = run_fused(shapes=((1 << 16, 4), (1 << 16, 8)), iters=3)
        else:
            rows = run_fused()
    elif args.sharded:
        if args.smoke:
            rows = run_sharded(learner_counts=(4, 8), param_counts=(1 << 16,),
                               iters=3)
        else:
            rows = run_sharded()
    elif args.robust:
        if args.smoke:
            rows = run_robust(learner_counts=(4, 8), param_counts=(1 << 16,),
                              iters=3, trim_k=1)
        else:
            rows = run_robust()
    elif args.compare:
        if args.smoke:
            rows = run_compare(learner_counts=(4, 8), param_counts=(1 << 16,),
                               iters=3)
        else:
            rows = run_compare()
    else:
        if args.smoke:
            rows = run(sizes=("100k",), learner_counts=(4,), iters=2)
        else:
            rows = run()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    return rows


if __name__ == "__main__":
    main()
