"""Figs. 5/6/7 reproduction: per-operation wall-clock across model sizes and
federation sizes, MetisFL-style controller vs the naive (old-Python) one.

Measured operations per federation round (paper Fig. 1 / Figs. 5-7 panels):
  train_dispatch, train_round, aggregation, eval_dispatch, eval_round,
  federation_round.

Arms:
  metis — this repo's controller: flat-buffer transport, async dispatch,
          fused packed aggregation.
  naive — sequential blocking dispatch with per-tensor pickle transport and
          per-tensor Python-loop aggregation (the paper's comparison point).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import Controller, SyncProtocol, naive, packing
from repro.launch.train import build_housing_learners
from repro.models import mlp as mlp_model


def _metis_round(size: str, n_learners: int, local_steps=1) -> dict:
    cfg, learners = build_housing_learners(size, n_learners, seed=0)
    ctrl = Controller(protocol=SyncProtocol(local_steps=local_steps, batch_size=100))
    ctrl.set_initial_model(mlp_model.init_params(jax.random.key(0), cfg))
    for l in learners:
        ctrl.register_learner(l)
    ctrl.engine.run(rounds=1)  # warmup (jit compilation of learner steps)
    t = ctrl.engine.run(rounds=1)[0]
    ctrl.shutdown()
    return t.as_row()


def _naive_round(size: str, n_learners: int, local_steps=1) -> dict:
    """Sequential controller: blocking dispatch, per-tensor transport+agg."""
    cfg, learners = build_housing_learners(size, n_learners, seed=0)
    params = mlp_model.init_params(jax.random.key(0), cfg)
    treedef = jax.tree_util.tree_structure(params)
    from repro.core.scheduler import TrainTask

    task = TrainTask(round_id=0, local_steps=local_steps, batch_size=100,
                     learning_rate=0.01)
    # warmup jits
    learners[0].fit(params, task)

    row = {}
    t_round = time.perf_counter()
    # train: serialize per-tensor, run learner, wait; strictly sequential
    updates = []
    t0 = time.perf_counter()
    dispatch_s = 0.0
    for l in learners:
        td = time.perf_counter()
        blobs = naive.naive_serialize(params)
        received = naive.naive_deserialize(blobs, treedef)
        dispatch_s += time.perf_counter() - td
        updates.append(l.fit(received, task))
    row["train_dispatch_s"] = dispatch_s
    row["train_round_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    agg = naive.naive_aggregate(
        [u.params for u in updates], [float(u.num_examples) for u in updates]
    )
    row["aggregation_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    dispatch_s = 0.0
    for l in learners:
        td = time.perf_counter()
        blobs = naive.naive_serialize(agg)
        received = naive.naive_deserialize(blobs, treedef)
        dispatch_s += time.perf_counter() - td
        l.evaluate(received, 0)
    row["eval_dispatch_s"] = dispatch_s
    row["eval_round_s"] = time.perf_counter() - t0
    row["federation_round_s"] = time.perf_counter() - t_round
    return row


OPS = ("train_dispatch_s", "train_round_s", "aggregation_s",
       "eval_dispatch_s", "eval_round_s", "federation_round_s")


def run(sizes=("100k", "1m"), learner_counts=(10, 25), include_naive=True):
    rows = []
    for size in sizes:
        for n in learner_counts:
            m = _metis_round(size, n)
            rec = {"bench": "ops", "size": size, "learners": n, "arm": "metis",
                   **{k: m[k] for k in OPS}}
            rows.append(rec)
            line = ",".join(f"{k}={m[k]*1e3:.2f}ms" for k in OPS)
            print(f"ops,metis,{size},{n},{line}", flush=True)
            if include_naive:
                nv = _naive_round(size, n)
                rows.append({"bench": "ops", "size": size, "learners": n,
                             "arm": "naive", **{k: nv[k] for k in OPS}})
                line = ",".join(f"{k}={nv[k]*1e3:.2f}ms" for k in OPS)
                print(f"ops,naive,{size},{n},{line}", flush=True)
                print(
                    f"ops,speedup,{size},{n},"
                    f"agg={nv['aggregation_s']/max(m['aggregation_s'],1e-9):.1f}x,"
                    f"round={nv['federation_round_s']/max(m['federation_round_s'],1e-9):.1f}x",
                    flush=True,
                )
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default=None, choices=["100k", "1m", "10m"])
    ap.add_argument("--learners", type=int, nargs="*", default=None)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweep: sizes x {10,25,50,100,200}")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.full:
        run(sizes=("100k", "1m", "10m"), learner_counts=(10, 25, 50, 100, 200))
    else:
        run(
            sizes=(args.size,) if args.size else ("100k", "1m"),
            learner_counts=tuple(args.learners) if args.learners else (10, 25),
        )
