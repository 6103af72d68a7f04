"""Federated training launcher.

Wires the full stack together: configs → models → learners → controller →
driver, with every paper feature selectable from the CLI:

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-14b --reduced --learners 8 --rounds 5 \
        --protocol semi_sync --server-opt fedadam --secure --quantize

``--arch housing-mlp --size 10m`` reproduces the paper's stress-test model.
Full-scale configs are exercised via ``launch/dryrun.py``; this launcher
trains reduced variants (or the 100M example config) on the host.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as optim_mod
from repro.configs import ARCHITECTURES, get_config, get_reduced
from repro.core import Driver, FederationEnv, Learner, SelectionPolicy, TerminationCriteria
from repro.launch.compile_cache import enable_compile_cache
from repro.data import LMDataIterator, dirichlet_partition, iid_partition, make_housing_data, make_lm_data
from repro.models import mlp as mlp_model
from repro.models import transformer
from repro.checkpoint import save_checkpoint

log = logging.getLogger("repro.train")


def build_lm_learners(cfg, n_learners: int, seed: int = 0,
                      n_seq_per_learner: int = 64, seq_len: int = 64,
                      optimizer=None):
    """One learner per silo over a disjoint synthetic token shard."""
    toks = make_lm_data(n_learners * n_seq_per_learner, seq_len, cfg.vocab_size, seed)
    shards = iid_partition(toks.shape[0], n_learners, seed=seed)
    learners = []
    for i, idx in enumerate(shards):
        it = LMDataIterator(toks[idx], seed=seed + i)

        def loss_fn(params, batch, _cfg=cfg):
            return transformer.lm_loss(params, batch, _cfg)

        def eval_fn(params, batch, _cfg=cfg):
            return {"eval_loss": transformer.lm_loss(params, batch, _cfg)}

        def eval_data(_it=it):
            return _it(16)

        learners.append(
            Learner(
                learner_id=f"learner_{i:03d}",
                loss_fn=loss_fn,
                eval_fn=eval_fn,
                data_fn=it,
                eval_data_fn=eval_data,
                optimizer=optimizer or optim_mod.sgd(0.5),
                num_examples=it.n_examples,
            )
        )
    return learners


def build_housing_learners(size: str, n_learners: int, seed: int = 0,
                           per_learner: int = 100, optimizer=None):
    """Paper §4.2 setup: 100 samples per learner, sampled with replacement."""
    from repro.configs import housing_mlp

    cfg = housing_mlp.config(size)
    data = make_housing_data(seed=seed)
    shards = iid_partition(
        data.x.shape[0], n_learners, seed=seed,
        per_learner=per_learner, with_replacement=True,
    )
    learners = []
    for i, idx in enumerate(shards):
        x, y = data.x[idx], data.y[idx]
        rng = np.random.default_rng(seed + i)

        def data_fn(bs, _x=x, _y=y, _rng=rng):
            j = _rng.integers(0, _x.shape[0], size=bs)
            return _x[j], _y[j]

        learners.append(
            Learner(
                learner_id=f"learner_{i:03d}",
                loss_fn=mlp_model.mse_loss,
                eval_fn=lambda p, b: {"eval_loss": mlp_model.mse_loss(p, b)},
                data_fn=data_fn,
                eval_data_fn=lambda _x=x, _y=y: (_x, _y),
                optimizer=optimizer or optim_mod.sgd(0.01),
                num_examples=x.shape[0],
            )
        )
    return cfg, learners


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="housing-mlp",
                    choices=list(ARCHITECTURES) + ["housing-mlp", "fedlm-100m"])
    ap.add_argument("--size", default="1m", help="housing-mlp size: 100k|1m|10m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of an assigned arch")
    ap.add_argument("--learners", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--protocol", default="sync", choices=["sync", "semi_sync", "async"])
    ap.add_argument("--server-opt", default="fedavg",
                    choices=["fedavg", "sgdm", "fedadagrad", "fedyogi", "fedadam"])
    ap.add_argument("--selection", default="all", choices=["all", "random", "stratified"])
    ap.add_argument("--fraction", type=float, default=1.0)
    ap.add_argument("--prox-mu", type=float, default=0.0)
    ap.add_argument("--secure", action="store_true")
    ap.add_argument("--quantize", action="store_true",
                    help="int8 transport codec (Pallas kernel)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(name)s: %(message)s")
    enable_compile_cache()
    dev = jax.devices()[0]
    log.info("device: %s %s x%d", dev.platform, dev.device_kind, len(jax.devices()))

    if args.arch == "housing-mlp":
        cfg, learners = build_housing_learners(args.size, args.learners, args.seed)
        initial = mlp_model.init_params(jax.random.key(args.seed), cfg)
    else:
        if args.arch == "fedlm-100m":
            from repro.configs.fedlm_100m import config as fedlm_config

            cfg = fedlm_config()
        else:
            cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
        learners = build_lm_learners(
            cfg, args.learners, args.seed, optimizer=optim_mod.sgd(args.lr)
        )
        initial = transformer.init_params(jax.random.key(args.seed), cfg)

    env = FederationEnv(
        protocol=args.protocol,
        local_steps=args.local_steps,
        batch_size=args.batch_size,
        learning_rate=args.lr,
        prox_mu=args.prox_mu,
        selection=SelectionPolicy(kind=args.selection, fraction=args.fraction),
        server_optimizer=args.server_opt,
        secure_aggregation=args.secure,
        termination=TerminationCriteria(max_rounds=args.rounds),
    )
    driver = Driver(env)
    if args.quantize:
        from repro.kernels.ops import QuantCodec

        driver.controller.channel.codec = QuantCodec()

    t0 = time.time()
    driver.initialize(initial, learners)
    history = driver.run()
    wall = time.time() - t0

    print("\nround,train_dispatch_s,train_round_s,aggregation_s,"
          "eval_dispatch_s,eval_round_s,federation_round_s,eval_loss")
    for h in history:
        r = h.as_row()
        print(
            f"{r['round']},{r['train_dispatch_s']:.4f},{r['train_round_s']:.4f},"
            f"{r['aggregation_s']:.4f},{r['eval_dispatch_s']:.4f},"
            f"{r['eval_round_s']:.4f},{r['federation_round_s']:.4f},"
            f"{h.metrics.get('eval_loss', float('nan')):.5f}"
        )
    stats = driver.controller.channel.stats
    print(f"\ntotal wall: {wall:.2f}s; wire bytes: {stats.bytes_moved:,}; "
          f"messages: {stats.messages}; serialize: {stats.serialize_s:.3f}s")

    if args.checkpoint_dir:
        path = save_checkpoint(
            args.checkpoint_dir, len(history), driver.controller.global_params,
            metadata={"arch": args.arch, "rounds": len(history)},
        )
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
