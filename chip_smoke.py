#!/usr/bin/env python3
"""Run the federation's main path once on a TPU and check what it commits.

    python chip_smoke.py             # one chip: Phase A (f32) and Phase B (int8)
    python chip_smoke.py --chips 4   # four chips: sharded vs single-device arena

The model is fedlm-100m (``repro.configs.fedlm_100m``, P = 73,937,664) at
full width with random weights from ``--seed``, driven through the entry
points a user calls: ``build_lm_learners`` -> ``FederationEnv`` ->
``Driver.initialize`` / ``Driver.run``.

* Phase A: 8 learners, sync FedAvg, f32 arena, raw uplink, 3 rounds.  Then
  the Pallas f32, int8 and trimmed-mean reductions run once on the phase's
  arena against the jnp reductions of ``core/aggregation.py``.
* Phase B: the same federation with an int8 uplink and an int8 arena, 2
  rounds: the Pallas quantize/dequantize and the fused int8 reduction are on
  the path.
* ``--chips 4``: only Phase A's federation, once on a 4-way column-sharded
  arena and once on a single-device arena.  The committed global rows must
  agree, the arena must sit on all four devices, and the compiled sharded
  reduction must hold no collective.

Every federation checks that its eval loss is finite and falls, and that the
committed global row equals a float64 host reference: the weighted mean of
the last round's arena rows under that round's mask.  Each earlier line of
stdout is one ``<record> {json}``; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits non-zero and prints
no result.  It runs in one process, which holds the chip, and starts no
other.  The compile cache goes where ``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

if not compile_cache.__file__.startswith(os.path.join(HERE, "src") + os.sep):
    raise SystemExit(f"chip_smoke: repro must come from {HERE}/src, "
                     f"not {compile_cache.__file__}")

N_LEARNERS = 8
BATCH, SEQ_LEN, LOCAL_STEPS, LR = 32, 64, 4, 0.05
ROUNDS_A, ROUNDS_B = 3, 2
# f32 reduction-order slack for the float64 reference: the masked reduce sums
# N rows and the fedavg server step adds two roundings.
REF_ULPS = 4 * (N_LEARNERS + 4)
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter")


class SmokeFailure(Exception):
    """A phase's output failed its check."""


def emit(record: str, **fields) -> None:
    print(f"{record} {json.dumps(fields, sort_keys=True)}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """Counts backend compiles and persistent-cache hits via jax.monitoring."""

    def __init__(self) -> None:
        from jax import monitoring

        self.counts: collections.Counter = collections.Counter()
        self.secs: collections.Counter = collections.Counter()
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        self.counts[name] += 1

    def _duration(self, name: str, secs: float, **_) -> None:
        self.counts[name] += 1
        self.secs[name] += secs

    def snapshot(self) -> dict:
        return {
            "compiles": self.counts["/jax/core/compile/backend_compile_duration"],
            "compile_s": self.secs["/jax/core/compile/backend_compile_duration"],
            "cache_hits": self.counts["/jax/compilation_cache/cache_hits"],
            "cache_misses": self.counts["/jax/compilation_cache/cache_misses"],
        }

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips: int) -> dict:
    """Refuse to run anywhere but on ``chips`` TPU devices, Mosaic kernels on."""
    from repro.kernels import ops as kops

    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found; JAX runs on "
                         f"{info['platform']!r} ({info['kind']})")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {info['count']}")
    if kops.interpret_mode():
        raise SystemExit("chip_smoke: Pallas kernels would run in interpret mode")
    return info


def memory_stat(key: str) -> list[int]:
    """``key`` of every device's ``memory_stats()``, -1 where absent."""
    import jax

    return [int((d.memory_stats() or {}).get(key, -1)) for d in jax.devices()]


def peak_bytes() -> list[int]:
    return memory_stat("peak_bytes_in_use")


def run_federation(cfg, *, rounds: int, seed: int, n_learners: int = N_LEARNERS,
                   batch: int = BATCH, seq_len: int = SEQ_LEN,
                   local_steps: int = LOCAL_STEPS, lr: float = LR, **env_kw):
    """One federation through the user's entry points; returns (driver, history)."""
    import jax

    from repro.core import Driver, FederationEnv, TerminationCriteria
    from repro.launch.train import build_lm_learners
    from repro.models import transformer
    from repro.optim import sgd

    learners = build_lm_learners(cfg, n_learners, seed=seed, seq_len=seq_len,
                                 optimizer=sgd(lr))
    initial = transformer.init_params(jax.random.key(seed), cfg)
    env = FederationEnv(
        protocol="sync", local_steps=local_steps, batch_size=batch,
        learning_rate=lr, server_optimizer="fedavg",
        termination=TerminationCriteria(max_rounds=rounds), **env_kw,
    )
    driver = Driver(env)
    driver.initialize(initial, learners)
    del initial
    history = driver.run()
    return driver, history


def check_losses(history) -> list[float]:
    losses = [float(h.metrics["eval_loss"]) for h in history]
    check(all(math.isfinite(x) for x in losses), f"eval loss not finite: {losses}")
    check(losses[-1] < losses[0], f"eval loss did not fall: {losses}")
    return losses


def reference_check(controller) -> dict:
    """Committed global row vs a float64 host reference of the last round.

    The reference is the weighted mean, under the round's mask, of the arena
    rows as the arena holds them (int8 rows dequantized exactly in float64),
    accumulated one row at a time.
    """
    arena = controller.arena
    p = arena.num_params
    with arena.lock:
        mask = np.asarray(arena.round_mask(controller.learner_ids), np.float64)
        weights = np.asarray(arena.weights, np.float64) * mask
        check(weights.sum() > 0, "no valid arena row in the last round")
        acc = np.zeros((p,), np.float64)
        row_max = 0.0
        for i in np.flatnonzero(mask > 0):
            if arena.arena_dtype == "int8":
                q = np.asarray(arena.buffer[i]).astype(np.float64)
                s = np.asarray(arena.scales[i]).astype(np.float64)
                row = (q.reshape(-1, arena.qgroup) * s[:, None]).reshape(-1)[:p]
            else:
                row = np.asarray(arena.buffer[i, :p]).astype(np.float64)
            row_max = max(row_max, float(np.max(np.abs(row))))
            acc += weights[i] * row
            del row
    ref = acc / weights.sum()
    got = np.asarray(controller.global_buffer, np.float64)
    check(got.shape == ref.shape, f"global row {got.shape} vs reference {ref.shape}")
    err = float(np.max(np.abs(got - ref)))
    tol = REF_ULPS * float(np.finfo(np.float32).eps) * row_max
    check(math.isfinite(err) and err <= tol,
          f"global row off the float64 reference: {err:.3e} > {tol:.3e}")
    return {"ref_max_abs_err": err, "ref_tol": tol, "rows": int(mask.sum())}


def compiled_call(fn, *args) -> tuple:
    """Compile ``fn`` for ``args``, require a Mosaic kernel in it, and run it."""
    compiled = fn.lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)} compiled without a Pallas TPU kernel")
    return compiled(*args)


def kernel_checks(arena, mask) -> dict:
    """The three Pallas reductions vs the jnp ones, on a federation's arena."""
    import jax
    import jax.numpy as jnp

    from repro.core import aggregation
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    buf, w = arena.buffer, arena.weights
    scale = float(jnp.max(jnp.abs(buf)))
    out = {}

    def compare(name, got, want):
        err = float(jnp.max(jnp.abs(got - want)))
        tol = REF_ULPS * float(np.finfo(np.float32).eps) * scale
        check(math.isfinite(err) and err <= tol,
              f"Pallas {name} off the jnp reduction: {err:.3e} > {tol:.3e}")
        out[f"{name}_max_abs_err"] = err

    compare("masked_fedavg",
            compiled_call(kops.masked_fedavg, buf, w, mask),
            aggregation.masked_weighted_average(buf, w, mask))
    compare("masked_trimmed_mean",
            compiled_call(kops.masked_trimmed_mean, buf, w, mask),
            aggregation.masked_trimmed_mean(buf, w, mask, 1))
    quantize_rows = jax.jit(jax.vmap(kref.quantize_ref))
    q, s = quantize_rows(buf)
    compare("masked_fedavg_q8",
            compiled_call(kops.masked_fedavg_q8, q, s, w, mask),
            aggregation.masked_fedavg_q8(q, s, w, mask))
    out["tol"] = REF_ULPS * float(np.finfo(np.float32).eps) * scale
    return out


def phase(name: str, cfg, compiles: CompileLog, *, rounds: int, seed: int,
          kernels: bool = False, counters: tuple = (), **fed_kw) -> dict:
    """Run one federation, check it, emit its record, return it."""
    before = compiles.snapshot()
    t0 = time.perf_counter()
    driver, history = run_federation(cfg, rounds=rounds, seed=seed, **fed_kw)
    wall = time.perf_counter() - t0
    ctrl = driver.controller
    rec = {
        "P": int(ctrl.arena.num_params),
        "N": len(ctrl.learner_ids),
        "rounds": len(history),
        "round_s": [h.federation_round_s for h in history],
        "aggregation_s": [h.aggregation_s for h in history],
        "eval_loss": check_losses(history),
        "wall_s": wall,
        **compiles.since(before),
    }
    # The process's peak so far: the federations run up to here, without
    # the kernel checks' extra copies of the arena.
    rec["peak_bytes_in_use"] = peak_bytes()
    rec.update(reference_check(ctrl))
    for counter in counters:
        rec[counter] = ctrl.telemetry.value(counter)
        check(rec[counter] > 0, f"counter {counter} is 0: the path was not taken")
    if kernels:
        mask = ctrl.arena.round_mask(ctrl.learner_ids)
        rec["kernels"] = kernel_checks(ctrl.arena, mask)
        rec["peak_bytes_after_kernels"] = peak_bytes()
    emit(name, **rec)
    return {"driver": driver, "record": rec}


def one_chip(cfg, compiles: CompileLog, seed: int, **fed_kw) -> None:
    a = phase("phase_a", cfg, compiles, rounds=ROUNDS_A, seed=seed,
              kernels=True, **fed_kw)
    del a
    gc.collect()
    phase("phase_b", cfg, compiles, rounds=ROUNDS_B, seed=seed,
          upload_codec="int8", arena_dtype="int8",
          counters=("controller.aggregations.fused_q8",
                    "engine.uploads.quantized_direct"), **fed_kw)


def four_chips(cfg, compiles: CompileLog, seed: int, chips: int,
               **fed_kw) -> None:
    from repro.core import aggregation

    single = phase("single_device", cfg, compiles, rounds=ROUNDS_A, seed=seed,
                   **fed_kw)
    g_single = np.asarray(single["driver"].controller.global_buffer)
    del single
    gc.collect()
    sharded = phase("sharded", cfg, compiles, rounds=ROUNDS_A, seed=seed,
                    arena_shards=chips, **fed_kw)
    ctrl = sharded["driver"].controller
    arena = ctrl.arena
    shards = sorted((s.device.id, s.data.shape) for s in arena.buffer.addressable_shards)
    check(len({d for d, _ in shards}) == chips,
          f"arena buffer on {len(shards)} devices, expected {chips}")
    in_use = memory_stat("bytes_in_use")
    mask = arena.round_mask(ctrl.learner_ids)
    hlo = (aggregation.masked_fedavg_sharded(arena.mesh, arena.axes)
           .lower(arena.buffer, arena.weights, mask).compile().as_text())
    found = [op for op in COLLECTIVES if f" {op}(" in hlo or f"{op}-start" in hlo]
    check(not found, f"sharded reduction holds collectives: {found}")
    g_sharded = np.asarray(ctrl.global_buffer)
    diff = float(np.max(np.abs(g_sharded.astype(np.float64) - g_single)))
    tol = REF_ULPS * float(np.finfo(np.float32).eps) * float(np.max(np.abs(g_single)))
    check(diff <= tol, f"sharded vs single-device global rows: {diff:.3e} > {tol:.3e}")
    emit("sharded_vs_single", max_abs_diff=diff, tol=tol,
         bit_identical=bool(np.array_equal(g_sharded, g_single)),
         arena_shards=[[d, list(shape)] for d, shape in shards],
         bytes_in_use=in_use, collectives=found)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable_compile_cache()
    info = require_tpu(args.chips)
    compiles = CompileLog()
    from repro.configs.fedlm_100m import config as fedlm_config

    cfg = fedlm_config()
    emit("setup", model=cfg.name, n_learners=N_LEARNERS, batch=BATCH,
         seq_len=SEQ_LEN, local_steps=LOCAL_STEPS, lr=LR, seed=args.seed,
         chips=args.chips, compile_cache=cache_dir, device=info,
         bytes_limit=memory_stat("bytes_limit"))
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(cfg, compiles, args.seed, args.chips)
        else:
            one_chip(cfg, compiles, args.seed)
        check("repro.launch.dryrun" not in sys.modules,
              "launch.dryrun was imported")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    emit("total", wall_s=time.perf_counter() - t0, **compiles.snapshot())
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
