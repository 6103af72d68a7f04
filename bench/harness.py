"""One run of one cell: build, warm up, measure, compare, report.

The federation is the user's: the program's ``Learner``s over the
benchmark's data, ``Driver(FederationEnv(...))``, ``Driver.initialize``, and
``controller.engine.run(rounds=1)`` round after round.  The harness departs
from a user's run in exactly two places, both to keep a window of many
rounds inside the chip's memory (``PERF.md`` says why and what program
change would remove them):

1. the learner adapter drops ``LocalUpdate.params`` once the update carries
   its wire envelope (``Controller.ingest`` reads only the envelope then);
2. ``engine.event_log`` is emptied at each round boundary.

Set-up (``setup_s``) is everything before the window: weights on the device,
learners, the driver, and the warm-up rounds, the first of which compiles.
The first ``reference_rounds`` of them are what the comparison follows.
The window (:func:`measure`) is framed by two snapshots of the program's
``Telemetry`` registry, taken on the host between rounds, which the
per-layer readers read through ``bench/spans.py``.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time

import numpy as np

from bench import check, counts, device, reference, spec, traffic
from bench import trace as trace_mod


def seed32(seed: int) -> int:
    """A JAX key seed drawn from ``seed``, whatever its size."""
    return int(np.random.default_rng(seed % 2**63).integers(0, 2**31 - 1))


def init_params(fam, model, config: dict, traffic: dict, seed: int, source):
    """The program's parameter tree, filled from the seed in one jitted call.

    A family may refuse a draw on which its training would not stay finite
    (``fam.usable(params, config, traffic, source)``); the seed's next key
    then draws again, so a seed always gives the same weights.
    """
    import jax

    abstract = fam.abstract_params(model)
    paths = reference.leaf_paths(abstract)
    leaves, treedef = jax.tree_util.tree_flatten(abstract)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            fam.init_leaf(path, k, leaf.shape, config)
            for path, k, leaf in zip(paths, keys, leaves)
        ])

    key = jax.random.key(seed32(seed))
    params, draw = make(key), 0
    usable = getattr(fam, "usable", None)
    while usable is not None and not usable(params, config, traffic, source):
        draw += 1
        params = make(jax.random.fold_in(key, draw))
    return params


class Spans:
    """Host-clock time per benchmark span; profiler annotations when tracing."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.total: collections.Counter = collections.Counter()
        self.count: collections.Counter = collections.Counter()
        self._lock = threading.Lock()

    def wrap(self, name: str, fn):
        import jax

        def timed(*args, **kwargs):
            ctx = (jax.profiler.TraceAnnotation(f"bench.{name}") if self.annotate
                   else contextlib.nullcontext())
            t0 = time.perf_counter()
            with ctx:
                out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            with self._lock:
                self.total[name] += dt
                self.count[name] += 1
            return out

        return timed

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()

    def mean_s(self, name: str) -> float | None:
        return self.total[name] / self.count[name] if self.count[name] else None


class Federation:
    """The cell's federation, built through the user's entry points."""

    def __init__(self, w: spec.Workload, seed: int, spans: Spans, plant=None):
        from repro.core import Driver, FederationEnv, Learner, TerminationCriteria
        from repro.optim import sgd

        t = w.traffic
        self.w = w
        self.fam = spec.family(w.family)
        self.model = self.fam.program_model(w.config)
        self.source = traffic.make(w.config, t, seed)
        self.n = int(t["learners"])
        self.losses: dict[tuple[int, int], float] = {}
        self.record_losses = True
        loss_fn, eval_fn = self.fam.learner_fns(self.model)
        lr = float(w.config["learning_rate"])
        learners = []
        for i in range(self.n):
            learner = Learner(
                learner_id=f"learner_{i:03d}",
                loss_fn=loss_fn,
                eval_fn=eval_fn,
                data_fn=traffic.LearnerFeed(self.source, i),
                eval_data_fn=lambda i=i: self.source.eval_batch(i),
                optimizer=sgd(lr),
                num_examples=int(t["examples_per_learner"]),
            )
            learner.fit = spans.wrap("fit", self._adapter(learner.fit, i))
            learner.evaluate = spans.wrap("evaluate", learner.evaluate)
            learners.append(learner)
        # The traffic's ``federation`` settings reach the program as they are.
        env = FederationEnv(**t["federation"], learning_rate=lr,
                            termination=TerminationCriteria(max_rounds=2**31))
        self.driver = Driver(env)
        self.params0 = init_params(self.fam, self.model, w.config, t, seed, self.source)
        self.driver.initialize(self.params0, learners)
        c = self.controller = self.driver.controller
        self.engine = c.engine
        c.ingest = spans.wrap("ingest", c.ingest)
        c.aggregate_round = spans.wrap("aggregate", c.aggregate_round)
        c._broadcast = spans.wrap("broadcast", c._broadcast)
        c.channel.recv = spans.wrap("recv", c.channel.recv)
        c.channel.upload = spans.wrap("upload", c.channel.upload)
        if plant is not None:
            plant(self)

    def _adapter(self, fit, index: int):
        def adapted(params, task):
            update = fit(params, task)
            if update.upload is not None:
                # Departure 1: only the envelope leaves the learner.
                update.params = None
            if self.record_losses:
                self.losses[(task.round_id, index)] = float(update.metrics["train_loss"])
            return update

        return adapted

    def round(self):
        """One committed model version; returns its ``RoundTimings`` and tasks."""
        from repro.core.engine import Dispatched, UploadArrived, UploadRejected

        timings = self.engine.run(rounds=1)[0]
        log = self.engine.event_log
        sent = sum(isinstance(e, Dispatched) for e in log)
        landed = sum(isinstance(e, UploadArrived) and e.error is None for e in log)
        rejected = sum(isinstance(e, UploadRejected) for e in log)
        # Departure 2: the log would keep every upload's envelope alive.
        log.clear()
        return timings, sent, sent - landed + rejected

    def readings(self, rounds: int, changes: list) -> reference.Readings:
        return reference.Readings(
            losses=[[self.losses[(r, i)] for i in range(self.n)] for r in range(rounds)],
            changes=changes,
        )

    def close(self) -> None:
        self.driver.shutdown()


def _window(fed: Federation, seconds: float):
    timings, attempted, failed = [], 0, 0
    t0 = time.perf_counter()
    while True:
        t, sent, lost = fed.round()
        timings.append(t)
        attempted += sent
        failed += lost
        if time.perf_counter() - t0 >= seconds:
            break
    return timings, attempted, failed, time.perf_counter() - t0


def reference_readings(w: spec.Workload, seed: int, **kw) -> reference.Readings:
    """The plain federation's first rounds on this seed's weights and data."""
    fam = spec.family(w.family)
    model = fam.program_model(w.config)
    source = traffic.make(w.config, w.traffic, seed)
    params0 = init_params(fam, model, w.config, w.traffic, seed, source)
    return reference.run(spec.reference(w.family), w.config, w.traffic, params0,
                         source, int(w.traffic["reference_rounds"]), **kw)


def configure_jax() -> str:
    """The program's compile cache, with every program kept in it."""
    import jax
    from repro.launch import compile_cache

    cache = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def measure(w: spec.Workload, seed: int, seconds: float, trace: bool, *,
            require_chip: bool = True, plant=None, log=print):
    """Set-up, warm-up and the window of one run of cell ``w``.

    Returns the readers' :class:`Context` and the federation's readings of
    its first rounds.  The program's ``Telemetry`` registry is snapshotted
    on the host right before the window and right after it (``ctx.before``,
    ``ctx.after``); with ``trace`` the window runs under the profiler and
    ``ctx.planes`` / ``ctx.trace`` hold the trace.  The federation is closed
    before this returns, so the reference replay has the chip to itself.
    """
    t_start = time.perf_counter()
    if require_chip:
        configure_jax()
        dev = device.require_tpu(w.chips)
    else:
        dev = device.info()
    compiles = device.CompileLog()
    spans = Spans(annotate=trace)

    fed = Federation(w, seed, spans, plant)
    warmup = int(w.traffic["warmup_rounds"])
    compared = int(w.traffic["reference_rounds"])
    changes = []
    for r in range(warmup):
        fed.round()
        if r < compared:
            changes.append(reference.change_norms(fed.controller.global_params,
                                                  fed.params0))
    fed.record_losses = False
    prog = fed.readings(compared, changes)
    fed.params0 = None
    setup = compiles.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.2f} s, {setup}")

    spans.reset()
    telemetry = fed.controller.telemetry
    tracer = trace_mod.Tracer() if trace else contextlib.nullcontext()
    before = telemetry.snapshot()
    with tracer:
        timings, attempted, failed, window_s = _window(fed, seconds)
    after = telemetry.snapshot()
    in_window = compiles.since(setup)
    dev["memory_peak_bytes"] = device.memory_peak_bytes()
    log(f"window {window_s:.3f} s, {len(timings)} rounds, {in_window}")

    shapes = counts.Shapes.of(fed)
    fed.close()
    del fed
    gc.collect()

    planes = tracer.planes() if trace else None
    ctx = Context(w=w, dev=dev, timings=timings, window_s=window_s, spans=spans,
                  setup_compile=setup, shapes=shapes, before=before, after=after,
                  planes=planes, trace=None if planes is None else trace_mod.reduce(planes),
                  setup_s=setup_s, attempted=attempted, failed=failed,
                  window_compiles=in_window["compiles"])
    return ctx, prog


def run(w: spec.Workload, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, plant=None, log=print) -> dict:
    """One run of cell ``w``; returns the result line's object."""
    ctx, prog = measure(w, seed, seconds, trace, require_chip=require_chip,
                        plant=plant, log=log)
    ctx.planes = None  # reduced into ctx.trace; freed before the replay

    ref = reference_readings(w, seed)
    values = check.numbers(prog, ref)
    ok, shown = check.verdict(values, w.limits)
    ok = ok and ctx.failed == 0

    dev = ctx.dev
    result: dict = {"correct": ok, "attempted": ctx.attempted, "failed": ctx.failed}
    if trace:
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        result["metrics"] = per_layer(ctx)
        result["breakdown"] = ctx.trace.breakdown()
    else:
        result["metrics"] = {
            "round_s": {"value": ctx.window_s / len(ctx.timings), "unit": "s"},
            "setup_s": {"value": ctx.setup_s, "unit": "s"},
        }
    result["device"] = dev
    result["window"] = {"rounds": len(ctx.timings), "seconds": ctx.window_s,
                        "compiles": ctx.window_compiles}
    result["readings"] = values
    result["check"] = shown
    return result


class Context:
    """What a per-layer metric reader may read.

    Besides the window's ``timings``, ``spans``, ``shapes``, the set-up's
    compiles and the trace ``Summary`` (None untraced): ``before`` and
    ``after``, the program's ``Telemetry`` snapshots around the window, read
    with ``bench.spans.window`` (span histograms) and ``bench.spans.delta``
    (counters and gauges).  A reader names the program spans, counters and
    compiled programs it reads in its module's ``SPANS``, ``COUNTERS`` and
    ``PROGRAMS``, which the CPU tests fill with made-up readings.
    """

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def flops(self) -> float:
        """Training and evaluation operations of all learners in the window."""
        t = self.w.traffic
        tasks = len(self.timings) * int(t["learners"])
        per = spec.family(self.w.family).learner_flops(self.w.config, t)
        return tasks * (per["train"] + per["eval"])

    def peak(self, key: str) -> float:
        return float(device.peaks(self.dev["kind"])[key])


def per_layer(ctx: Context) -> dict:
    out = {}
    for m in ctx.w.per_layer:
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
