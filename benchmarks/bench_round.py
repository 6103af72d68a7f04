"""Table 2 reproduction: federation round time (secs) for the 10M-param model
across federation sizes, MetisFL-arm vs naive-arm — plus the dispatch-scaling
arm (``--dispatch``) and the wire-aware semi-sync sizing arm (``--schedule``).

Paper Table 2 (10M params): MetisFL 4.58/6.10/14.13/21.28/45.61 s for
10/25/50/100/200 learners vs e.g. IBM FL 175->1915 s.  Our two arms
reproduce the *shape* of that comparison on this host; EXPERIMENTS.md
compares the scaling exponents.

``--dispatch`` measures the serialize-once broadcast claim: per-round train
*dispatch* wall time must stay ~flat in federation size N (the global model
is serialized once per round and fanned out as shared envelopes — O(P + N)),
against the legacy per-send arm that re-serializes per learner (O(N·P)).
Defaults follow the acceptance shape: N ∈ {8, 32, 128} at P = 2^23 (≥ 2^22).

``--schedule`` measures the wire-cost-aware semi-sync sizing claim: under a
bandwidth cap, the hyper-period budget must cover *train + round-trip wire*
time.  The naive arm (``wire_aware=False``) sizes tasks from train time only
and overshoots the hyper-period by roughly the wire time; the wire-aware arm
(default) subtracts each learner's modeled round-trip (broadcast down +
upload payload up, ``Controller.wire_time_s``) and stays within budget.
"""

from __future__ import annotations

import argparse
import json
import time


def run(learner_counts=(10, 25, 50), size="10m", include_naive=True):
    from benchmarks.bench_ops import _metis_round, _naive_round

    rows = []
    for n in learner_counts:
        m = _metis_round(size, n)
        rows.append({"bench": "round", "size": size, "learners": n,
                     "arm": "metis", "federation_round_s": m["federation_round_s"]})
        print(f"round,metis,{size},{n},{m['federation_round_s']:.3f}s", flush=True)
        if include_naive:
            nv = _naive_round(size, n)
            rows.append({"bench": "round", "size": size, "learners": n,
                         "arm": "naive",
                         "federation_round_s": nv["federation_round_s"]})
            print(f"round,naive,{size},{n},{nv['federation_round_s']:.3f}s",
                  flush=True)
    return rows


# ---------------------------------------------------------------------------
# dispatch-scaling arm
# ---------------------------------------------------------------------------


def _make_null_learner(lid, upload_buffer):
    """A learner that trains instantly and uploads a pre-packed flat buffer.

    Isolates the *dispatch* path: the round still runs the full engine
    machinery (broadcast, recv, UploadArrived ingest + arena write,
    aggregation, eval fan-out) but no local SGD, so ``train_dispatch_s`` is
    measured under realistic envelope traffic without minutes of training
    per round.
    """
    from repro.core import EvalReport, Learner, LocalUpdate
    from repro.optim import sgd

    class _NullLearner(Learner):
        def fit(self, params, task):
            return LocalUpdate(
                learner_id=self.learner_id, round_id=task.round_id,
                params=None, num_examples=1, metrics={}, seconds_per_step=0.0,
                buffer=upload_buffer,
            )

        def evaluate(self, params, round_id):
            return EvalReport(self.learner_id, round_id,
                              {"eval_loss": 0.0}, 1)

    dummy = lambda *a, **k: None  # noqa: E731 - never called by _NullLearner
    return _NullLearner(lid, dummy, dummy, dummy, dummy, sgd(0.1), 1)


def run_dispatch(learner_counts=(8, 32, 128), p=1 << 23, rounds=3,
                 include_persend=True):
    """Per-round train-dispatch wall time vs federation size N.

    The wire cache is invalidated before every measured round (as if the
    model had just been re-published), so each dispatch pays its one
    serialization inside the timed region — the worst case; in steady state
    that single serialization is shared with the previous round's eval
    fan-out.  Median over ``rounds`` engine rounds: the completion side
    (N recvs + N arena writes) runs concurrently with the next
    measurement's setup and adds noise on small hosts.  The ``persend`` arm
    is the legacy cost: one full serialization per learner.
    """
    import jax.numpy as jnp

    from repro.core import Channel, Controller, SyncProtocol

    rows = []
    base = None
    for n in learner_counts:
        ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=1),
                          arena_n_max=n)
        params = {"w": jnp.zeros((p,), jnp.float32)}
        ctrl.set_initial_model(params)
        upload = jnp.zeros((ctrl.arena.padded_params,), jnp.float32)
        for i in range(n):
            ctrl.register_learner(_make_null_learner(f"l{i}", upload))

        def one_dispatch():
            ctrl.invalidate_wire_cache()  # model re-published: cold cache
            return ctrl.engine.run(rounds=1)[0].train_dispatch_s

        one_dispatch()  # warmup: compiles recv/arena-write programs
        dispatch = sorted(one_dispatch() for _ in range(rounds))
        dispatch_s = dispatch[len(dispatch) // 2]
        serialized = ctrl.telemetry.value("channel.serializations")
        assert ctrl.telemetry.value("controller.upload_fallback_packs") == 0, \
            "flat upload path not engaged"
        ctrl.shutdown()

        persend_s = None
        if include_persend:
            ch = Channel()
            t0 = time.perf_counter()
            for _ in range(n):
                ch.send(params)
            persend_s = time.perf_counter() - t0

        row = {"bench": "dispatch", "params": p, "learners": n,
               "dispatch_s": dispatch_s, "persend_s": persend_s,
               "serializations_total": serialized}
        if base is None:
            base = dispatch_s
        row["ratio_vs_smallest_n"] = dispatch_s / base
        rows.append(row)
        persend_txt = f",persend={persend_s*1e3:.1f}ms" if persend_s else ""
        print(f"dispatch,P={p},N={n},dispatch={dispatch_s*1e3:.2f}ms"
              f"{persend_txt},ratio={row['ratio_vs_smallest_n']:.2f}x",
              flush=True)
    flat = rows[-1]["dispatch_s"] / rows[0]["dispatch_s"]
    note = ("<=1.5x expected at this payload: serialize-once"
            if p >= 1 << 22 else
            "smoke payload: fan-out overhead dominates; the <=1.5x "
            "flatness claim holds at P>=2^22")
    print(f"dispatch flatness: {flat:.2f}x from N={learner_counts[0]} to "
          f"N={learner_counts[-1]} ({note})", flush=True)
    return rows


# ---------------------------------------------------------------------------
# flight-recorder overhead arm
# ---------------------------------------------------------------------------


def run_journal(p=1 << 20, n=8, rounds=12):
    """Flight-recorder overhead: journaled rounds vs recording disabled.

    Two identical null-learner federations run the same engine rounds; the
    baseline disables recording entirely (``journal_capacity=0`` — the
    ``record()`` early-exit), the journal arm keeps the default ring *and*
    streams JSONL to a file sink (the worst case: serialization work plus a
    background flusher competing for the GIL).  Reported overhead is the
    median per-round delta; the acceptance target is < 2%.  The journal
    arm's row also embeds the run's telemetry snapshot and journal/replay
    accounting — the artifact shape the nightly CI archives.
    """
    import os
    import tempfile

    import jax.numpy as jnp

    from repro.core import Controller, SyncProtocol

    def build(journal_capacity, journal_sink):
        ctrl = Controller(protocol=SyncProtocol(local_steps=1, batch_size=1),
                          arena_n_max=n, journal_capacity=journal_capacity,
                          journal_sink=journal_sink)
        ctrl.set_initial_model({"w": jnp.zeros((p,), jnp.float32)})
        upload = jnp.zeros((ctrl.arena.padded_params,), jnp.float32)
        for i in range(n):
            ctrl.register_learner(_make_null_learner(f"l{i}", upload))
        return ctrl

    def median_round_s(ctrl):
        ctrl.engine.run(rounds=2)  # warmup: compiles recv/arena-write/agg
        t = sorted(r.federation_round_s for r in ctrl.engine.run(rounds=rounds))
        return t[len(t) // 2]

    with tempfile.TemporaryDirectory() as tmp:
        base = build(0, None)
        base_s = median_round_s(base)
        assert len(base.journal.records()) == 0, "baseline journal not disabled"
        base.shutdown()

        sink = os.path.join(tmp, "journal.jsonl")
        ctrl = build(4096, sink)
        journal_s = median_round_s(ctrl)
        snapshot = ctrl.telemetry.snapshot()
        summaries = ctrl.journal.replay()
        cursor = ctrl.journal.cursor
        ctrl.shutdown()
        sink_records = len(ctrl.journal.read_jsonl(sink))

    overhead_pct = 100.0 * (journal_s - base_s) / max(base_s, 1e-12)
    row = {"bench": "journal", "params": p, "learners": n, "rounds": rounds,
           "baseline_round_s": base_s, "journal_round_s": journal_s,
           "overhead_pct": overhead_pct,
           "journal_records": cursor, "sink_records": sink_records,
           "rounds_replayed": len([s for s in summaries if s.aggregated]),
           "telemetry": snapshot}
    print(f"journal,P={p},N={n},base={base_s*1e3:.2f}ms,"
          f"journaled={journal_s*1e3:.2f}ms,overhead={overhead_pct:+.2f}%,"
          f"records={cursor},sink={sink_records}", flush=True)
    assert sink_records == cursor, "flush-on-stop lost records"
    return [row]


# ---------------------------------------------------------------------------
# fault-injecting stress arm
# ---------------------------------------------------------------------------


def run_stress_arm(learners=1000, rounds=5, fault_seed=7, protocols=None):
    """Thousand-learner churn sweep: every protocol under injected faults.

    Drives ``tests/stress/harness.run_stress`` — a SimLearner fleet on the
    real engine/transport/journal with seeded dropout/rejoin churn, upload
    loss + duplication, heavy-tailed stragglers, and per-learner bandwidth
    caps — once per protocol, and reports uploads/sec, rounds/sec, the
    staleness histogram, and every ``engine.faults.*`` counter as JSON
    rows.  The same ``--fault-seed`` reproduces the identical run
    (byte-identical journal JSONL; ``tests/stress/test_stress.py`` pins
    that contract on small fleets).
    """
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tests"))
    from stress.harness import STRESS_PROTOCOLS, run_stress

    from repro.core import FaultSpec

    spec = FaultSpec(
        seed=fault_seed, dropout_rate=0.05, rejoin_rate=0.5,
        upload_loss_rate=0.02, upload_dup_rate=0.02, straggler_rate=0.1,
        bandwidth_min_gbps=0.05, bandwidth_max_gbps=10.0,
    )
    rows = []
    for name in (protocols or STRESS_PROTOCOLS):
        row = run_stress(protocol=name, learners=learners, rounds=rounds,
                         spec=spec)
        row["bench"] = "stress"
        rows.append(row)
        f = row["faults"]
        print(f"stress,{name},N={learners},rounds={rounds},"
              f"uploads={row['uploads']},"
              f"uploads_per_s={row['uploads_per_s']:.0f},"
              f"rounds_per_s={row['rounds_per_s']:.2f},"
              f"dropouts={f['dropouts']},rejoins={f['rejoins']},"
              f"lost={f['uploads_lost']},dup={f['uploads_duplicated']},"
              f"orphaned={f['orphaned']}", flush=True)
    return rows


def run_adversarial_arm(learners=1000, rounds=3, fault_seed=7,
                        adversarial_fraction=0.15):
    """Byzantine sweep (``--stress --adversarial-fraction``): rule shoot-out.

    Four sync-protocol arms on a ``value_mode="target"`` SimLearner fleet —
    a faultless FedAvg baseline, then FedAvg / coordinate median / trimmed
    mean under ``adversarial_fraction`` scale + sign-flip adversaries
    (admission screen and quarantine on).  Each row carries the per-fate
    ``adversarial`` counters, the ``admission`` block (rejected / clipped /
    quarantined) and ``final_eval_loss`` against the consensus target, so
    the nightly artifact tracks the headline claim directly: the robust
    rules stay at the baseline's epsilon while FedAvg diverges.
    """
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tests"))
    from stress.harness import run_stress

    from repro.core import FaultSpec

    spec = FaultSpec(seed=fault_seed,
                     adversarial_fraction=adversarial_fraction)
    # Trim deep enough to cover the adversarial minority with headroom,
    # while keeping 2 * trim_k strictly below the fleet size.
    trim_k = max(1, min(int(learners * adversarial_fraction * 1.5),
                        (learners - 1) // 2))
    arms = [
        ("faultless_fedavg", None, "fedavg", 1),
        ("fedavg", spec, "fedavg", 1),
        ("median", spec, "median", 1),
        ("trimmed_mean", spec, "trimmed_mean", trim_k),
    ]
    rows = []
    for arm, arm_spec, rule, tk in arms:
        row = run_stress(protocol="sync", learners=learners, rounds=rounds,
                         spec=arm_spec, aggregation_rule=rule, trim_k=tk,
                         value_mode="target")
        row["bench"] = "adversarial"
        row["arm"] = arm
        row["adversarial_fraction"] = (
            0.0 if arm_spec is None else adversarial_fraction
        )
        rows.append(row)
        adv = row["adversarial"]
        adm = row["admission"]
        print(f"adversarial,{arm},N={learners},rounds={rounds},"
              f"loss={row['final_eval_loss']:.3e},"
              f"scale={adv['scale']},sign_flip={adv['sign_flip']},"
              f"clipped={adm['clipped']},"
              f"quarantined={adm['quarantine_entered']},"
              f"uploads_per_s={row['uploads_per_s']:.0f}", flush=True)
    base = rows[0]["final_eval_loss"]
    fed = rows[1]["final_eval_loss"]
    tm = rows[3]["final_eval_loss"]
    print(f"adversarial headline: baseline={base:.3e}, "
          f"fedavg-under-attack={fed:.3e} "
          f"({fed / max(base, 1e-12):.1e}x worse), "
          f"trimmed_mean-under-attack={tm:.3e} (tracks baseline)",
          flush=True)
    return rows


# ---------------------------------------------------------------------------
# wire-aware semi-sync sizing arm
# ---------------------------------------------------------------------------


def run_schedule(p=1 << 22, n=8, hyperperiod_s=0.5, bandwidth_gbps=1.0,
                 latency_ms=2.0, sps_range=(2e-4, 2e-3)):
    """Wire-aware vs naive semi-sync task sizing under a bandwidth cap.

    Builds a bandwidth-capped controller, seeds ``n`` synthetic learner
    profiles spanning ``sps_range`` seconds-per-step, and sizes each
    learner's task through the real policy + wire model
    (``SemiSyncProtocol.size_task`` fed by ``Controller.wire_time_s`` —
    exactly what the engine's dispatch does).  The modeled round wall-clock
    is the slowest learner's ``steps * sps + round_trip_wire``; wire time is
    virtual by design (the channel never sleeps), so the modeled time *is*
    the round time a bandwidth-capped deployment would see.  The wire-aware
    arm must stay within the hyper-period; the naive arm overshoots by
    roughly the wire time.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Channel, Controller, LearnerProfile, SemiSyncProtocol

    sps = np.geomspace(sps_range[0], sps_range[1], n)
    rows = []
    for arm, wire_aware in (("wire_aware", True), ("naive", False)):
        ctrl = Controller(
            protocol=SemiSyncProtocol(hyperperiod_s=hyperperiod_s,
                                      wire_aware=wire_aware),
            channel=Channel(bandwidth_gbps=bandwidth_gbps,
                            latency_ms=latency_ms),
        )
        ctrl.set_initial_model({"w": jnp.zeros((p,), jnp.float32)})
        round_s = 0.0
        max_steps = 0
        wire_s = 0.0
        for i, s in enumerate(sps):
            lid = f"l{i}"
            prof = LearnerProfile()
            prof.observe_step_time(float(s))
            ctrl._learner_profiles[lid] = prof
            wire_s = ctrl.wire_time_s(lid)
            task = ctrl.protocol.size_task(1, prof, wire_s=wire_s)
            completion_s = task.local_steps * float(s) + wire_s
            round_s = max(round_s, completion_s)
            max_steps = max(max_steps, task.local_steps)
        ctrl.shutdown()
        row = {"bench": "schedule", "arm": arm, "params": p, "learners": n,
               "hyperperiod_s": hyperperiod_s,
               "bandwidth_gbps": bandwidth_gbps,
               "round_trip_wire_s": wire_s,
               "modeled_round_s": round_s,
               "budget_ratio": round_s / hyperperiod_s,
               "within_budget": bool(round_s <= hyperperiod_s),
               "max_steps": max_steps}
        rows.append(row)
        print(f"schedule,{arm},P={p},N={n},bw={bandwidth_gbps}Gbps,"
              f"wire={wire_s*1e3:.1f}ms,round={round_s*1e3:.1f}ms,"
              f"budget={hyperperiod_s*1e3:.0f}ms,"
              f"ratio={row['budget_ratio']:.2f}x,"
              f"within={row['within_budget']}", flush=True)
    aware, naive = rows[0], rows[1]
    print(f"schedule: wire-aware {aware['budget_ratio']:.2f}x of budget "
          f"(within={aware['within_budget']}), naive "
          f"{naive['budget_ratio']:.2f}x (within={naive['within_budget']})",
          flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dispatch", action="store_true",
                    help="train-dispatch scaling vs N (serialize-once claim)")
    ap.add_argument("--schedule", action="store_true",
                    help="bandwidth-capped semi-sync sizing: wire-aware vs naive")
    ap.add_argument("--journal", action="store_true",
                    help="flight-recorder overhead: journaled vs disabled")
    ap.add_argument("--stress", action="store_true",
                    help="1000-learner fault-injecting churn sweep, "
                         "every protocol")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="stress-arm fault seed (same seed => identical run)")
    ap.add_argument("--adversarial-fraction", type=float, default=0.0,
                    help="with --stress: byzantine rule shoot-out (faultless"
                         " / fedavg / median / trimmed_mean) at this "
                         "adversary rate instead of the churn sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="dump result rows as JSON")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.dispatch:
        if args.smoke:
            rows = run_dispatch(learner_counts=(4, 8, 16), p=1 << 16, rounds=1)
        else:
            rows = run_dispatch()
    elif args.journal:
        if args.smoke:
            rows = run_journal(p=1 << 16, n=4, rounds=6)
        else:
            rows = run_journal()
    elif args.stress:
        if args.adversarial_fraction > 0:
            if args.smoke:
                rows = run_adversarial_arm(
                    learners=64, rounds=2, fault_seed=args.fault_seed,
                    adversarial_fraction=args.adversarial_fraction)
            else:
                rows = run_adversarial_arm(
                    fault_seed=args.fault_seed,
                    adversarial_fraction=args.adversarial_fraction)
        elif args.smoke:
            rows = run_stress_arm(learners=64, rounds=2,
                                  fault_seed=args.fault_seed)
        else:
            rows = run_stress_arm(fault_seed=args.fault_seed)
    elif args.schedule:
        if args.smoke:
            rows = run_schedule(p=1 << 16, n=4, bandwidth_gbps=0.02)
        else:
            rows = run_schedule()
    else:
        rows = run(learner_counts=(10, 25) if args.smoke else (10, 25, 50))

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {len(rows)} rows to {args.json}", flush=True)
    return rows


if __name__ == "__main__":
    main()
