#!/usr/bin/env python3
"""The program's own spans and counters over a window, and idle by thread.

    python bench/spans.py --workload <cell> --seed <n> --seconds <s>

The harness (``bench/harness.measure``) takes a snapshot of the program's
``Telemetry`` registry (``controller.telemetry``, which the channel and the
registered learners report into) right before its window and right after
it, on the host between rounds.  :func:`window` and :func:`delta` read two
such snapshots; the per-layer readers in ``bench/metrics/`` call them.

The CLI runs one cell's set-up, warm-up and window under the profiler
through that same ``harness.measure`` (no reference replay, no
``correct``) and prints one JSON line:

* ``metrics``: the window's mean of one program span each, in ms (the
  ``Telemetry`` histograms' count and sum at the window's end less those at
  its start), under the names of ``METRICS``;
* ``check``: the program's ``learner.fit`` and ``controller.ingest`` means
  beside the benchmark's ``fit_ms`` and ``ingest_ms``, which time the same
  calls from outside, and the window's ``engine.aggregate`` sum beside the
  sum of ``RoundTimings.aggregation_s``;
* ``idle``: the device's idle time charged, on each host thread apart, to
  the innermost program span open on that thread (:func:`idle_by_thread`),
  and the idle time under no program span on any thread.

The same tables end standard error.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Name -> the program span whose window mean the CLI prints under it.  Each
#: but ``upload_encode_ms`` (``upload_encode_gbps`` reads the same span's
#: sum) is also a per-layer metric, whose reader names its span in ``SPANS``.
METRICS = {
    "downlink_decode_ms": "channel.recv",
    "upload_encode_ms": "channel.upload",
    "local_steps_ms": "learner.steps",
    "screen_sync_ms": "controller.screen",
    "commit_ms": "controller.commit",
    "task_wait_ms": "engine.task.wait",
}

#: Name prefixes of the program's spans (``docs/OBSERVABILITY.md``, "Spans").
PROGRAM = ("engine.", "channel.", "learner.", "controller.")
WORKER = ("learner.", "channel.recv", "channel.upload")
NO_SPAN = "(no program span)"


def window(before: dict, after: dict) -> dict[str, tuple[int, float]]:
    """``(count, seconds)`` of each span observed between two snapshots."""
    out = {}
    for name, h in after.items():
        if not isinstance(h, dict):
            continue
        b = before.get(name) or {"count": 0, "sum": 0.0}
        if h["count"] > b["count"]:
            out[name] = (h["count"] - b["count"], h["sum"] - b["sum"])
    return out


def delta(before: dict, after: dict, name: str) -> float | None:
    """A counter's or gauge's change between two snapshots; None where it is absent."""
    return after[name] - before.get(name, 0) if name in after else None


def mean_ms(spans: dict, name: str) -> float | None:
    """The window's mean of span ``name`` in ms; None where it never ran."""
    if name not in spans:
        return None
    count, secs = spans[name]
    return 1e3 * secs / count


def _role(names: set[str]) -> str:
    if any(n.startswith(WORKER) for n in names):
        return "worker"
    return "loop" if "engine.wait" in names else "other"


def idle_by_thread(planes) -> dict:
    """The device's idle time in the traced window, by thread and program span.

    ``planes`` are ``bench/trace.read_planes``'s tuples.  Each host line is a
    thread; a line that carries ``learner.*``, ``channel.recv`` or
    ``channel.upload`` spans is a worker, the one that carries
    ``engine.wait`` the engine loop.  On each line apart, every idle moment
    of the device goes to the innermost program span open there (or to
    ``NO_SPAN``).  ``no_span_s`` is the idle time in which no line had one
    open.  Seconds are averaged over the devices, as ``busy_s`` is.
    """
    from bench import trace

    lo = hi = None
    free, devices, lines = [], 0, []
    for pname, plane_lines in planes:
        if pname.startswith(trace.DEVICE_PREFIX):
            events = dict(plane_lines).get("XLA Modules")
            if events:
                devices += 1
                free.append([(s, s + d) for _, s, d in events])
            continue
        for _, events in plane_lines:
            spans = []
            for name, start, dur in events:
                if name == trace.WINDOW:
                    lo, hi = start, start + dur
                elif name.startswith(PROGRAM):
                    spans.append((name, start, start + dur))
            if spans:
                # A child that starts with its parent is the inner one: ties
                # go to the span met first, so the shorter one comes first.
                lines.append(sorted(spans, key=lambda x: (x[1], x[2])))
    if lo is None:
        raise ValueError(f"no {trace.WINDOW!r} span in the trace")
    gaps = []
    for intervals in free:
        gaps += trace.gaps(trace.union(trace.clip(intervals, lo, hi)), lo, hi)
    scale = 1e-9 / max(devices, 1)

    def charged(spans) -> dict[str, float]:
        got = trace.attribute(gaps, spans)
        if trace.NO_SPAN in got:
            got[NO_SPAN] = got.pop(trace.NO_SPAN)
        return {k: v * scale for k, v in got.most_common()}

    threads = [{"role": _role({n for n, _, _ in spans}), "idle_s": charged(spans)}
               for spans in lines]
    everywhere = charged([s for spans in lines for s in spans])
    return {
        "idle_s": sum(e - s for s, e in gaps) * scale,
        "no_span_s": everywhere.get(NO_SPAN, 0.0),
        "threads": threads,
    }


def by_role(idle: dict) -> dict[str, dict]:
    """Each role's threads summed: thread-seconds of idle device per span."""
    out: dict[str, collections.Counter] = {}
    for t in idle["threads"]:
        out.setdefault(t["role"], collections.Counter()).update(t["idle_s"])
    return {role: dict(c.most_common()) for role, c in out.items()}


def run(w, seed: int, seconds: float, *, require_chip: bool = True) -> dict:
    """Cell ``w`` built, warmed up and traced over the harness's window."""
    from bench import harness

    ctx, _ = harness.measure(w, seed, seconds, True, require_chip=require_chip,
                             log=lambda m: None)
    deltas = window(ctx.before, ctx.after)
    idle = idle_by_thread(ctx.planes)
    outside = {"fit_ms": ctx.spans.mean_s("fit"), "ingest_ms": ctx.spans.mean_s("ingest")}
    return {
        "workload": w.name, "seed": seed, "device": ctx.dev,
        "rounds": len(ctx.timings), "attempted": ctx.attempted, "failed": ctx.failed,
        "round_s": ctx.window_s / len(ctx.timings),
        "window_s": ctx.trace.window_s, "busy_s": ctx.trace.busy_s,
        "metrics": {k: mean_ms(deltas, v) for k, v in METRICS.items()},
        "check": {
            "learner.fit_ms": mean_ms(deltas, "learner.fit"),
            "fit_ms": None if outside["fit_ms"] is None else 1e3 * outside["fit_ms"],
            "controller.ingest_ms": mean_ms(deltas, "controller.ingest"),
            "ingest_ms": (None if outside["ingest_ms"] is None
                          else 1e3 * outside["ingest_ms"]),
            "engine.aggregate_s": deltas.get("engine.aggregate", (0, 0.0))[1],
            "aggregation_s": sum(t.aggregation_s for t in ctx.timings),
        },
        "spans": {k: list(v) for k, v in sorted(deltas.items())},
        "idle": {"idle_s": idle["idle_s"], "no_span_s": idle["no_span_s"],
                 "threads": len(idle["threads"]), "by_role": by_role(idle)},
        "breakdown": ctx.trace.breakdown(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import device, spec

    try:
        out = run(spec.load(args.workload), args.seed, args.seconds)
    except (spec.SpecError, device.NoChip) as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    idle = out["idle"]
    print(f"spans: idle {idle['idle_s']:.4f} s, under no program span "
          f"{idle['no_span_s']:.4f} s, {idle['threads']} threads", file=sys.stderr)
    for role, table in idle["by_role"].items():
        print(f"spans: idle by {role} span (thread-s): "
              + ", ".join(f"{k} {v:.4f}" for k, v in table.items()), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
