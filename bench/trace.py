"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and programs.

Device activity is read from each TPU plane's ``XLA Modules`` line: one
event per execution of a compiled program, named ``<program>(<id>)``.  Busy
time is the union of those intervals inside the traced window, per device,
averaged over the devices used.  An idle gap is a stretch of the window in
which no program ran on a device; its time is charged to the innermost
benchmark span (``bench.*`` host annotations) open at each moment,
latest-started first, and averaged over the devices as busy time is.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
NO_SPAN = "(no bench span)"
_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    return _ID.sub("", event_name)


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi)`` that the sorted disjoint ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(free, spans) -> collections.Counter:
    """Charge each moment of ``free`` to the latest-started open span.

    ``free`` may hold the gaps of several devices: a moment is charged once
    for each device idle in it.
    """
    events = []
    for s, e in free:
        events += [(s, 1, "gap", None), (e, 0, "gap", None)]
    for i, (name, s, e) in enumerate(spans):
        events += [(s, 1, "span", (i, name, s)), (e, 0, "span", (i, name, s))]
    events.sort(key=lambda x: (x[0], x[1]))
    charged: collections.Counter = collections.Counter()
    open_spans: dict[int, tuple[str, float]] = {}
    in_gap, last = 0, None
    for t, opening, kind, ref in events:
        if last is not None and in_gap and t > last:
            if open_spans:
                name = max(open_spans.values(), key=lambda v: v[1])[0]
            else:
                name = NO_SPAN
            charged[name] += (t - last) * in_gap
        if kind == "gap":
            in_gap += 1 if opening else -1
        elif opening:
            open_spans[ref[0]] = (ref[1], ref[2])
        else:
            open_spans.pop(ref[0], None)
        last = t
    return charged


@dataclasses.dataclass
class Summary:
    """A traced window, reduced.  Times are in seconds."""

    window_s: float
    busy_s: float
    devices: int
    program_s: dict[str, float]
    program_calls: dict[str, int]
    idle_by_span: dict[str, float]

    def program_time(self, *names: str) -> tuple[float, int]:
        """Device seconds and executions of programs whose name holds one of ``names``."""
        secs = calls = 0
        for prog, s in self.program_s.items():
            if any(n in prog for n in names):
                secs += s
                calls += self.program_calls[prog]
        return secs, calls

    def breakdown(self) -> dict:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(planes, span_prefix: str = "bench.") -> Summary:
    """Reduce planes of ``(name, [(line, [(event, start_ns, dur_ns)])])``."""
    window = None
    spans = []
    device_events: dict[str, list] = {}
    for pname, lines in planes:
        if pname.startswith(DEVICE_PREFIX):
            by_line = dict(lines)
            events = by_line.get("XLA Modules")
            if events:
                device_events[pname] = events
            continue
        for _, events in lines:
            for name, start, dur in events:
                if name == WINDOW:
                    window = (start, start + dur)
                elif name.startswith(span_prefix):
                    spans.append((name[len(span_prefix):], start, start + dur))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = window
    busy_total = 0.0
    program_s: collections.Counter = collections.Counter()
    program_calls: collections.Counter = collections.Counter()
    free_all = []
    for events in device_events.values():
        intervals = []
        for name, start, dur in events:
            inside = clip([(start, start + dur)], lo, hi)
            if not inside:
                continue
            intervals += inside
            prog = program_name(name)
            program_s[prog] += (inside[0][1] - inside[0][0]) * 1e-9
            program_calls[prog] += 1
        busy = union(intervals)
        busy_total += sum(e - s for s, e in busy)
        free_all += gaps(busy, lo, hi)
    n_dev = len(device_events)
    idle = attribute(free_all, spans)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / max(n_dev, 1),
        devices=n_dev,
        program_s=dict(program_s),
        program_calls=dict(program_calls),
        idle_by_span={k: v * 1e-9 / max(n_dev, 1) for k, v in idle.items()},
    )


def read_planes(path: str):
    """The planes of an ``.xplane.pb`` as plain tuples."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name, [
            (line.name, [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                         for ev in line.events])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


class Tracer:
    """Profile a window into a temporary directory; its planes are read once, then it is deleted."""

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        self._window.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def planes(self):
        """The traced window's planes (:func:`read_planes`); the files are deleted."""
        try:
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
            if not found:
                raise ValueError("the profiler wrote no .xplane.pb")
            return read_planes(found[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
