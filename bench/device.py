"""What the run is on: the chip check, device identity, memory, compiles."""

from __future__ import annotations

import collections
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


class NoChip(Exception):
    """JAX found no TPU, too few of them, or Pallas would run interpreted."""


def info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips: int) -> dict:
    """The device identity, if JAX runs on ``chips`` TPUs with Mosaic kernels."""
    from repro.kernels import ops as kops

    found = info()
    if found["platform"] != "tpu":
        raise NoChip(f"no TPU found; JAX runs on {found['platform']!r} "
                     f"({found['kind']})")
    if found["count"] < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees {found['count']}")
    if kops.interpret_mode():
        raise NoChip("Pallas kernels would run in interpret mode")
    return found


def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                       f"known: {sorted(k for k in table if k != '_source')}")
    return table[kind]


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest device, 0 where not reported."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileLog:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

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
            "compiles": self.counts[self.COMPILE],
            "compile_s": self.secs[self.COMPILE],
            "cache_hits": self.counts["/jax/compilation_cache/cache_hits"],
            "cache_misses": self.counts["/jax/compilation_cache/cache_misses"],
        }

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
