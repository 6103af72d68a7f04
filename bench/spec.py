"""Resolve a workload of ``BENCHMARK.json`` to the files that define it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


# ``FederationEnv``'s defaults for the fields the benchmark reads itself.
FEDERATION_DEFAULTS = {
    "protocol": "sync",
    "local_steps": 1,
    "batch_size": 100,
    "upload_codec": "raw",
    "aggregation_rule": "fedavg",
    "server_optimizer": "fedavg",
    "server_lr": 1.0,
}


class SpecError(Exception):
    """The benchmark's files do not define the requested workload."""


@dataclasses.dataclass(frozen=True)
class Workload:
    """One cell: a configuration under a traffic mix, with its metrics."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]

    @property
    def family(self) -> str:
        return self.config["family"]


def federation(traffic: dict) -> dict:
    """A traffic mix's ``federation`` settings over the program's defaults."""
    return {**FEDERATION_DEFAULTS, **traffic["federation"]}


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(name: str, root: pathlib.Path = ROOT) -> Workload:
    """The workload ``name`` as ``BENCHMARK.json`` under ``root`` defines it."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[cell["config"]]["file"])
    traffic = _read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = _read_json(BENCH / "limits" / f"{name}.json")
    return Workload(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def _module(path: pathlib.Path, name: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def part(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py``: one named part of the benchmark."""
    ident = name.replace(".", "_").replace("-", "_")
    return _module(BENCH / kind / f"{name}.py", f"bench_{kind}_{ident}")


def family(name: str) -> ModuleType:
    """``bench/configs/<name>.py``: builds the program's side of a family."""
    return part("configs", name)


def reference(name: str) -> ModuleType:
    """``bench/configs/<name>_reference.py``: the family's plain reference."""
    return part("configs", f"{name}_reference")


def metric_reader(name: str) -> ModuleType:
    """``bench/metrics/<name>.py``: the reader of one per-layer metric."""
    return part("metrics", name)
