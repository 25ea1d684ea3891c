"""A cell of ``BENCHMARK.json`` and the files it names, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``checks/<check>.py`` and ``metrics/<metric>.py``."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def block_shape(self):
        return tuple(self.config["block_shape"])

    @property
    def volume_shape(self):
        return tuple(self.config["volume_shape"])


def load(name: str, bench_path: str = None) -> Cell:
    bench = _json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        per_layer=mine(bench["per_layer"]),
    )


class Context:
    """What checks and metric readers see of a run."""

    def __init__(self, cell: Cell, raw=None, output_path: str = None):
        self.cell = cell
        self.raw = raw
        self.output_path = output_path
        self.volume_shape = cell.volume_shape
        self.block_shape = cell.block_shape
        # filled in by the run
        self.jobs: List[dict] = []
        self.spans: List[dict] = []
        self.counters: Dict[str, float] = {}
        self.trace = None
        self.peaks: Dict[str, float] = {}

    def task_config(self, task: str) -> Dict[str, Any]:
        return self.cell.traffic["task_configs"][task]
