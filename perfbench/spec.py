"""Reads ``BENCHMARK.json`` and finds each cell's files by name.

A cell names a configuration and a traffic mix; a metric names its
reader. Each is found at a fixed place under this directory:

    configs/<config name>.json        the configuration as it is run
    configs/<reference>.py            its plain reference (named inside)
    traffic/<traffic name>.json       the traffic mix
    metrics/<metric name>.py          a reader: read(run) -> float | None
    metrics/<stem>.py                 the reader of <stem>.<suffix> names
                                      that have no file of their own

so a later cell, configuration or metric is new files plus new entries,
with no edit to a file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None         # per-layer metrics only
    workloads: Optional[List[str]] = None


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Benchmark:
    def __init__(self, data: dict, base: str = HERE):
        self.data = data
        self.base = base
        self.cells: Dict[str, Cell] = {
            w["name"]: Cell(w["name"], w["config"], w["traffic"], w["chips"])
            for w in data["workloads"]}
        self.end_to_end = [self._metric(m) for m in data["end_to_end"]]
        self.per_layer = [self._metric(m) for m in data["per_layer"]]

    @staticmethod
    def _metric(m: dict) -> Metric:
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      m.get("moves"), m.get("workloads"))

    @classmethod
    def load(cls, root: str, base: str = HERE) -> "Benchmark":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return cls(json.load(f), base)

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; "
                           f"choose from {sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: Cell) -> dict:
        for c in self.data["configs"]:
            if c["name"] == cell.config:
                with open(os.path.join(os.path.dirname(self.base),
                                       c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"unknown config {cell.config!r}")

    def traffic(self, cell: Cell) -> dict:
        with open(os.path.join(self.base, "traffic",
                               cell.traffic + ".json")) as f:
            return json.load(f)

    def end_to_end_for(self, cell: Cell) -> List[Metric]:
        return [m for m in self.end_to_end
                if m.workloads is None or cell.name in m.workloads]

    def per_layer_for(self, cell: Cell) -> List[Metric]:
        """A per-layer metric is read in the cells it lists; without a
        list, in every cell that reports the end-to-end metric it
        moves."""
        e2e = {m.name for m in self.end_to_end_for(cell)}
        return [m for m in self.per_layer
                if (cell.name in m.workloads if m.workloads is not None
                    else m.moves in e2e)]

    def reader(self, metric: str) -> Callable:
        """``metrics/<name>.py``; a name split by a suffix that has no
        file of its own (``step_ms.serve``) takes its stem's reader
        (``metrics/step_ms.py``)."""
        folder = os.path.join(self.base, "metrics")
        path = os.path.join(folder, metric + ".py")
        stem = metric.rsplit(".", 1)[0]
        if stem != metric and not os.path.exists(path):
            path = os.path.join(folder, stem + ".py")
        return load_module(path).read


def load_module(path: str):
    """Import a file by path (metric names hold dots, so the readers
    are not importable as a package)."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + os.path.basename(path)[:-3].replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict):
    """The plain reference module a configuration names."""
    return load_module(os.path.join(HERE, "configs", cfg["reference"] + ".py"))
