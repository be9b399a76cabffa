"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

Nothing here knows a workload: a cell names a configuration and a traffic
mix, the mix names its driver, and each per-layer metric is a JSON entry of
``BENCHMARK.json`` read by ``benchmark/metrics/<name>.py`` (or by the reader
its ``benchmark/metrics/<name>.json`` names).  Adding a cell, a mix, a
configuration or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration and traffic mix."""

    def __init__(self, name: str, man: Dict[str, Any] = None):
        man = man or manifest()
        self.manifest = man
        cells = {w["name"]: w for w in man["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in man["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        self.cache_dir = os.path.join(BENCH, ".cache", name)

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.manifest["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = [m["name"] for m in self.end_to_end()]
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def driver(self):
        return importlib.import_module(
            "benchmark.drivers." + self.traffic["driver"])


def metric_reader(name: str):
    """The reader of one per-layer metric: ``benchmark/metrics/<name>.json``
    may name a shared reader module and its arguments; without that file the
    reader is ``benchmark/metrics/<name>.py``.  Returns (read, args)."""
    spec_path = os.path.join(BENCH, "metrics", name + ".json")
    spec = load_json(spec_path) if os.path.exists(spec_path) else {}
    mod = importlib.import_module(
        "benchmark.metrics." + spec.get("reader", name).replace("-", "_"))
    return mod.read, spec.get("args", {})


def read_per_layer(cell: Cell, evidence: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer():
        read, args = metric_reader(m["name"])
        value = read(evidence, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
