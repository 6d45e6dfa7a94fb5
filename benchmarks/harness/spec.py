"""Resolve one cell of BENCHMARK.json to its files: everything is found by name."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def load_module(path: str):
    """Import one file by path (names under the benchmark may hold `-` and `.`)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def bench_module(kind: str, name: str):
    """The file `benchmarks/<kind>/<name>.py`, imported."""
    return load_module(os.path.join(BENCH, kind, name + ".py"))


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of `workloads`, with the files its names lead to."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def module(self, kind: str, name: str):
        return bench_module(kind, name)


def _in_cell(metric: Dict[str, Any], cell: str, default: bool) -> bool:
    listed = metric.get("workloads")
    return default if listed is None else cell in listed


def load_cell(workload: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, workload, True)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _in_cell(m, workload, m["moves"] in reported)]
    return Cell(workload, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, layer)
