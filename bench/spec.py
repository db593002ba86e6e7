"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A configuration is the JSON file its entry names; a traffic mix is
``bench/traffic/<traffic>.json``; a cell's correctness limit is
``bench/limits/<workload>.json``; a per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of them is adding a file and an
entry: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def cell(workload: str, bench_file: str = "") -> Cell:
    spec = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = [w["name"] for w in spec["workloads"]]
        raise SystemExit(f"unknown workload {workload!r}; known: {names}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=load_json(os.path.join(ROOT, conf["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       entry["traffic"] + ".json")),
        limits=load_json(os.path.join(BENCH, "limits", workload + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
