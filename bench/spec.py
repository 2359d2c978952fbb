"""Finds a cell's configuration, traffic mix, model family and per-layer
metric readers by the names in ``BENCHMARK.json``, so that a new cell,
configuration, mix or metric is added as files and entries alone."""
from __future__ import annotations

import importlib
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    pass


def load_benchmark(root: str = ROOT) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json in {root}")
    with open(path) as f:
        return json.load(f)


def cell(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                    f"{[w['name'] for w in bm['workloads']]}")


def config(bm: Dict, name: str, root: str = ROOT) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    with open(os.path.join(BENCH_DIR, "traffic", f"{name}.json")) as f:
        return json.load(f)


def family(cfg: Dict):
    """The model family module a configuration file names."""
    return importlib.import_module(f"bench.families.{cfg['family']}")


def end_to_end(bm: Dict, workload: str) -> List[Dict]:
    return [m for m in bm["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer(bm: Dict, workload: str) -> List[Dict]:
    return [m for m in bm["per_layer"]
            if workload in m.get("workloads", [workload])]


def reader(metric: str):
    """``bench/metrics/<metric>.py``: its ``read(run)`` returns the value,
    or None where the run holds nothing to read."""
    return importlib.import_module(f"bench.metrics.{metric}").read
