"""Find a cell's data by its name in BENCHMARK.json.

Everything that belongs to one cell, one traffic mix, one configuration
or one metric is a file of its own under ``benchmark/``; this module
only joins them. A later PR adds files and ``BENCHMARK.json`` entries and
edits nothing here:

    workloads/<cell>.json   config, traffic, chips, engine overrides
    traffic/<mix>.json      the generator's parameters
    configs/<name>/         config.json as it is run + about.json
    metrics/<name>.py       one reader: raw material of a run -> number
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's BENCHMARK.json entry joined with its files."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {[w['name'] for w in bench['workloads']]})")
    bdir = os.path.join(root, "benchmark")
    cell = _load(os.path.join(bdir, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{name}: {key} is {cell[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["name"] = name
    cell["model_path"] = os.path.dirname(os.path.join(root, config["file"]))
    cell["traffic_file"] = os.path.join(bdir, "traffic",
                                        cell["traffic"] + ".json")
    cell["traffic_params"] = _load(cell["traffic_file"])
    return cell


def engine_overrides(cell: dict) -> dict:
    """The cell's EngineConfig overrides, JSON lists as the tuples the
    dataclass holds."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cell["engine"].items()}


def metrics_for(name: str, kind: str, root: str = ROOT) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: a
    metric without a ``workloads`` key belongs to every cell."""
    return [m for m in load_benchmark(root)[kind]
            if name in m.get("workloads", [name])]


def load_reader(metric: str, root: str = ROOT) -> Optional[Callable]:
    """``read(raw) -> number | None`` from ``metrics/<metric>.py``."""
    path = os.path.join(root, "benchmark", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
