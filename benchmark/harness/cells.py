"""Find a cell's data by its name in BENCHMARK.json.

Everything that belongs to one cell, one traffic mix, one configuration
or one metric is a file of its own under ``benchmark/``; this module
only joins them. A later PR adds files and ``BENCHMARK.json`` entries and
edits nothing here:

    workloads/<cell>.json   config, traffic, chips, engine overrides
    traffic/<mix>.json      the generator's parameters
    configs/<name>/         config.json as it is run + about.json
    metrics/<name>.py       one reader: raw material of a run -> number
                            (``<quantity>.<variant>``, the same quantity
                            listed again for cells where it moves another
                            end-to-end metric, is read by
                            ``metrics/<quantity>.py`` unless it has a
                            file of its own)

A configuration's ``about.json`` (beside its ``config.json``) names what
the harness must not assume about a model family:

    "reference"       the file, relative to the repo's root and under
                      ``benchmark/``, whose ``reference_logits(params,
                      cfg, tokens) -> [T, V] float32`` the agreement
                      check compares the engine with. No default.
    "weight_scales"   {leaf name: multiple of what the weight rule
                      gives, or "zeros"} for harness/weights.py; leaves
                      not named take the rule as it is.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
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
    cell["model_config"] = _load(os.path.join(root, config["file"]))
    about = os.path.join(cell["model_path"], "about.json")
    cell["reference_file"], cell["weight_scales"] = _about(about, root)
    cell["traffic_file"] = os.path.join(bdir, "traffic",
                                        cell["traffic"] + ".json")
    cell["traffic_params"] = _load(cell["traffic_file"])
    return cell


def _about(path: str, root: str):
    """(reference file, weight scales) of a configuration's about.json,
    refused here rather than after the engine is built."""
    about = _load(path) if os.path.isfile(path) else {}
    rel = about.get("reference")
    if not isinstance(rel, str):
        raise SystemExit(f"{path}: no \"reference\" key (the file under "
                         f"benchmark/ that has reference_logits)")
    ref = os.path.abspath(os.path.join(root, rel))
    under = os.path.abspath(os.path.join(root, "benchmark")) + os.sep
    if not ref.startswith(under) or not os.path.isfile(ref):
        raise SystemExit(f"{path}: \"reference\" names {rel!r}, which is "
                         f"not a file under benchmark/")
    scales = about.get("weight_scales", {})
    for leaf, scale in scales.items():
        if scale != "zeros" and (isinstance(scale, bool)
                                 or not isinstance(scale, (int, float))):
            raise SystemExit(f"{path}: \"weight_scales\" gives {leaf!r} "
                             f"{scale!r}; a number or \"zeros\"")
    return ref, scales


def engine_overrides(cell: dict) -> dict:
    """The cell's EngineConfig overrides, JSON lists as the tuples the
    dataclass holds."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cell["engine"].items()}


def context_tokens(cell: dict) -> int:
    """The longest sequence the cell's engine takes: its largest page
    bucket in tokens. Engine data that leave ``page_buckets`` or
    ``page_size`` out run the program's defaults, read from
    ``EngineConfig`` (only then is the program imported)."""
    e = cell["engine"]
    if "page_buckets" not in e or "page_size" not in e:
        from dynamo_tpu.engine.jax_engine import EngineConfig

        e = {"page_buckets": EngineConfig.page_buckets,
             "page_size": EngineConfig.page_size, **e}
    return e["page_buckets"][-1] * e["page_size"]


def metrics_in(bench: dict, name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries of a loaded
    BENCHMARK.json that this cell reports: a metric without a
    ``workloads`` key belongs to every cell."""
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def metrics_for(name: str, kind: str, root: str = ROOT) -> list:
    return metrics_in(load_benchmark(root), name, kind)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(metric: str, root: str = ROOT) -> str:
    """``metrics/<metric>.py``; for a variant ``<quantity>.<variant>``
    without a file of its own, the quantity's."""
    mdir = os.path.join(root, "benchmark", "metrics")
    path = os.path.join(mdir, metric + ".py")
    if not os.path.isfile(path) and "." in metric:
        path = os.path.join(mdir, metric.rsplit(".", 1)[0] + ".py")
    return path


def load_reader(metric: str, root: str = ROOT) -> Optional[Callable]:
    """``read(raw) -> number | None`` of the file ``reader_path`` names."""
    return _module(reader_path(metric, root),
                   "benchmark_metric_" + metric).read


def load_reference(cell: dict):
    """The module of the configuration's reference file: it has
    ``reference_logits(params, cfg, tokens) -> [T, V] float32`` and may
    have ``layer(cfg, params, h, l)``, one layer on ``h [T, D]`` float32
    with a traced layer index (rehearse.py compiles it for its memory
    count)."""
    mod = _module(cell["reference_file"],
                  "benchmark_reference_" + cell["config"])
    if not callable(getattr(mod, "reference_logits", None)):
        raise SystemExit(f"{cell['reference_file']}: no reference_logits")
    return mod
