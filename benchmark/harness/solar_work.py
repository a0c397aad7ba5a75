"""What the readers of ``solar-open2-250b.long-reason`` need of a
``solar_open2`` ``config.json`` as it is run: its KDA heads and head
size and how many of its layers are KDA and how many attend, from THIS
family's keys (``gqa_layers`` counts from 0 and is kept whole in a file
cut in depth: the entries under ``num_hidden_layers`` are the layers
that attend, every other layer is KDA).

No operation or byte is counted here. A KDA kernel's work is
benchmark/harness/kda_work.py's and the GQA decode kernel's
benchmark/harness/roofline.py's, whatever the family, through the
accepted readers: ``through`` hands an accepted reader the run with this
configuration's layer counts written under the keys that reader asks
for (kimi_linear's two 1-based lists, which ``kda_work.kda_shapes``
reads), so that one place counts a kernel's work.
"""

from __future__ import annotations

import os
from typing import Optional

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _attending(config: dict) -> list:
    """The layers that attend, counted from 0, of those that run."""
    depth = config["num_hidden_layers"]
    return sorted({l for l in config["gqa_layers"] if 0 <= l < depth})


def shapes(config: dict) -> Optional[dict]:
    """heads, head_dim, the number of KDA layers and of attending layers
    of a ``solar_open2`` configuration as it is run; None for a
    configuration without ``gqa_layers`` or KDA heads."""
    lin = config.get("linear_attn_config")
    if not lin or "gqa_layers" not in config:
        return None
    attending = len(_attending(config))
    return {"heads": lin["num_heads"], "head_dim": lin["head_dim"],
            "layers": config["num_hidden_layers"] - attending,
            "attending": attending}


def _kimi_keys(config: dict) -> dict:
    """The configuration with its layer kinds also under kimi_linear's
    keys: ``linear_attn_config.kda_layers`` / ``full_attn_layers``,
    counted from 1."""
    gqa = [l + 1 for l in _attending(config)]
    lin = dict(config["linear_attn_config"], full_attn_layers=gqa,
               kda_layers=[l for l in range(1, config["num_hidden_layers"] + 1)
                           if l not in gqa])
    return {**config, "linear_attn_config": lin}


def through(raw: dict, reader: str, attending_depth: bool = False):
    """What the accepted reader ``reader`` reads of the run ``raw`` of a
    ``solar_open2`` configuration (None for any other): the KDA readers
    find the layer counts under kimi_linear's keys; with
    ``attending_depth`` the model's depth is the layers that ATTEND, for
    a reader that multiplies one layer's attention by ``num_layers``."""
    found = shapes(raw["model"]["config"])
    if found is None:
        return None
    model = {**raw["model"], "config": _kimi_keys(raw["model"]["config"])}
    if attending_depth:
        model["num_layers"] = found["attending"]
    return cells.load_reader(reader, ROOT)({**raw, "model": model})
