"""What the readers of ``command-a-plus-05-2026.rag-long`` need of a
``cohere2_moe`` ``config.json`` as it is run: the kinds of the layers
that run (``layer_types`` is kept whole in a file cut in depth: the
first ``num_hidden_layers`` entries are the layers that run), the
window, the heads and the held share, from THIS family's keys.

No operation or byte is counted here. The decode kernel's work in a
window layer and in a full layer is benchmark/harness/
window_attn_work.py's (``attention_decode``, ``kernel_seconds``,
``decode_contexts``, through ``roofline_share``) whatever the family:
``through`` hands an accepted reader the run with this configuration's
layout written under the keys that reader asks for (SmallThinker's
``sliding_window_layout`` / ``sliding_window_size``, which
``window_attn_work.layers_of`` reads; DeepSeek's ``n_shared_experts``,
which ``moe_shared_busy_share`` asks for), so that one place counts a
kernel's work.
"""

from __future__ import annotations

import os
from typing import Optional

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def shapes(config: dict) -> Optional[dict]:
    """Of a ``cohere2_moe`` configuration as it is run: how many of its
    layers are held to the window and how many see everything, the
    window, the heads, and the experts held of those the router scores;
    None for a configuration of another family (no ``layer_types`` of
    these two kinds, no ``use_parallel_block``)."""
    kinds = (config.get("layer_types") or [])[:config.get(
        "num_hidden_layers", 0)]
    if not config.get("use_parallel_block") or not kinds \
            or set(kinds) - {"sliding_attention", "full_attention"}:
        return None
    held = config["num_experts"]
    return {"window": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention"),
            "size": int(config["sliding_window"]),
            "layout": [int(k == "sliding_attention") for k in kinds],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "experts_held": held,
            "router_width": config.get("router_num_experts", held),
            "shared_experts": config.get("num_shared_experts", 0)}


def _accepted_keys(config: dict, found: dict) -> dict:
    """The configuration with its layout also under the keys the
    accepted readers ask for."""
    return {**config, "sliding_window_layout": found["layout"],
            "sliding_window_size": found["size"],
            "n_shared_experts": found["shared_experts"]}


def through(raw: dict, reader: str):
    """What the accepted reader ``reader`` reads of the run ``raw`` of a
    ``cohere2_moe`` configuration (None for any other)."""
    found = shapes(raw["model"]["config"])
    if found is None:
        return None
    model = {**raw["model"],
             "config": _accepted_keys(raw["model"]["config"], found)}
    return cells.load_reader(reader, ROOT)({**raw, "model": model})
