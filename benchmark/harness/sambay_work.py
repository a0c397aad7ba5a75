"""What the readers of ``phi-4-mini-flash-reasoning.long-think`` need of a
``phi4flash`` ``config.json`` as it is run (the decoder-hybrid-decoder:
dynamo_tpu/models/phi4flash.py): the kinds of its layers by the family's
rule, its shapes, and the one count no other family has: the decode
reads of ONE layer's K/V pages by several layers.

**The shared K/V read.** Layer ``L/2 + 1`` is the only layer that sees
the whole context, and it and every cross layer after it (``L/4 - 1`` of
them) read ITS pages: ``readers`` = L/4 layers a decode step, one after
the other (each needs the layer before it), so a page counts once a
READING layer and no implementation can read it fewer times. A row of
context n reads, a reading layer, the pages of the pool that hold
``[0, n - in_buffer)`` (the last ``decode_steps`` positions may wait in
the window program's buffer, which XLA reads, not the kernel), K and V
once each, whole pages; q is read and the output written. Differential
attention over a position is two query heads' scores against one key
each (2 x 2 hd) and two weighted sums of a value pair (2 x 2 x 2 hd) a
query pair: 6 H hd operations a position. A floor: softmax arithmetic,
statistics, the subtraction and the page table are left out.

The window layers' and the scan's work are counted where every family's
is (benchmark/harness/window_attn_work.py, ssm_work.py): ``through``
hands an accepted reader the run with this configuration's layout under
the keys that reader asks for, as benchmark/harness/cohere_work.py does.
"""

from __future__ import annotations

import os
from typing import Iterable, Optional, Tuple

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kinds(layers: int) -> list:
    """Each layer's mixer by the family's rule."""
    half = layers // 2
    return [("mamba" if l <= half else "gmu") if l % 2 == 0 else
            ("window" if l < half + 1 else "full" if l == half + 1
             else "cross") for l in range(layers)]


def shapes(config: dict) -> Optional[dict]:
    """Of a ``phi4flash`` configuration as it is run; None for any
    other."""
    if config.get("model_type") != "phi4flash":
        return None
    of = kinds(config["num_hidden_layers"])
    heads = config["num_attention_heads"]
    return {"kinds": of, "mamba": of.count("mamba"),
            "window": of.count("window"), "cross": of.count("cross"),
            "gmu": of.count("gmu"),
            # the layers that read the ONE full layer's pages a step
            "readers": 1 + of.count("cross"),
            "size": int(config["sliding_window"]), "heads": heads,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // heads,
            "d_inner": config.get("mamba_expand", 2) * config["hidden_size"],
            "d_state": config.get("mamba_d_state", 16)}


def shared_kv_decode(contexts: Iterable[int], *, readers: int,
                     in_buffer: int, num_heads: int, num_kv_heads: int,
                     head_dim: int, page_size: int, itemsize: int = 2
                     ) -> Tuple[float, float]:
    """(operations, bytes) of the decode reads of the full layer's pages
    by ``readers`` layers over rows whose contexts (tokens attended, the
    new one included) are given (the module's docstring)."""
    ops = bytes_ = 0.0
    for n in contexts:
        hi = max(n - in_buffer, 0)
        pages = -(-hi // page_size)
        ops += 6.0 * num_heads * head_dim * hi
        bytes_ += (2.0 * pages * page_size * num_kv_heads * head_dim
                   + 3.0 * num_heads * head_dim) * itemsize
    return readers * ops, readers * bytes_


def _accepted_keys(config: dict, found: dict) -> dict:
    """The configuration with its window layout also under the keys
    benchmark/harness/window_attn_work.py ``layers_of`` asks for."""
    return {**config,
            "sliding_window_layout": [int(k == "window")
                                      for k in found["kinds"]],
            "sliding_window_size": found["size"]}


def through(raw: dict, reader: str):
    """What the accepted reader ``reader`` reads of the run ``raw`` of a
    ``phi4flash`` configuration (None for any other)."""
    found = shapes(raw["model"]["config"])
    if found is None:
        return None
    model = {**raw["model"],
             "config": _accepted_keys(raw["model"]["config"], found)}
    return cells.load_reader(reader, ROOT)({**raw, "model": model})
