"""``paged_attn_roofline`` for a model whose layers are named one by one
(``layer_types``): the accepted reader's share (least time for the
decode attention the slice did over the time of the Pallas decode
kernel's events), with the one layer's count multiplied by the layers
that ATTEND, counted from the configuration as it is run (the first
``num_hidden_layers`` entries of ``layer_types`` that say
``full_attention``), not by ``num_layers``, most of which here are
short-convolution layers and call no attention kernel. Operations and
bytes are those of the published heads (8 KV heads of 64): the program
stores two of them side by side in 128 lanes and the kernel's dot
products are twice as wide, which the count leaves out, so the share is
not flattered. A configuration without ``layer_types`` reports
nothing."""

import os

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def attending_layers(config: dict) -> int:
    kinds = config.get("layer_types") or []
    return sum(1 for k in kinds[:config.get("num_hidden_layers", 0)]
               if k == "full_attention")


def read(raw):
    layers = attending_layers(raw["model"]["config"])
    if not layers:
        return None
    model = {**raw["model"], "num_layers": layers}
    return cells.load_reader("paged_attn_roofline", ROOT)(
        {**raw, "model": model})
