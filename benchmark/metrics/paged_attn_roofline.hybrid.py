"""``paged_attn_roofline`` for a model whose layers are of two kinds:
the accepted reader's share (least time for the decode attention the
slice did over the time of the Pallas decode kernel's events), with the
one layer's count multiplied by the layers that ATTEND, read from the
configuration as it is run (``attn_layer_offset`` / ``attn_layer_period``),
not by ``num_layers``, most of which here are Mamba layers and call no
attention kernel."""

import os

from benchmark.harness import cells, ssm_work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(raw):
    config = raw["model"]["config"]
    if not config.get("attn_layer_period"):
        return None
    model = {**raw["model"], "num_layers": ssm_work.attending_layers(config)}
    return cells.load_reader("paged_attn_roofline", ROOT)(
        {**raw, "model": model})
