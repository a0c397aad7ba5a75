"""``latent_attn_roofline`` for a model whose layers are of two kinds:
the accepted reader's share (least time for the latent decode attention
the slice did, benchmark/harness/latent_work.py through that reader, over
the time of the latent decode kernel's events), with the one layer's
count multiplied by the layers that ATTEND, read from the configuration
as it is run (``linear_attn_config.full_attn_layers`` up to
``num_hidden_layers``: benchmark/harness/kda_work.py), not by
``num_hidden_layers``, most of which here are KDA layers and call no
attention kernel (the accepted reader would read four times too high at
2 attending layers of 8)."""

import os

from benchmark.harness import cells, kda_work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(raw):
    config = raw["model"]["config"]
    shapes = kda_work.kda_shapes(config)
    if shapes is None:
        return None
    attending = {**config, "num_hidden_layers": shapes["attending"]}
    return cells.load_reader("latent_attn_roofline", ROOT)(
        {**raw, "model": {**raw["model"], "config": attending}})
