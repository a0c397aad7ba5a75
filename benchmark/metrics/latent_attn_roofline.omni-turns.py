"""``latent_attn_roofline`` for ``longcat-flash-omni.omni-turns``: the
accepted reader's share (the least time a v5e could take for the latent
decode attention the slice did, benchmark/harness/latent_work.py through
that reader, over the device time of the latent decode kernel's events),
with the one pool entry's count multiplied by the SUB-BLOCKS that attend,
2 x ``num_layers`` (benchmark/harness/longcat_work.py: a layer of
dynamo_tpu/models/longcat_flash.py calls the kernel twice), where the
accepted reader multiplies by ``num_hidden_layers``, a key this family's
published config does not have. At 64 heads a cached byte takes 121
operations, half the chip's ridge of 240 (kanana's 32 heads: 60), so the
floor is still the pages' read. A configuration of another family and a
program without the kernel report nothing."""

import os

from benchmark.harness import cells, longcat_work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(raw):
    config = raw["model"]["config"]
    found = longcat_work.shapes(config)
    if found is None:
        return None
    attending = {**config, "num_hidden_layers": found["sub_blocks"]}
    return cells.load_reader("latent_attn_roofline", ROOT)(
        {**raw, "model": {**raw["model"], "config": attending}})
