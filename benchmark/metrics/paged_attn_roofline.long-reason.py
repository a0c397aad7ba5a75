"""``paged_attn_roofline`` for ``solar-open2-250b.long-reason``: the
accepted reader's share (least time for the decode attention the slice
did over the time of the Pallas decode kernel's events), with the one
layer's count multiplied by the layers that ATTEND, read from the
configuration as it is run (``gqa_layers`` under ``num_hidden_layers``:
benchmark/harness/solar_work.py), not by ``num_layers``, three of four
of which here are KDA layers and call no attention kernel (as
``paged_attn_roofline.hybrid`` does for Jamba's keys)."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "paged_attn_roofline",
                              attending_depth=True)
