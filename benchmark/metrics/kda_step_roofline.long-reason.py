"""``kda_step_roofline`` for ``solar-open2-250b.long-reason``: the
accepted reader itself (least time for the decode steps the slice did,
benchmark/harness/kda_work.py ``kda_decode``, over the device time of
the ``kda_step`` kernel's events) at THIS configuration's shapes: 64
heads of 128 x 128 float32 a layer a row, 2 x 4 MiB read and written a
row-step, three KDA layers (benchmark/harness/solar_work.py reads them
from ``gqa_layers``). A name of its own because the accepted entry's
list is pinned to its one cell by tests/benchmark/test_bm_kimi_linear.py."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "kda_step_roofline")
