"""``kda_chunk_roofline`` for ``solar-open2-250b.long-reason``: the
accepted reader itself (least time for the prompt tokens the slice
prefilled, benchmark/harness/kda_work.py ``kda_prefill``, over the
device time under ``kda.scan`` in ``jit(prefill_step)``: the
``kda_chunk`` kernel) at THIS configuration's shapes, 64 heads of 128 x
128 in three KDA layers (benchmark/harness/solar_work.py). A name of its
own because the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_kimi_linear.py."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "kda_chunk_roofline")
