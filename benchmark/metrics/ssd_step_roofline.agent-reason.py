"""``ssd_step_roofline`` for ``nemotron-3-super-120b-a12b.agent-reason``:
the accepted reader itself (least time for the decode steps the slice
did, benchmark/harness/ssd_work.py ``ssd_decode``, over the device time
of the ops under ``ssm.scan`` in ``jit(decode_window)``) at THIS
configuration's shapes: 128 heads of 64 x 128 float32 a layer a row, 2 x
4 MiB read and written a row-step, five Mamba-2 layers
(benchmark/harness/nemotron_work.py reads them from
``hybrid_override_pattern``; B and C of seven of the eight groups are
left out of the floor, 0.04% of it: it errs low). A name of its own
because the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_granite.py."""

from benchmark.harness import nemotron_work


def read(raw):
    return nemotron_work.through(raw, "ssd_step_roofline")
