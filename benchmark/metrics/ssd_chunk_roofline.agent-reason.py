"""``ssd_chunk_roofline`` for
``nemotron-3-super-120b-a12b.agent-reason``: the accepted reader itself
(least time for the prompt tokens the slice prefilled,
benchmark/harness/ssd_work.py ``ssd_prefill``, over the device time of
the ops under ``ssm.scan`` in ``jit(prefill_step)``) at THIS
configuration's shapes (benchmark/harness/nemotron_work.py; the chunked
form by groups runs chunks of 128 tokens, one [Q, Q] product a group).
A name of its own because the accepted entry's list is pinned to its one
cell by tests/benchmark/test_bm_granite.py."""

from benchmark.harness import nemotron_work


def read(raw):
    return nemotron_work.through(raw, "ssd_chunk_roofline")
