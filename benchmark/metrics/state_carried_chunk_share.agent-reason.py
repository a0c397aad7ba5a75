"""``state_carried_chunk_share`` for
``nemotron-3-super-120b-a12b.agent-reason``: the accepted reader itself
(``prefill_row_chunks_carried_total`` over ``prefill_row_chunks_total``),
under a name of its own because the accepted entry's list is pinned to
its one cell by tests/benchmark/test_bm_kimi_linear.py. 81% of this
traffic's prompts are longer than one chunk of 512 tokens."""

from benchmark.harness import nemotron_work


def read(raw):
    return nemotron_work.through(raw, "state_carried_chunk_share")
