"""``rows_past_window_share`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (``decode_row_steps_past_window_total`` over
``decode_row_steps_total``), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. Near 100% here: the shortest
prompt is half the window and every answer adds 384 tokens or more."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "rows_past_window_share")
