"""``window_pages_released_share`` for ``command-a-plus-05-2026.rag-long``:
the accepted reader itself (``kv_window_pages_released_total`` over
``kv_window_pages_allocated_total``), under a name of its own because
the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. 84% of this cell's prompts are
past the window on arrival."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "window_pages_released_share")
