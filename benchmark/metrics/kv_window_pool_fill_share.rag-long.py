"""``kv_window_pool_fill_share`` for ``command-a-plus-05-2026.rag-long``:
the accepted reader itself (``kv_window_pages_held_total`` over
``kv_window_pages_seen_total``), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. The window layers' pool here:
32 rows x 73 pages, three layers."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "kv_window_pool_fill_share")
