"""``kv_window_pool_fill_share`` for
``phi-4-mini-flash-reasoning.long-think``: the accepted reader itself
(``kv_window_pages_held_total`` over ``kv_window_pages_seen_total``),
under a name of its own because the accepted entry's list is pinned to
its one cell by tests/benchmark/test_bm_smallthinker.py. The window
layers' pool here: 48 rows x 17 pages of 64, eight layers."""

from benchmark.harness import sambay_work


def read(raw):
    return sambay_work.through(raw, "kv_window_pool_fill_share")
