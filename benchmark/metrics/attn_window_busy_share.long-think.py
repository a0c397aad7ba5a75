"""``attn_window_busy_share`` for
``phi-4-mini-flash-reasoning.long-think``: the accepted reader itself
(device time under the scope ``attn.window`` over busy time), under a
name of its own because the accepted entry's list is pinned to its one
cell by tests/benchmark/test_bm_smallthinker.py. The layout follows
this family's rule (benchmark/harness/sambay_work.py): the odd layers
below L/2 + 1, 8 of 32, a window of 512."""

from benchmark.harness import sambay_work


def read(raw):
    return sambay_work.through(raw, "attn_window_busy_share")
