"""``window_attn_roofline`` for
``phi-4-mini-flash-reasoning.long-think``: the accepted reader itself
(the least time to read the pages that intersect each decoding row's
window over the decode kernel's time under ``attn.window``:
benchmark/harness/window_attn_work.py), under a name of its own because
the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. This configuration's shapes
(benchmark/harness/sambay_work.py): 8 window layers of 512, 20 KV heads
of 64 under 40 query heads (kept a pair a head, the same bytes); the
accepted count of 4 H hd operations a position is under the
differential form's 6, and the bytes bind either way."""

from benchmark.harness import sambay_work


def read(raw):
    return sambay_work.through(raw, "window_attn_roofline")
