"""``window_attn_roofline`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (the least time to read the pages that intersect
each decoding row's window over the decode kernel's time under
``attn.window``: benchmark/harness/window_attn_work.py), under a name of
its own because the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. This configuration's shapes: 8
KV heads of 128 under 128 query heads, 3 window layers of 4,096."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "window_attn_roofline")
