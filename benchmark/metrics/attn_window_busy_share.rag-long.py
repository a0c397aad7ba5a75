"""``attn_window_busy_share`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (device time under the scope ``attn.window`` over
busy time), under a name of its own because the accepted entry's list is
pinned to its one cell by tests/benchmark/test_bm_smallthinker.py. The
layout is read from this family's ``layer_types``
(benchmark/harness/cohere_work.py): three window layers of four."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "attn_window_busy_share")
