"""``full_attn_roofline`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (the least time to read each decoding row's whole
context in the layers that see it over the decode kernel's time under
``attn.full``: benchmark/harness/window_attn_work.py), under a name of
its own because the accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_smallthinker.py. This configuration's shapes: 8
KV heads of 128 under 128 query heads, 1 full layer."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "full_attn_roofline")
