"""``moe_held_pair_share`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (``moe_pairs_held_total`` over
``moe_pairs_routed_total``, which the by-kind window of models/llama.py
counts as models/granite.py's does), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_granite.py. 16 of the router's 128 experts are
held: near 12.5% when the router spreads its choices evenly."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "moe_held_pair_share")
