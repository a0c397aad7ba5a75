"""``moe_held_pair_share`` for
``nemotron-3-super-120b-a12b.agent-reason``: the accepted reader itself
(``moe_pairs_held_total`` over ``moe_pairs_routed_total``, which
models/nemotron_h.py's window counts through ``llama.pairs_counted`` as
models/granite.py's does), under a name of its own because the accepted
entry's list is pinned to its one cell by
tests/benchmark/test_bm_granite.py. 128 of the router's 512 experts are
held: near 25% when the router spreads its 22 choices a token evenly."""

from benchmark.harness import nemotron_work


def read(raw):
    return nemotron_work.through(raw, "moe_held_pair_share")
