"""``moe_held_pair_share`` for ``solar-open2-250b.long-reason``: the
accepted reader itself (``moe_pairs_held_total`` over
``moe_pairs_routed_total``, which models/solar_open2.py's window counts
as models/granite.py's does), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_granite.py. 40 of the router's 320 experts are
held: near 12.5% when the router spreads its choices evenly."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "moe_held_pair_share")
