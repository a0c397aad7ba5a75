"""``moe_held_pair_share`` for ``kimi-linear-48b-a3b.doc-reason``: the
accepted reader itself (``moe_pairs_held_total`` over
``moe_pairs_routed_total``, which models/kimi_linear.py's window counts
as models/granite.py's does), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_granite.py, which a ``model_config`` PR may not
edit. 64 of the router's 256 experts are held: near 25% when the router
spreads its choices evenly."""

import os

from benchmark.harness import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(raw):
    return cells.load_reader("moe_held_pair_share", ROOT)(raw)
