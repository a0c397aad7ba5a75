"""``moe_shared_busy_share`` for
``nemotron-3-super-120b-a12b.agent-reason``: the accepted reader itself
(device time under the scope ``moe.shared`` over busy time; the
configuration has the key ``n_shared_experts``, which that reader asks
for: one shared expert at the full width, 4,096 x 5,376, two matrices),
under a name of its own because the accepted entry's list is pinned to
its one cell by tests/benchmark/test_bm_kanana.py."""

from benchmark.harness import nemotron_work


def read(raw):
    return nemotron_work.through(raw, "moe_shared_busy_share")
