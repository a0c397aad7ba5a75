"""``moe_shared_busy_share`` for ``solar-open2-250b.long-reason``: the
accepted reader itself (device time under the scope ``moe.shared`` over
busy time; the configuration has DeepSeek's key ``n_shared_experts``,
which that reader asks for), under a name of its own because the
accepted entry's list is pinned to its one cell by
tests/benchmark/test_bm_kanana.py."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "moe_shared_busy_share")
