"""``kda_busy_share`` for ``solar-open2-250b.long-reason``: the accepted
reader itself (device time under the scope ``kda``, which
models/solar_open2.py's KDA layers keep from models/kimi_linear.py, over
busy time), under a name of its own because the accepted entry's list is
pinned to its one cell by tests/benchmark/test_bm_kimi_linear.py and its
reader asks for kimi_linear's keys (benchmark/harness/solar_work.py
writes this configuration's counts under them). Three layers of four are
KDA here."""

from benchmark.harness import solar_work


def read(raw):
    return solar_work.through(raw, "kda_busy_share")
