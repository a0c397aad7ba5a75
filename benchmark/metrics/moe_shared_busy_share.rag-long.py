"""``moe_shared_busy_share`` for ``command-a-plus-05-2026.rag-long``: the
accepted reader itself (device time under the scope ``moe.shared`` over
busy time), under a name of its own because the accepted entry's list is
pinned to its one cell by tests/benchmark/test_bm_kanana.py. That reader
asks for DeepSeek's key ``n_shared_experts``; this family's is
``num_shared_experts`` (benchmark/harness/cohere_work.py hands it over)."""

from benchmark.harness import cohere_work


def read(raw):
    return cohere_work.through(raw, "moe_shared_busy_share")
