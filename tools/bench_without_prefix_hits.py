"""One run of a benchmark cell with the prefix cache COLD-ONLY: the same
command as benchmark/run.py, the same engine and traffic, but
``PageManager.allocate_sequence`` is handed an empty hash chain here, so
every request misses, prefills its whole prompt and (for a model whose
state is snapshotted by the page) starts from zeros. Pages are still
published; nothing matches them. What a cell reads this way, beside its
normal reading, is what prefix hits buy it (PERF.md, Findings PR 33: the
LFM2 cell, whose hits hand over pages and conv state).

    chiprun -- python3 tools/bench_without_prefix_hits.py \
        --workload lfm2-24b-a2b.agent-loop --seed 1 --seconds 50 --trace 0

A tool, not an option of the program: the patch lives in this file.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from dynamo_tpu.engine.kv_manager import PageManager

    allocate = PageManager.allocate_sequence

    def cold_only(self, token_ids, extra_pages=0, chain=None):
        return allocate(self, token_ids, extra_pages, chain=[])

    PageManager.allocate_sequence = cold_only
    from benchmark import run

    return run.main()


if __name__ == "__main__":
    sys.exit(main())
