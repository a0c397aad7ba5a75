"""One run of a benchmark cell that also prints what the engine counted
in the measured window: the same command as benchmark/run.py, the same
engine and traffic, and after the window one more line,

    {"note": "counters", "<key>": <stats()[key] after - before>, ...}

for every whole-number ``*_total`` key of ``JaxEngine.stats()``, and
where warmup() timed its prefill programs their table,

    {"note": "prefill_program_cost_ms", "<PB>x<T>": [ms at one row, at PB]}

The benchmark's result line holds only what its metric readers take; a
counter a PR adds to the program (``moe_grouped_programs_total`` beside
``prefill_dispatches_total``: PERF.md, PR 42; ``prefill_rows_held_back_
total`` and ``prefill_bucket_narrowed_total``: PR 43) is read this way
without an edit under benchmark/.

    chiprun -- python3 tools/bench_with_counters.py \
        --workload qwen3-30b-a3b.decode-heavy --seed 1 --seconds 50 --trace 1

A tool, not an option of the program: the patch lives in this file.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import run

    window = run._window

    async def counted(*args, **kwargs):
        raw = await window(*args, **kwargs)
        s0, s1 = raw["stats0"], raw["stats1"]
        run.note("counters", **{
            k: v - s0[k] for k, v in s1.items()
            if k.endswith("_total") and type(v) is int and k in s0})
        if s1.get("prefill_program_cost_ms"):
            run.note("prefill_program_cost_ms",
                     **s1["prefill_program_cost_ms"])
        return raw

    run._window = counted
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
