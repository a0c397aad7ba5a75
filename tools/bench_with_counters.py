"""One run of a benchmark cell that also prints what the engine counted
in the measured window: the same command as benchmark/run.py, the same
engine and traffic, and after the window one more line,

    {"note": "counters", "<key>": <stats()[key] after - before>, ...}

for every whole-number ``*_total`` key of ``JaxEngine.stats()``, and
where warmup() timed its prefill programs their table,

    {"note": "prefill_program_cost_ms", "<PB>x<T>": [ms at one row, at PB]}

and, of a program with the set-up ledger (runtime/profiling.py
SetupLedger), what ``setup_s`` was made of,

    {"note": "setup", "spans": [[name, start_s, seconds, depth], ...],
     "stages": {span: {stage: seconds}}, "programs": {...},
     "warm_forms": [...]}

the spans in order on run.py's clock (seconds since ``T_START``) with the
gaps between the depth-0 spans as rows of their own (``(gap)``: what the
harness does there, or what the program does not bracket), the jit stages
charged to each span, the ten costliest programs and the ten costliest
forms of the warm grid.

The benchmark's result line holds only what its metric readers take; a
counter a PR adds to the program (``moe_grouped_programs_total`` beside
``prefill_dispatches_total``: PERF.md, PR 42; ``prefill_rows_held_back_
total`` and ``prefill_bucket_narrowed_total``: PR 43; ``moe_grouped_
window_forwards_total`` beside ``diffusion_forwards_total`` or
``decode_rows_total``: PR 66) is read this way
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


def setup_table(stats: dict, t_start: float, t_open: float) -> dict:
    """The "setup" note's fields from one ``stats()``: times since
    ``t_start``, a ``(gap)`` row wherever no depth-0 span covers."""
    rows, edge = [], t_start
    for name, start, end, depth in stats["setup_spans"]:
        if depth == 0:
            if start - edge > 0.05:
                rows.append(["(gap)", edge - t_start, start - edge, 0])
            edge = max(edge, end)
        rows.append([name, start - t_start, end - start, depth])
    rows.append(["(gap)", edge - t_start, t_open - edge, 0])

    def cost(row: dict) -> float:
        return sum(row[s] for s in stats["jit_stage_seconds_total"])

    programs = stats["jit_program_seconds"]
    return {
        "spans": [[n, round(a, 3), round(b, 3), d] for n, a, b, d in rows],
        "stages": stats["setup_span_jit_seconds"],
        "totals": stats["jit_stage_seconds_total"],
        "cache": [stats["compile_cache_hits_total"],
                  stats["compile_cache_misses_total"]],
        "programs": {n: programs[n] for n in
                     sorted(programs, key=lambda n: -cost(programs[n]))[:10]},
        "warm_forms": sorted(stats["warmup_programs"],
                             key=lambda r: -r["seconds"])[:10]}


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
        if "setup_spans" in s0:
            run.note("setup", **setup_table(s0, run.T_START, raw["t_open"]))
        return raw

    run._window = counted
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
