"""cost_diff: before/after diff of two ``--prof-sample`` BENCH reports.

The hot-path evidence format (docs/hot_path.md): every decode hot-path
change quotes a per-bucket ``dispatch_us`` / ``device_us`` delta from the
profiler cost table, plus the headline client-visible metrics riding the
same record. This tool turns two ``bench.py --report-out`` JSON files
into that quote::

    python -m tools.cost_diff before.json after.json

Accepts either a full BENCH-shaped record (``detail.bucket_cost``) or a
bare ``{"bucket_cost": {...}}`` / ``{bucket: {...}}`` mapping, so it also
diffs the ``bench_results/*.json`` reports of the A/B invocations listed
in ROADMAP A6.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

# headline scalars quoted alongside the table when both reports carry them
HEADLINE_KEYS = (
    "itl_raw_chunk_p99_ms",
    "itl_p99_ms",
    "ttft_p50_ms",
    "ttft_p95_ms",
    "loop_lag_p99_ms",
    "output_tok_per_s",
    "post_warmup_compiles",
)

# dynaheat cache counter family (bench.py --scenario shared flat keys):
# realized hit rates, the allocation prefix split, restore-pipeline cost,
# and the eviction fate split — so a cache A/B quote is one command over
# the two arms' --report-out files
CACHE_KEYS = (
    "prefix_hit_rate",
    "hit_rate_windowed",
    "device_hit_blocks",
    "host_restored_blocks",
    "fresh_blocks",
    "restore_wait_ms",
    "restore_batch_pages_mean",
    "evict_offloaded_total",
    "evict_dropped_total",
    "host_evictions_total",
)


def _bucket_cost(report: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    detail = report.get("detail")
    if isinstance(detail, dict) and isinstance(detail.get("bucket_cost"),
                                               dict):
        return detail["bucket_cost"]
    if isinstance(report.get("bucket_cost"), dict):
        return report["bucket_cost"]
    # bare mapping: every value already looks like a bucket row
    if report and all(isinstance(v, dict) and ("dispatch_us" in v
                                               or "device_us" in v)
                      for v in report.values()):
        return report
    return {}


def _detail(report: Dict[str, Any]) -> Dict[str, Any]:
    d = report.get("detail")
    return d if isinstance(d, dict) else report


def diff_reports(before: Dict[str, Any],
                 after: Dict[str, Any]) -> Dict[str, Any]:
    """Structured diff: per-bucket dispatch/device deltas + headline
    scalars. Buckets present on only one side keep ``None`` for the
    missing side (bucket shapes can legitimately change across an
    overhaul — e.g. longer decode windows rename ``decode_window:BxKxP``
    keys)."""
    b_cost, a_cost = _bucket_cost(before), _bucket_cost(after)
    buckets: List[Dict[str, Any]] = []
    for key in sorted(set(b_cost) | set(a_cost)):
        b, a = b_cost.get(key), a_cost.get(key)
        row: Dict[str, Any] = {"bucket": key}
        for col in ("dispatch_us", "device_us"):
            bv = None if b is None else b.get(col)
            av = None if a is None else a.get(col)
            row[f"{col}_before"] = bv
            row[f"{col}_after"] = av
            row[f"{col}_delta"] = (av - bv if bv is not None
                                   and av is not None else None)
        row["samples_before"] = None if b is None else b.get("samples")
        row["samples_after"] = None if a is None else a.get("samples")
        buckets.append(row)
    b_det, a_det = _detail(before), _detail(after)

    def _scalar_family(keys) -> Dict[str, Dict[str, Optional[float]]]:
        fam: Dict[str, Dict[str, Optional[float]]] = {}
        for key in keys:
            bv, av = b_det.get(key), a_det.get(key)
            if bv is None and av is None:
                continue
            fam[key] = {
                "before": bv, "after": av,
                "delta": (av - bv if isinstance(bv, (int, float))
                          and isinstance(av, (int, float)) else None),
            }
        return fam

    return {"buckets": buckets,
            "headline": _scalar_family(HEADLINE_KEYS),
            "cache": _scalar_family(CACHE_KEYS)}


def _fmt(v: Optional[float], unit: str = "") -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        # sub-1 magnitudes are rates/ratios — one decimal would erase
        # the whole signal (0.2433 → "0.2")
        return (f"{v:.3f}{unit}" if abs(v) < 1 else f"{v:.1f}{unit}")
    return f"{v}{unit}"


def format_table(diff: Dict[str, Any]) -> str:
    lines = []
    if diff["buckets"]:
        head = (f"{'bucket':<28} {'dispatch_us':>24} {'Δdisp':>9} "
                f"{'device_us':>22} {'Δdev':>9} {'samples':>9}")
        lines.append(head)
        lines.append("-" * len(head))
    for row in diff["buckets"]:
        disp = (f"{_fmt(row['dispatch_us_before']):>11} →"
                f"{_fmt(row['dispatch_us_after']):>11}")
        dev = (f"{_fmt(row['device_us_before']):>10} →"
               f"{_fmt(row['device_us_after']):>10}")
        samp = (f"{_fmt(row['samples_before'])}/"
                f"{_fmt(row['samples_after'])}")
        lines.append(f"{row['bucket']:<28} {disp:>24} "
                     f"{_fmt(row['dispatch_us_delta']):>9} {dev:>22} "
                     f"{_fmt(row['device_us_delta']):>9} {samp:>9}")
    if diff["headline"]:
        lines.append("")
        for key, h in diff["headline"].items():
            lines.append(f"{key:<24} {_fmt(h['before'])} → "
                         f"{_fmt(h['after'])}"
                         + (f"  (Δ {_fmt(h['delta'])})"
                            if h["delta"] is not None else ""))
    if diff.get("cache"):
        lines.append("")
        lines.append("cache (dynaheat)")
        lines.append("-" * 16)
        for key, h in diff["cache"].items():
            lines.append(f"{key:<24} {_fmt(h['before'])} → "
                         f"{_fmt(h['after'])}"
                         + (f"  (Δ {_fmt(h['delta'])})"
                            if h["delta"] is not None else ""))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if len(argv) != 2:
        print("usage: python -m tools.cost_diff [--json] "
              "before.json after.json", file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        before = json.load(f)
    with open(argv[1]) as f:
        after = json.load(f)
    diff = diff_reports(before, after)
    if not diff["buckets"] and not diff["cache"] and not diff["headline"]:
        print("no bucket cost table, headline, or cache counters in "
              "either report (run bench.py with --prof-sample N, or "
              "--scenario shared for the cache family)", file=sys.stderr)
        return 1
    if as_json:
        print(json.dumps(diff, indent=2))
    else:
        print(format_table(diff))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
