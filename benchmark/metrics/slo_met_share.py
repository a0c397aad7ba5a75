"""Share of the requests sent whose TTFT (from due) met the limit and
whose every chunk gap met the gap limit; the limits are data in the
traffic file (``slo``). A failed request misses."""

from benchmark.harness import stats


def read(raw):
    slo = raw["traffic"].get("slo")
    rows = [r for r in raw["rows"] if not r["cut"]]
    if not slo or not rows:
        return None
    met = sum(1 for r in rows if stats.ok(r)
              and stats.ttft_s(r) * 1000.0 <= slo["ttft_ms"]
              and all(g * 1000.0 <= slo["gap_ms"] for g in stats.gaps_s(r)))
    return 100.0 * met / len(rows)
