"""Raw gap between consecutive streamed content chunks, 99th percentile
over all gaps of all requests (client's clock): what a reader of the
stream sees as a stall, not a window-amortised mean."""

from benchmark.harness import stats


def read(raw):
    gaps = [g for r in raw["rows"] for g in stats.gaps_s(r)]
    return stats.finite_ms(stats.pctile(gaps, 0.99))
