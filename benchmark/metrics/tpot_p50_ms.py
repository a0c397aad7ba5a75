"""Per request (last chunk - first chunk) / (output tokens after the
first chunk), median over the requests answered in full."""

from benchmark.harness import stats


def read(raw):
    tpot = [t for t in map(stats.tpot_s, raw["rows"]) if t is not None]
    return stats.finite_ms(stats.pctile(tpot, 0.5))
