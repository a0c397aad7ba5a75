"""How late the load generator sent a request (sent - due), 95th
percentile: a starved generator must not read as a fast server."""

from benchmark.harness import stats


def read(raw):
    late = [x for x in map(stats.late_s, raw["rows"]) if x is not None]
    return stats.finite_ms(stats.pctile(late, 0.95))
