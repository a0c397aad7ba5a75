"""First content chunk minus the time the request was DUE, 95th
percentile over the requests of the window (client's clock). A request
that failed or never produced a token is the worst value: the metric is
then left out and the run is judged by ``failed``."""

from benchmark.harness import stats


def read(raw):
    ttft = [stats.ttft_s(r) for r in raw["rows"] if not r["cut"]]
    return stats.finite_ms(stats.pctile(ttft, 0.95))
