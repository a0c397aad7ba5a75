"""First content chunk minus the time the request was DUE, mean over all
requests of the window (client's clock). The mean takes in the whole
tail; of the TTFT statistics it is the one that repeats from run to run
(the p95 of some hundreds of requests swings by 5-25%, PERF.md Findings
PR 23, and stands beside it as a per-layer metric). A request that
failed or never produced a token is the worst value: the metric is then
left out and the run is judged by ``failed``."""

from benchmark.harness import stats


def read(raw):
    ttft = [stats.ttft_s(r) for r in raw["rows"] if not r["cut"]]
    if not ttft:
        return None
    return stats.finite_ms(sum(ttft) / len(ttft))
