"""What of the client's mean TTFT the engine does not see: the client's
mean (first content chunk minus the time the request was due, as
``ttft_mean_ms`` computes it) minus the engine's own mean from arrival
in ``generate()`` to the first emission (engine_ttft_seconds_total /
first_tokens_total). Connection, parse, preprocess, the hop to the event
loop, detokenise and the SSE write."""

from benchmark.harness import counters, stats


def read(raw):
    inside = counters.ratio(raw, "engine_ttft_seconds_total",
                            "first_tokens_total", 1000.0)
    ttft = [stats.ttft_s(r) for r in raw["rows"] if not r["cut"]]
    if inside is None or not ttft:
        return None
    total = stats.finite_ms(sum(ttft) / len(ttft))
    return None if total is None else total - inside
