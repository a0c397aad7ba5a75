"""Output tokens that arrived at the clients inside the window, over the
window: all the work and all the time, whether or not a request ended
inside it (a count of finished requests only would move in steps of one
request's length)."""

from benchmark.harness import stats


def read(raw):
    n = stats.tokens_in_window(raw["rows"], raw["window_s"])
    return n / raw["window_s"] if n else None
