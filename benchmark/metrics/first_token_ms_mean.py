"""Mean time from a request's first prefill dispatch to its first
emission from the engine (``t_first_dispatch`` -> first ``_emit``): the
device queue behind the window in flight, every chunk of the prompt, the
readback that pipelining defers by one iteration, ``process_prefill``."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "first_token_seconds_total",
                          "first_tokens_total", 1000.0)
