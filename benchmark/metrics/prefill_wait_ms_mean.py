"""Mean time from a request's admission to the first prefill dispatch
that carried a chunk of its prompt (``Sequence.t_admit`` ->
``t_first_dispatch``): admitted, but not yet in a prefill sweep. The
engine's prefill_wait_seconds_total over first_tokens_total, both summed
at each request's first emission."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "prefill_wait_seconds_total",
                          "first_tokens_total", 1000.0)
