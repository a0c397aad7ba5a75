"""Mean time from the HTTP handler's entry to ``Sequence.arrival`` (the
stamp ``engine_ttft_seconds_total`` starts from): body read and parse,
validation, chat template, tokenise, the first (role) chunk. With
``gen_late``, the engine's TTFT and ``emit_to_wire`` of first chunks it
makes up the client's TTFT (``intake_seconds_total`` /
``intake_total``)."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "intake_seconds_total", "intake_total",
                          1000.0)
