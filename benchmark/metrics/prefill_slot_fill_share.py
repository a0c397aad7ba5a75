"""Prompt tokens computed over the token slots of the prefill programs
that ran (prefill_tokens_total / prefill_slots_total: PB x T of the
bucket chosen at each dispatch)."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "prefill_tokens_total", "prefill_slots_total",
                          100.0)
