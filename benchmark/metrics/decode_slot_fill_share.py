"""Rows that held a live sequence over the rows of the batch bucket the
decode windows ran in (decode_rows_total / decode_slots_total, both
counted at dispatch, x decode_steps on both sides)."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "decode_rows_total", "decode_slots_total",
                          100.0)
