"""Share of the decode row-steps whose query had positions behind its
window (``decode_row_steps_past_window_total`` / ``decode_row_steps_
total``, counted at every decode dispatch from the host's view of each
row): how much of the traffic reaches the mechanism that gives pages
back. A program with one pool reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "decode_row_steps_past_window_total",
                          "decode_row_steps_total", 100.0)
