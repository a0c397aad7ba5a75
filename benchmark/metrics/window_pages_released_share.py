"""Of the window-pool pages handed to rows inside the measured window,
the share given back WHILE the row ran (behind its window, after a
prefill chunk or a decode window: ``kv_window_pages_released_total``)
and not at its end, over ``kv_window_pages_allocated_total``, as deltas
between the two ``stats()`` reads. Rows that never pass the window give
nothing back. A program with one pool reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "kv_window_pages_released_total",
                          "kv_window_pages_allocated_total", 100.0)
