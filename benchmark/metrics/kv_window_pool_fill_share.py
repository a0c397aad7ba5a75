"""How much of the window layers' K/V pool the traffic occupied: pages
held by admitted rows over the pages there are, both summed by the
engine at every decode dispatch between the two ``stats()`` reads around
the window (``kv_window_pages_held_total`` / ``kv_window_pages_seen_
total``, as ``state_pool_fill_share``: a gauge cannot be read as a
delta). ``kv_pool_fill_share`` stays the full layers' pool. A program
with one pool reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "kv_window_pages_held_total",
                          "kv_window_pages_seen_total", 100.0)
