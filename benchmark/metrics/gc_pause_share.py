"""Seconds inside a garbage collection, as ``gc.callbacks`` reports them
(``gc_pause_seconds_total``: a collection holds the GIL, so every thread
of the hot path waits for it), over the wall time between the two
``stats()`` reads."""

from benchmark.harness import counters, host_counters


def read(raw):
    return host_counters.share_of_wall(
        raw, counters.delta(raw, "gc_pause_seconds_total"))
