"""Mean device duration of one execution of the prefill program in the
traced slice."""

from benchmark.harness import trace


def read(raw):
    m = raw["trace"] and trace.module_stats(raw["trace"],
                                            trace.PREFILL_MODULE)
    return 1000.0 * m["mean_s"] if m else None
