"""Mean device duration of one execution of the fused decode-window
program (decode_steps steps) in the traced slice."""

from benchmark.harness import trace


def read(raw):
    m = raw["trace"] and trace.module_stats(raw["trace"],
                                            trace.WINDOW_MODULE)
    return 1000.0 * m["mean_s"] if m else None
