"""Positions the prefill programs ran the cross half of the stack on, as
a share of the positions they ran the self half on, over the window:
``cross_rows_total`` / ``self_rows_total`` (``JaxEngine.stats()``, summed
a prefill dispatch: rows x 1 over rows x the chunk's length where the
module runs the layers that keep nothing a position on each row's last
position alone; dynamo_tpu/models/phi4flash.py). 100 without the
shortcut, 1 / chunk with it. A program without the counters (the
parent's, another family's) reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "cross_rows_total", "self_rows_total", 100.0)
