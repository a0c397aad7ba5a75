"""The decode kernel's share of its roofline in the WINDOW layers over
the traced slice: the least time a v5e could take to read the pages that
intersect each decoding row's window, K and V once
(benchmark/harness/window_attn_work.py, bound by 819 GB/s), over the
device time of the kernel's events under the scope ``attn.window``. What
the rows did is counted from the clients' rows, as ``paged_attn_roofline``
counts it, so the share errs low by a few percent. A program without the
scope, or a configuration without window layers, reports nothing."""

from benchmark.harness import window_attn_work


def read(raw):
    return window_attn_work.roofline_share(raw, "window", __file__)
