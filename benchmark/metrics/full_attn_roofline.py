"""The decode kernel's share of its roofline in the FULL layers (no
window, no positions) over the traced slice: the least time to read each
decoding row's whole context, K and V once, in the layers that see it
(benchmark/harness/window_attn_work.py: ``paged_attn_roofline``'s count
assumes every layer reads the whole context), over the device time of
the kernel's events under the scope ``attn.full``. A program without the
scope, or a configuration without the layout, reports nothing."""

from benchmark.harness import window_attn_work


def read(raw):
    return window_attn_work.roofline_share(raw, "full", __file__)
