"""Of the (token, expert) pairs the router chose in the decode windows,
the share whose expert is HELD here, for a configuration that is one
chip's share of a layer's experts (``num_local_experts`` of a wider
router: models/granite.py): ``moe_pairs_held_total`` over
``moe_pairs_routed_total``, counted by the window program itself a live
row-step a layer and summed at read-back, as deltas between the two
``stats()`` reads around the window. The held pairs are the expert work
this chip does; near ``held / router width`` when the router spreads its
choices evenly, and how far from it says how uneven the share's load is.
A program without the counters reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "moe_pairs_held_total",
                          "moe_pairs_routed_total", 100.0)
