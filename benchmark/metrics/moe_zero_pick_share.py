"""The share of the router's picks that went to an identity
(zero-computation) expert, over the window: ``moe_pairs_identity_total``
over ``moe_pairs_routed_total``, which the decode window of
dynamo_tpu/models/longcat_flash.py counts itself a live row-step a layer
(``llama.pairs_counted``) and ``JaxEngine.stats()`` sums. Such a pair
costs no expert's arithmetic and no weight's read: the token itself
times its gate weight (scope ``moe.zero``). Uniform routing over 512
real + 256 identity outputs reads 33.3%; the published average of 8
real pairs of 12 is the same third. A program without the counter
reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "moe_pairs_identity_total",
                          "moe_pairs_routed_total", 100.0)
