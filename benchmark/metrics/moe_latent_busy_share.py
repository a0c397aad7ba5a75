"""Device time of the ops under the program's scope ``moe.latent`` (the
two projections between the hidden width and the width the routed
experts work at, once a token: dynamo_tpu/models/nemotron_h.py
``_moe_ff``; 2 x 4,096 x 1,024 a layer) as a share of the time an
operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A configuration without
``moe_latent_size`` and a program without the scope report nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("moe_latent_size"):
        return None
    return scope_ops.path_share(raw, "moe.latent", __file__) or None
