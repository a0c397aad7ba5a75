"""Device time of the ops under the program's scope ``moe.router`` (the
router's product, the sigmoid, the top-k of its outputs, 22 of 512 here,
and the one-hot that weighs the dense form: latency-bound work beside
two memory-bound parts) as a share of the time an operation ran on the
device, in the traced slice (benchmark/harness/scope_ops.py). A
configuration without ``moe_latent_size`` (the one family this entry is
listed for) and a program without the scope report nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("moe_latent_size"):
        return None
    return scope_ops.path_share(raw, "moe.router", __file__) or None
