"""Device time of the ops under the program's scope ``moe.shared`` (the
shared experts every token passes through, beside the routed ones:
dynamo_tpu/models/mla.py ``_deepseek_moe_mlp``) as a share of the time
an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A configuration without shared experts
and a program without the scope report nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("n_shared_experts"):
        return None
    share = scope_ops.path_share(raw, "moe.shared", __file__)
    return share or None
