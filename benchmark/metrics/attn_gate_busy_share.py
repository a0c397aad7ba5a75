"""Device time of the ops under the program's scope ``attn.gate`` (the
output gate of an attending layer: the product of the layer's normed
input with ``wg``, the sigmoid and the multiply onto attention's output,
dynamo_tpu/models/jamba.py ``_gated``; in the decode window and in
prefill) as a share of the time an operation ran on the device, in the
traced slice (benchmark/harness/scope_ops.py). A configuration without
the gate and a program without the scope report nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("use_gqa_gate"):
        return None
    return scope_ops.path_share(raw, "attn.gate", __file__) or None
