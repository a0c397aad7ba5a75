"""Device time of the ops under the program's scope ``kda.conv`` (the
three causal depthwise convolutions of a Kimi Delta Attention mixer: the
K-tap filter with its SiLU and the slicing and writing back of the tails,
the last ``d_conv - 1`` inputs a row carries; dynamo_tpu/models/
kimi_linear.py ``_causal_conv``; in the decode window and in prefill) as
a share of the time an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). The small ops of a decode step that no
large read explains sit here (PERF.md section 5, cell 11). A program
without the scope reports nothing."""

from benchmark.harness import scope_ops


def read(raw):
    return scope_ops.path_share(raw, "kda.conv", __file__) or None
