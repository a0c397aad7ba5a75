"""Device time of the ops under the program's scope ``conv`` (the gated
short-convolution operator whole: the in and out projections under
``conv.proj``, the gate, the K-tap filter and the tail's slice under
``conv.mix``, in the decode window and in prefill) as a share of the
time an operation ran on the device, in the traced slice. The path is
matched by benchmark/harness/scope_ops.py (``host_trace``'s tuple of
scopes is older than this one). A program without the scope
(dynamo_tpu/models/lfm2.py has it) reports nothing."""

from benchmark.harness import scope_ops


def read(raw):
    return scope_ops.path_share(raw, "conv", __file__)
