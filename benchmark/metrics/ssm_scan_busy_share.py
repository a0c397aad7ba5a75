"""Device time of the ops under the program's scope ``ssm.scan`` (the
recurrence alone: the state's update and the read-out, one token a step
in the decode window, a loop over time in prefill) as a share of the
time an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A program without the scopes reports
nothing."""

from benchmark.harness import scope_ops


def read(raw):
    return scope_ops.path_share(raw, "ssm.scan", __file__)
