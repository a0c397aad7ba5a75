"""Device time of the ops under the program's scope ``ssm`` (the Mamba
mixers whole: projections, conv and selective scan, in the decode window
and in prefill) as a share of the time an operation ran on the device,
in the traced slice. ``host_trace`` counts these ops as ``unscoped``
(its tuple of scopes is older than this one); the path is matched by
benchmark/harness/scope_ops.py. A program without the scopes reports
nothing."""

from benchmark.harness import scope_ops


def read(raw):
    return scope_ops.path_share(raw, "ssm", __file__)
