"""Device time of the ops under the program's scope ``kda`` (the Kimi
Delta Attention mixers whole: projections, the three convolutions, the
gates, the delta rule's step kernel or chunked form, the gated norm; in
the decode window and in prefill) as a share of the time an operation
ran on the device, in the traced slice. The path is matched by
benchmark/harness/scope_ops.py. A program without the scopes reports
nothing."""

from benchmark.harness import kda_work, scope_ops


def read(raw):
    if kda_work.kda_shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "kda", __file__)
