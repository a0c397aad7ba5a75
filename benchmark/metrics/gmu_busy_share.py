"""Device time of the ops under the program's scope ``gmu`` (the gated
memory units: two projections around an elementwise gate by the scan
output an earlier Mamba layer handed down; dynamo_tpu/models/phi4flash.py
``_cross_half``) as a share of the time an operation ran on the device,
in the traced slice (benchmark/harness/scope_ops.py). A configuration of
another family and a program without the scope report nothing."""

from benchmark.harness import sambay_work, scope_ops


def read(raw):
    if sambay_work.shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "gmu", __file__) or None
