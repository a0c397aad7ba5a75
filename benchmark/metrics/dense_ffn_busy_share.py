"""Device time of the ops under the program's scope ``mlp`` (the two
dense MLPs of every layer of dynamo_tpu/models/longcat_flash.py: 6,144 ->
12,288 -> 6,144 each, 453 MB of weights a sub-block, read whole a decode
step whatever the rows; the shortcut MoE runs beside them under ``moe``)
as a share of the time an operation ran on the device, in the traced
slice (benchmark/harness/scope_ops.py). A configuration of another
family and a program without the scope report nothing."""

from benchmark.harness import longcat_work, scope_ops


def read(raw):
    if longcat_work.shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "mlp", __file__) or None
