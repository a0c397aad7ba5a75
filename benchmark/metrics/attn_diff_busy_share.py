"""Device time of the ops under the program's scope ``attn.diff`` (what
the differential form costs beside the reads: the subtraction of the two
softmaxes' outputs, the RMS norm over the pair;
dynamo_tpu/models/phi4flash.py ``_diff_out``) as a share of the time an
operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A configuration of another family and
a program without the scope report nothing."""

from benchmark.harness import sambay_work, scope_ops


def read(raw):
    if sambay_work.shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "attn.diff", __file__) or None
