"""Device time of the ops under the program's scope ``attn.cross`` (the
cross layers' reads of the ONE full layer's K/V: the decode kernel over
its pages, and in a decode window the part over the window's buffer and
the merge; dynamo_tpu/models/phi4flash.py ``_cross_half``) as a share of
the time an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A configuration of another family and
a program without the scope report nothing."""

from benchmark.harness import sambay_work, scope_ops


def read(raw):
    if sambay_work.shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "attn.cross", __file__) or None
