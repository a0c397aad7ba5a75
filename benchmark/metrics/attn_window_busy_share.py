"""Device time of the ops under the program's scope ``attn.window`` (the
window layers' attention call, prefill and decode alike: the kernels on
the window layers' pool, and in a decode window the part over the
window's own buffer and the merge; dynamo_tpu/models/llama.py
``_forward_by_kind``, ``_window_family_by_kind``) as a share of the time
an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A program without the scope reports
nothing."""

from benchmark.harness import scope_ops, window_attn_work


def read(raw):
    if window_attn_work.layers_of(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "attn.window", __file__) or None
