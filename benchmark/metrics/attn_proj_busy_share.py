"""Device time of the ops under the program's scope ``attn.proj`` (the
q, k, v and o products of a layer's attention, prefill and decode alike:
dynamo_tpu/models/llama.py ``_qkv`` and ``_attn_out`` on the by-kind
path; 285 MB of weights a layer at 128 query heads of 128) as a share of
the time an operation ran on the device, in the traced slice
(benchmark/harness/scope_ops.py). A configuration of another family and
a program without the scope report nothing."""

from benchmark.harness import cohere_work, scope_ops


def read(raw):
    if cohere_work.shapes(raw["model"]["config"]) is None:
        return None
    return scope_ops.path_share(raw, "attn.proj", __file__) or None
