"""Device time of the ops under the program's scope ``attn.latent`` (the
latent decode kernel, the XLA arm that reads the pool in prefill, the
part over the program's own tokens and the merge of the two:
dynamo_tpu/models/mla.py) as a share of the time an operation ran on the
device, in the traced slice (benchmark/harness/scope_ops.py:
``host_trace.SCOPES`` does not list the scope). A program without the
scope reports nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("kv_lora_rank"):
        return None
    share = scope_ops.path_share(raw, "attn.latent", __file__)
    return share or None
