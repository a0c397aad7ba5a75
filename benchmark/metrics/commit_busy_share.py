"""Device time of the ops under the program's scope ``diffusion.commit``
(the forward on a block's final tokens that only writes the block's K/V
and yields no token: dynamo_tpu/models/llama.py _make_block_window_fn)
as a share of the time an operation ran on the device, in the traced
slice: what folding that forward into the next block's first denoising
forward would remove. The path is matched by
benchmark/harness/scope_ops.py. A program without the scope reports
nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("block_length"):
        return None
    return scope_ops.path_share(raw, "diffusion.commit", __file__) or None
