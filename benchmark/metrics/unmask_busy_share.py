"""Device time of the ops under the program's scope ``diffusion.unmask``
(after each denoising forward: the draw and its probability at every
position of the block, the choice of the positions to make final, the
update of the block; after the block: the stop ids, the budget, the
counts) as a share of the time an operation ran on the device, in the
traced slice. The vocabulary-wide sampling over L positions a row lies
here and under ``sample`` both. The path is matched by
benchmark/harness/scope_ops.py. A program without the scope reports
nothing."""

from benchmark.harness import scope_ops


def read(raw):
    if not raw["model"]["config"].get("block_length"):
        return None
    return scope_ops.path_share(raw, "diffusion.unmask", __file__) or None
