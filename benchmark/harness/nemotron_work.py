"""What the readers of ``nemotron-3-super-120b-a12b.agent-reason`` need
of a ``nemotron_h`` ``config.json`` as it is run: its Mamba-2 heads, head
size, state size and how many of its layers are Mamba-2, from THIS
family's keys (``mamba_num_heads``, ``mamba_head_dim``,
``ssm_state_size``; the first ``num_hidden_layers`` characters of
``hybrid_override_pattern`` are the layers that run, ``M`` the Mamba-2
ones).

No operation or byte is counted here. The Mamba-2 recurrence's work is
benchmark/harness/ssd_work.py's, whatever the family, through the
accepted readers: ``through`` hands an accepted reader the run with this
configuration's shapes written under the keys that reader asks for
(granite's: ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``layer_types``), so that one place counts the kernel's work.

The groups. This configuration has 8 groups of B and C (``n_groups``): a
row-step's B and C are 2 x 8 x 128 elements, where ``ssd_work._vectors``
counts 2 x 128, ONE group's. The floor therefore leaves out 2 x 7 x 128 x
2 B = 3.5 KB of the 8 MiB a row-step a layer moves (0.04%): it errs LOW,
a share of it cannot pass 100% for that, and nothing is counted twice to
make it up.
"""

from __future__ import annotations

import os
from typing import Optional

from benchmark.harness import cells, ssd_work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def _granite_keys(config: dict) -> dict:
    """The configuration with its Mamba-2 shapes and layer kinds also
    under granitemoehybrid's keys, which ``ssd_work.mamba2_shapes``
    reads."""
    run = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return {**config, "mamba_n_heads": config["mamba_num_heads"],
            "mamba_d_head": config["mamba_head_dim"],
            "mamba_d_state": config["ssm_state_size"],
            "layer_types": [KINDS.get(k, k) for k in run]}


def shapes(config: dict) -> Optional[dict]:
    """heads, head_dim, d_state and the number of Mamba-2 layers of a
    ``nemotron_h`` configuration as it is run (``ssd_work.mamba2_shapes``
    of it under granite's keys); None for a configuration without
    ``hybrid_override_pattern`` or Mamba-2 heads."""
    if not config.get("mamba_num_heads") \
            or "hybrid_override_pattern" not in config:
        return None
    return ssd_work.mamba2_shapes(_granite_keys(config))


def through(raw: dict, reader: str):
    """What the accepted reader ``reader`` reads of the run ``raw`` of a
    ``nemotron_h`` configuration (None for any other)."""
    if shapes(raw["model"]["config"]) is None:
        return None
    model = {**raw["model"],
             "config": _granite_keys(raw["model"]["config"])}
    return cells.load_reader(reader, ROOT)({**raw, "model": model})
