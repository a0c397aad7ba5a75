"""Operations and bytes the selective scan of a Mamba-1 layer needs,
from its shapes: what ``ssm_scan_roofline`` sets against the device time
of the ops under the scope ``ssm.scan``. Floors: what any implementation
of the recurrence has to do, so the share they give is never flattered.

Per token and layer the recurrence is, over d_inner x d_state elements,
``s = exp(dt * A) * s + (dt * x) * B`` and ``y = sum_n s * C``: one
exponential, three multiplications, one addition and one
multiply-accumulate = 6 operations an element (``dt * A`` and ``dt * x``
are formed once per channel and shared; ``+ D * x`` is 2 per channel).
"""

from __future__ import annotations

from typing import Tuple

OPS_PER_ELEMENT = 6.0
STATE_ITEMSIZE = 4      # the state is float32 wherever it is stored


def selective_scan_decode(row_steps: int, *, d_inner: int, d_state: int,
                          layers: int, itemsize: int = 2
                          ) -> Tuple[float, float]:
    """(operations, bytes) of ``row_steps`` single-token steps (one row
    advancing one token) through ``layers`` Mamba layers from a STORED
    state. Bytes per row-step and layer: the state is read and written
    once (2 x d_inner x d_state x 4); x and dt are read and y written
    (3 x d_inner) and B and C read (2 x d_state) in the model's type.
    The conv tail is not counted: it moves under ``ssm.conv``."""
    per = d_inner * d_state
    ops = row_steps * layers * (OPS_PER_ELEMENT * per + 2.0 * d_inner)
    bytes_ = row_steps * layers * (
        2.0 * per * STATE_ITEMSIZE
        + (3.0 * d_inner + 2.0 * d_state) * itemsize)
    return ops, bytes_


def selective_scan_prefill(tokens: int, *, d_inner: int, d_state: int,
                           layers: int, itemsize: int = 2
                           ) -> Tuple[float, float]:
    """(operations, bytes) of ``tokens`` prompt tokens through ``layers``
    Mamba layers from a CARRIED state: the same operations a token, and
    of the bytes only the per-token vectors (x, dt, B, C in, y out); the
    state of a chunk can stay on the chip from its first token to its
    last, so its traffic is not part of the floor."""
    per = d_inner * d_state
    ops = tokens * layers * (OPS_PER_ELEMENT * per + 2.0 * d_inner)
    bytes_ = tokens * layers * (3.0 * d_inner + 2.0 * d_state) * itemsize
    return ops, bytes_


def attending_layers(config: dict) -> int:
    """How many layers of a Jamba-family ``config.json`` attend."""
    return sum(
        1 for l in range(config["num_hidden_layers"])
        if (l - config["attn_layer_offset"]) % config["attn_layer_period"]
        == 0)


def mamba_shapes(config: dict) -> dict:
    """d_inner, d_state and the number of Mamba layers of a Jamba-family
    ``config.json`` as it is run."""
    return {"d_inner": config["mamba_expand"] * config["hidden_size"],
            "d_state": config["mamba_d_state"],
            "layers": config["num_hidden_layers"] - attending_layers(config)}
