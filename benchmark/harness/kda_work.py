"""Operations and bytes the gated delta rule of a KDA layer needs, from
its shapes: what ``kda_step_roofline`` (the decode window's step kernel)
and ``kda_chunk_roofline`` (prefill's chunked form) set against the
device time of what ran it. Floors from the recurrence itself, whatever
implements it, so the share they give is never flattered; this file is
the only place that counts them.

Per token and layer, over heads x d_k x d_v elements of state:
``S' = exp(g) S`` (1), ``S'^T k`` (a multiply-accumulate: 2), ``S = S' +
k d^T`` (2) and ``o = S^T q`` (2) = 7 operations an element; the
exponentials are one a key channel and the delta ``beta (v - S'^T k)`` 2
a value channel. The chunked form prefill runs does other arithmetic (two
[Q, Q] tables a head whose every entry sums d_k decayed products, a
triangular solve, three products with the state a chunk) to do the same
recurrence: its share is of this floor all the same.
"""

from __future__ import annotations

from typing import Optional, Tuple

OPS_PER_ELEMENT = 7.0
STATE_ITEMSIZE = 4      # the state is float32 wherever it is stored


def _vectors(heads: int, head_dim: int) -> float:
    """Elements of a token's vectors: q, k and the decay (heads x d_k
    each), v in and o out (heads x d_v each), beta (a head)."""
    return 5.0 * heads * head_dim + heads


def _ops(heads: int, head_dim: int) -> float:
    return (OPS_PER_ELEMENT * heads * head_dim * head_dim
            + 3.0 * heads * head_dim)


def kda_decode(row_steps: float, *, heads: int, head_dim: int, layers: int,
               itemsize: int = 2, **_) -> Tuple[float, float]:
    """(operations, bytes) of ``row_steps`` single-token steps (one row
    advancing one token) through ``layers`` KDA layers from a STORED
    state: the state is read and written once a row-step a layer (2 x
    heads x d_k x d_v x 4: 4 MiB at 32 x 128 x 128), the token's vectors
    move in the model's type. The conv tails are not counted: they move
    under ``kda.conv``."""
    bytes_ = row_steps * layers * (
        2.0 * heads * head_dim * head_dim * STATE_ITEMSIZE
        + _vectors(heads, head_dim) * itemsize)
    return row_steps * layers * _ops(heads, head_dim), bytes_


def kda_prefill(tokens: float, *, heads: int, head_dim: int, layers: int,
                itemsize: int = 2, **_) -> Tuple[float, float]:
    """(operations, bytes) of ``tokens`` prompt tokens through ``layers``
    KDA layers from a CARRIED state: the same operations a token, and of
    the bytes the per-token vectors only (a chunk's state can stay on
    the chip from its first token to its last)."""
    return (tokens * layers * _ops(heads, head_dim),
            tokens * layers * _vectors(heads, head_dim) * itemsize)


def kda_shapes(config: dict) -> Optional[dict]:
    """heads, head_dim, the number of KDA layers and of attending layers
    of a ``kimi_linear`` ``config.json`` as it is run (the entries of its
    two 1-based lists up to ``num_hidden_layers``); None for a
    configuration without such layers."""
    lin = config.get("linear_attn_config")
    if not lin or not lin.get("kda_layers"):
        return None
    depth = config["num_hidden_layers"]
    return {"heads": lin["num_heads"], "head_dim": lin["head_dim"],
            "layers": sum(1 for l in lin["kda_layers"] if l <= depth),
            "attending": sum(1 for l in lin["full_attn_layers"]
                             if l <= depth)}
