"""Operations and bytes the recurrence of a Mamba-2 layer needs, from
its shapes, and the device time of the ops that ran it in ONE program:
what ``ssd_step_roofline`` (the decode window's step kernel) and
``ssd_chunk_roofline`` (prefill's chunked form) set against each other.
Floors from the recurrence itself, whatever implements it, so the share
they give is never flattered.

Per token and layer, over heads x head_dim x d_state elements:
``S = exp(dt A) S + (dt x) B`` and ``y = sum_n S C``: two
multiplications and an addition, then a multiply-accumulate = 5
operations an element (the exponential is one a head, ``dt x`` one a
channel, ``+ D x`` 2 a channel). The chunked form prefill runs does more
arithmetic than this (about 8.4 MFLOP a token a layer at 128 x 64 x 128,
on the MXU) to do the same recurrence: its share is of this floor all
the same.
"""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark.harness import counters, host_trace, trace

OPS_PER_ELEMENT = 5.0
STATE_ITEMSIZE = 4      # the state is float32 wherever it is stored


def _vectors(heads: int, head_dim: int, d_state: int) -> float:
    """Elements of a token's vectors: x in and y out (heads x head_dim
    each), dt (a head), B and C (d_state each)."""
    return 2.0 * heads * head_dim + heads + 2.0 * d_state


def ssd_decode(row_steps: float, *, heads: int, head_dim: int, d_state: int,
               layers: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ``row_steps`` single-token steps (one row
    advancing one token) through ``layers`` Mamba-2 layers from a STORED
    state: the state is read and written once a row-step a layer (2 x
    heads x head_dim x d_state x 4), the token's vectors move in the
    model's type. The conv tail is not counted: it moves under
    ``ssm.conv``."""
    per = heads * head_dim * d_state
    ops = row_steps * layers * (OPS_PER_ELEMENT * per
                                + 2.0 * heads * head_dim)
    bytes_ = row_steps * layers * (
        2.0 * per * STATE_ITEMSIZE
        + _vectors(heads, head_dim, d_state) * itemsize)
    return ops, bytes_


def ssd_prefill(tokens: float, *, heads: int, head_dim: int, d_state: int,
                layers: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ``tokens`` prompt tokens through ``layers``
    Mamba-2 layers from a CARRIED state: the same operations a token, and
    of the bytes the per-token vectors only (a chunk's state can stay on
    the chip from its first token to its last)."""
    per = heads * head_dim * d_state
    ops = tokens * layers * (OPS_PER_ELEMENT * per + 2.0 * heads * head_dim)
    bytes_ = tokens * layers * _vectors(heads, head_dim, d_state) * itemsize
    return ops, bytes_


def mamba2_shapes(config: dict) -> Optional[dict]:
    """heads, head_dim, d_state and the number of Mamba-2 layers of a
    ``granitemoehybrid`` ``config.json`` as it is run (the first
    ``num_hidden_layers`` entries of ``layer_types``); None for a
    configuration without such layers."""
    if not config.get("mamba_n_heads"):
        return None
    kinds = (config.get("layer_types") or [])[:config["num_hidden_layers"]]
    return {"heads": config["mamba_n_heads"],
            "head_dim": config["mamba_d_head"],
            "d_state": config["mamba_d_state"],
            "layers": sum(1 for kind in kinds if kind == "mamba")}


def scope_seconds_in(raw: dict, scope: str, program: str, reader_file: str
                     ) -> Optional[float]:
    """Device seconds of the ops under the scope ``scope`` of the program
    ``jit(<program>)`` alone (``scope_ops.path_seconds`` sums a scope
    over every program), averaged over the chips, in the traced slice.
    None where the run was not traced, the trace is another run's, the
    program has no scopes, or no such op ran."""
    if counters.PHASES_KEY not in raw.get("stats1", {}):
        return None
    found = host_trace._run_trace(raw, reader_file)
    if found is None:
        return None
    planes = found[0]["ops"]
    total = 0.0
    for ops in planes.values():
        for name, _, d, tf_op in ops:
            if trace.CONTAINER_OP.match(trace._op(name)[0]):
                continue
            parts = tf_op.rstrip(":").split("/")
            if scope in parts and f"jit({program})" in parts:
                total += d
    return total / len(planes) or None


def decoded_row_steps(raw: dict) -> int:
    """Tokens after a request's first that arrived inside the traced
    slice: each was one step of one row through every layer (the count
    ``ssm_scan_roofline`` and ``paged_attn_roofline`` use; it errs low,
    by the steps no client saw and the clients' clock trailing the
    device's by about a window)."""
    a, b = raw["trace_slice"]
    steps = 0
    for r in raw["rows"]:
        k = 0
        for at, n in zip(r["chunk_s"], r["chunk_n"]):
            if a <= at <= b:
                steps += k + n - max(k, 1)   # token 0 came from prefill
            k += n
    return steps
