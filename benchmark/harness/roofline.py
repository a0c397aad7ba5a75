"""Operations and bytes a kernel's call needs, from its shapes, and the
least time the chip could take for them: the larger of operations over
peak FLOP/s and bytes over peak bytes/s. Peaks come from
benchmark/peaks.json, keyed by ``device_kind``; a device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Iterable, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


def paged_attention_decode(contexts: Iterable[int], *, num_heads: int,
                           num_kv_heads: int, head_dim: int,
                           page_size: int, itemsize: int = 2
                           ) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's decode attention over rows
    whose contexts (tokens attended, the new one included) are given.

    Per row of context n: scores q.K and the weighted sum p.V are
    2 * H * hd * n multiply-adds each = 4 * H * hd * n operations. Bytes:
    the row's K and V pages are read whole (ceil(n / ps) pages of ps x
    KV x hd elements each, twice), q is read and the output written
    (2 * H * hd). Softmax arithmetic and the page table are left out:
    the count is a floor, so the share it gives is never flattered.
    """
    ops = bytes_ = 0.0
    for n in contexts:
        pages = -(-n // page_size)
        ops += 4.0 * num_heads * head_dim * n
        bytes_ += (2.0 * pages * page_size * num_kv_heads * head_dim
                   + 2.0 * num_heads * head_dim) * itemsize
    return ops, bytes_


def least_seconds(ops: float, bytes_: float, device_kind: str) -> dict:
    pk = peaks(device_kind)
    by_ops = ops / pk["bf16_flops_per_s"]
    by_bytes = bytes_ / pk["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "compute" if by_ops > by_bytes else "memory"}
