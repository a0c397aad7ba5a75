"""Operations and bytes the decode step of latent (MLA, absorbed)
attention needs, from its shapes: what ``latent_attn_roofline`` sets
against the device time of the kernel's events
(``latent_attention_decode_layered``, dynamo_tpu/ops/paged_attention.py).
Beside ``roofline.paged_attention_decode``, and a floor like it: what
any implementation has to do, so the share is never flattered.

There is ONE latent head. A cached token is its latent ``c`` (``r``
elements) and one rope key (``d_r`` elements); every query head scores
it by ``q_lat . c + q_rope . k_r`` and takes ``p . c`` as its value (the
value is a prefix of the key), so per head and token: r + d_r
multiply-adds for the score and r for the value.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def latent_attention_decode(contexts: Iterable[int], *, num_heads: int,
                            kv_lora_rank: int, rope_dim: int,
                            page_size: int, itemsize: int = 2
                            ) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's latent decode attention over
    rows whose contexts (tokens attended) are given.

    Per row of context n: ``2 * H * (2 r + d_r) * n`` operations. Bytes:
    the row's pages are read ONCE for all heads (ceil(n / ps) pages of
    ps x (r + d_r) elements: latent and rope key as published, not the
    lanes a pool pads them to), the absorbed query is read (H x (r +
    d_r)) and the latent-space result written in float32 (H x r x 4).
    Softmax arithmetic, the statistics and the page table are left
    out."""
    ops = bytes_ = 0.0
    r, dr = kv_lora_rank, rope_dim
    for n in contexts:
        pages = -(-n // page_size)
        ops += 2.0 * num_heads * (2 * r + dr) * n
        bytes_ += ((pages * page_size + num_heads) * (r + dr) * itemsize
                   + num_heads * r * 4.0)
    return ops, bytes_
