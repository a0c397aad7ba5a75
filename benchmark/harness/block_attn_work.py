"""Operations and bytes the pooled half of a block's attention needs, for
a model that generates by diffusion over blocks: what
``block_attn_roofline`` sets against the device time of the decode
kernel's events (``paged_attention_decode_layered``, which such a
model's window calls with the L queries of a block folded into its group
axis: G x L rows a KV head). Beside ``roofline.paged_attention_decode``,
and a floor like it: what any implementation has to do, so the share is
never flattered.

One ROW-FORWARD is one row's block of L queries through one forward
(denoising or commit), in one layer: every query attends to the n
pooled positions before the row's window.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def block_attention_pool(contexts: Iterable[Tuple[int, float]], *,
                         block_length: int, num_heads: int,
                         num_kv_heads: int, head_dim: int, page_size: int,
                         itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer over ``contexts``: pairs (n,
    row-forwards at a pooled context of n positions; the count may be a
    fraction, as a mean of forwards a token is).

    Per row-forward: scores q.K and the weighted sum p.V are 2 * H * hd *
    n multiply-adds each for every one of the L queries = 4 * L * H * hd *
    n operations. Bytes: the row's K and V pages are read ONCE for the L
    queries (ceil(n / ps) pages of ps x KV x hd elements, twice), the L
    queries are read and L outputs written (2 * L * H * hd). The window
    buffer's side (at most a window's own positions, computed in XLA),
    the softmax arithmetic, the statistics and the page table are left
    out."""
    ops = bytes_ = 0.0
    L = block_length
    for n, count in contexts:
        pages = -(-n // page_size)
        ops += count * 4.0 * L * num_heads * head_dim * n
        bytes_ += count * (2.0 * pages * page_size * num_kv_heads * head_dim
                           + 2.0 * L * num_heads * head_dim) * itemsize
    return ops, bytes_


def pooled_context(position: int, block_length: int,
                   decode_steps: int) -> int:
    """A floor of the pooled positions a block's queries read: the block
    of ``position`` starts at ``position // L * L``, and its row's window
    (``decode_steps`` positions, whole blocks) started at most
    ``decode_steps - L`` positions before that; the blocks between lie in
    the window's buffer, not in the pool."""
    start = position // block_length * block_length
    return max(start - (decode_steps - block_length), 0)
