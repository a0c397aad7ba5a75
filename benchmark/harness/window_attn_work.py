"""Operations and bytes of decode attention where layers differ in what
they may see (``sliding_window_layout`` of a ``smallthinker``
``config.json``), and the device time of the decode kernel's calls of
one kind of layer: what ``window_attn_roofline`` and
``full_attn_roofline`` set against each other. The only place that
knows the count.

A window layer's query at position t sees ``(t - window, t]``; a full
layer's sees ``[0, t]``. The program keeps a pool a kind of layer
(dynamo_tpu/models/llama.py ``_window_family_by_kind``) and calls the
one decode kernel (``paged_attention_decode_layered``) under the scope
``attn.window`` or ``attn.full``; the last ``decode_steps`` positions or
fewer wait in the window program's buffer, which XLA reads, not the
kernel. The count is the kernel's floor: the pages of the pool that
intersect what the layer may see and that no step of a decode window
finds in the buffer, K and V, each read once. ``roofline.
paged_attention_decode`` assumes every layer reads the whole context and
would read past 100% for the window layers here.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

from benchmark.harness import counters, host_trace, trace

KERNEL = trace.DECODE_KERNEL_OP      # ^paged_attention_decode


def layers_of(config: dict) -> Optional[dict]:
    """{"window": n, "full": n, "size": window} of the layers that run
    (the first ``num_hidden_layers`` entries of the layout); None for a
    configuration without the layout."""
    layout = config.get("sliding_window_layout")
    if not layout or not config.get("sliding_window_size"):
        return None
    kinds = layout[:config["num_hidden_layers"]]
    return {"window": sum(1 for k in kinds if k),
            "full": sum(1 for k in kinds if not k),
            "size": int(config["sliding_window_size"])}


def attention_decode(contexts: Iterable[int], *, window: Optional[int],
                     in_buffer: int, num_heads: int, num_kv_heads: int,
                     head_dim: int, page_size: int, itemsize: int = 2
                     ) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's decode kernel over rows whose
    contexts (tokens attended by a full layer, the new one included) are
    given; ``window``: the layer's, or None for a full layer;
    ``in_buffer``: the positions before the query that a decode window
    may still hold in its buffer (its ``decode_steps``).

    A row of context n has its query at n - 1 and sees positions
    ``[lo, n)``, ``lo = max(n - window, 0)`` (0 for a full layer); the
    kernel reads the pool's part ``[lo, n - in_buffer)`` at the least:
    the pages that intersect it, whole, K and V once each (ps x KV x hd
    elements a page each), q read and the output written; 4 H hd
    operations a position. Softmax arithmetic, the statistics and the
    page table are left out: a floor, so a kernel at the floor reads
    100% and no kernel reads more."""
    ops = bytes_ = 0.0
    for n in contexts:
        lo = max(n - window, 0) if window is not None else 0
        hi = max(n - in_buffer, lo)
        pages = -(-hi // page_size) - lo // page_size if hi > lo else 0
        ops += 4.0 * num_heads * head_dim * (hi - lo)
        bytes_ += (2.0 * pages * page_size * num_kv_heads * head_dim
                   + 2.0 * num_heads * head_dim) * itemsize
    return ops, bytes_


def kernel_seconds(raw: dict, scope: str, reader_file: str
                   ) -> Optional[float]:
    """Device seconds of the decode kernel's events whose name-stack
    path has the component ``scope`` (``attn.window`` / ``attn.full``),
    averaged over the chips, in the traced slice of the run ``raw`` came
    from. None where the run was not traced, the trace is another
    run's, the program has no scopes, or no such event ran."""
    if counters.PHASES_KEY not in raw.get("stats1", {}):
        return None
    found = host_trace._run_trace(raw, reader_file)
    if found is None:
        return None
    planes = found[0]["ops"]
    total = 0.0
    for ops in planes.values():
        for name, _, d, tf_op in ops:
            if KERNEL.match(trace._op(name)[0]) \
                    and scope in tf_op.rstrip(":").split("/"):
                total += d
    return total / len(planes) or None


def decode_contexts(raw: dict) -> list:
    """Context of every decode row-step a client saw inside the traced
    slice (``paged_attn_roofline``'s count): token j (0-based) of a
    request attends to prompt_len + j positions; j = 0 came from
    prefill."""
    a, b = raw["trace_slice"]
    contexts = []
    for r in raw["rows"]:
        k = 0
        for at, n in zip(r["chunk_s"], r["chunk_n"]):
            if a <= at <= b:
                contexts += [r["prompt_len"] + j
                             for j in range(max(k, 1), k + n)]
            k += n
    return contexts


def roofline_share(raw: dict, kind: str, reader_file: str
                   ) -> Optional[float]:
    """100 x the least time for the decode attention of the layers of
    ``kind`` ("window" / "full") in the slice over the device time of
    the kernel's events under ``attn.<kind>``."""
    from benchmark.harness import roofline

    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    layers = layers_of(raw["model"]["config"])
    if layers is None or not layers[kind]:
        return None
    seconds = kernel_seconds(raw, "attn." + kind, reader_file)
    if not seconds:
        return None
    m = raw["model"]
    ops, bytes_ = attention_decode(
        decode_contexts(raw),
        window=layers["size"] if kind == "window" else None,
        in_buffer=raw["engine"]["decode_steps"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
        page_size=m["page_size"], itemsize=m["kv_itemsize"])
    least = roofline.least_seconds(ops * layers[kind], bytes_ * layers[kind],
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
