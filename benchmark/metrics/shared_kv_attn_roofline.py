"""The decode kernel's share of its roofline in the reads of the ONE
layer's K/V pages that several layers share, over the traced slice: the
least time a v5e could take to read each decoding row's pages of the full
layer once a READING layer (the full layer and every cross layer, one
after the other: benchmark/harness/sambay_work.py ``shared_kv_decode``,
bound by 819 GB/s) over the device time of the kernel's events under the
scopes ``attn.full`` and ``attn.cross``
(benchmark/harness/window_attn_work.py ``kernel_seconds``). What the
rows did is counted from the clients' rows, as ``paged_attn_roofline``
counts it, and the kernel's time also holds the prefill programs' cross
half (one position a row a chunk: under 1% of the decode reads at this
cell's lengths), so the share errs low. A configuration of another
family and a program without the scopes report nothing."""

from benchmark.harness import roofline, sambay_work, window_attn_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    found = sambay_work.shapes(raw["model"]["config"])
    if found is None:
        return None
    seconds = sum(window_attn_work.kernel_seconds(raw, scope, __file__)
                  or 0.0 for scope in ("attn.full", "attn.cross"))
    if not seconds:
        return None
    m = raw["model"]
    ops, bytes_ = sambay_work.shared_kv_decode(
        window_attn_work.decode_contexts(raw), readers=found["readers"],
        in_buffer=raw["engine"]["decode_steps"], num_heads=found["heads"],
        num_kv_heads=found["kv_heads"], head_dim=found["head_dim"],
        page_size=m["page_size"], itemsize=m["kv_itemsize"])
    least = roofline.least_seconds(ops, bytes_, raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
