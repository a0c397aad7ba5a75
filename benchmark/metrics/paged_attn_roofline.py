"""The paged-attention decode kernel's share of its roofline over the
traced slice: the least time a v5e could take for the attention the
kernel did (benchmark/harness/roofline.py, bound by HBM bytes at these
shapes) over the time its events took.

What it did is counted from the clients' rows: every token after a
request's first that arrived inside the slice was one decode step of one
row, attending to prompt + tokens so far, in every layer. Rows the
engine computed without a client seeing a token (padding rows have
length 0 and cost nothing; a frozen row after its last token) are not
counted, and the clients' clock trails the device's by about one window,
so the share errs low, by a few percent over a 5 s slice."""

from benchmark.harness import roofline


def read(raw):
    t = raw["trace"]
    if not t or not raw["trace_slice"] or t["kernel_s"] <= 0:
        return None
    a, b = raw["trace_slice"]
    contexts = []
    for r in raw["rows"]:
        k = 0
        for at, n in zip(r["chunk_s"], r["chunk_n"]):
            if a <= at <= b:
                # token j (0-based) of the request attends to
                # prompt_len + j positions; j = 0 came from prefill
                contexts += [r["prompt_len"] + j
                             for j in range(max(k, 1), k + n)]
            k += n
    m = raw["model"]
    ops, bytes_ = roofline.paged_attention_decode(
        contexts, num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], page_size=m["page_size"],
        itemsize=m["kv_itemsize"])
    least = roofline.least_seconds(ops * m["num_layers"],
                                   bytes_ * m["num_layers"],
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / t["kernel_s"]
