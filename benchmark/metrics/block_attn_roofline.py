"""The decode kernel's share of its roofline where a block's L queries
fold into its group axis: the least time a v5e could take for the pooled
attention the slice's forwards did (benchmark/harness/block_attn_work.py
and roofline.least_seconds: the larger of operations over peak FLOP/s
and bytes over peak bytes/s) over the device time of the kernel's events.

What it did is counted from the clients' rows and the program's own
counters: every token that arrived inside the slice stood for
``diffusion_forwards_total / diffusion_tokens_total`` row-forwards (the
window's mean) of its row's block, each reading the pooled positions
before the row's window (``block_attn_work.pooled_context``, a floor).
Rows the engine computed without a client seeing a token (a frozen row,
tokens dropped past ``max_tokens``) are not counted and the clients'
clock trails the device's by about a window, so the share errs low. A
configuration without ``block_length``, or a program without the
counters, reports nothing."""

from benchmark.harness import block_attn_work, counters, roofline


def read(raw):
    t = raw["trace"]
    config = raw["model"]["config"]
    L = config.get("block_length")
    if not L or not t or not raw["trace_slice"] or t["kernel_s"] <= 0:
        return None
    per_token = counters.ratio(raw, "diffusion_forwards_total",
                               "diffusion_tokens_total")
    if not per_token:
        return None
    a, b = raw["trace_slice"]
    steps = raw["engine"]["decode_steps"]
    contexts = []
    for r in raw["rows"]:
        k = 0
        for at, n in zip(r["chunk_s"], r["chunk_n"]):
            if a <= at <= b:
                contexts += [
                    (block_attn_work.pooled_context(r["prompt_len"] + j, L,
                                                    steps), per_token)
                    for j in range(k, k + n)]
            k += n
    m = raw["model"]
    ops, bytes_ = block_attn_work.block_attention_pool(
        contexts, block_length=L, num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
        page_size=m["page_size"], itemsize=m["kv_itemsize"])
    least = roofline.least_seconds(ops * m["num_layers"],
                                   bytes_ * m["num_layers"],
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / t["kernel_s"]
