"""The latent decode kernel's share of its roofline over the traced
slice: the least time a v5e could take for the latent attention the
decode steps of the slice did (benchmark/harness/latent_work.py and
roofline.least_seconds: the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over the device time of the kernel's events
(``latent_attention_decode_layered``, by op name).

What it did is counted from the clients' rows, as ``paged_attn_roofline``
counts it: every token after a request's first that arrived inside the
slice was one decode step of one row, attending to prompt + tokens so
far, in every layer. The last one to three of those positions wait in
the window's buffer and not in the pool the kernel reads (under 0.05%
of an 8k context); rows the engine computed without a client seeing a
token are not counted, and the clients' clock trails the device's by
about one window, so the share errs low, by a few percent over a 5 s
slice. A program without the kernel reports nothing."""

from benchmark.harness import host_trace, latent_work, roofline


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    config = raw["model"]["config"]
    if not config.get("kv_lora_rank"):
        return None
    seconds = host_trace.op_seconds(raw, r"latent_attention_decode",
                                    __file__)
    if not seconds:
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
    ops, bytes_ = latent_work.latent_attention_decode(
        contexts, num_heads=m["num_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        rope_dim=config["qk_rope_head_dim"], page_size=m["page_size"],
        itemsize=m["kv_itemsize"])
    layers = config["num_hidden_layers"]
    least = roofline.least_seconds(ops * layers, bytes_ * layers,
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
