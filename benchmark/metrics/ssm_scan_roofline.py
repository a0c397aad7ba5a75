"""The selective scan's share of its roofline over the traced slice: the
least time a v5e could take for the scan work the slice did
(benchmark/harness/ssm_work.py: bound by HBM bytes, the float32 state of
every Mamba layer read and written once a decoded token) over the device
time of the ops under the scope ``ssm.scan``.

Decode work is counted from the clients' rows as ``paged_attn_roofline``
counts it: every token after a request's first that arrived inside the
slice was one step of one row through every Mamba layer. Prompt work is
the engine's ``prefill_tokens_total`` over the window, taken at the
slice's share of the window. Steps the engine computed without a client
seeing a token are not counted and the clients' clock trails the
device's by about a window, so the share errs low; it is a floor's
share and cannot pass 100%."""

from benchmark.harness import counters, roofline, scope_ops, ssm_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    config = raw["model"]["config"]
    if not config.get("mamba_d_state"):
        return None
    seconds = scope_ops.path_seconds(raw, "ssm.scan", __file__)
    if not seconds:
        return None
    a, b = raw["trace_slice"]
    row_steps = 0
    for r in raw["rows"]:
        k = 0
        for at, n in zip(r["chunk_s"], r["chunk_n"]):
            if a <= at <= b:
                row_steps += k + n - max(k, 1)   # token 0 came from prefill
            k += n
    shapes = ssm_work.mamba_shapes(config)
    itemsize = raw["model"]["kv_itemsize"]
    ops, bytes_ = ssm_work.selective_scan_decode(
        row_steps, itemsize=itemsize, **shapes)
    prompt = counters.delta(raw, "prefill_tokens_total") or 0.0
    p_ops, p_bytes = ssm_work.selective_scan_prefill(
        prompt * (b - a) / raw["window_s"], itemsize=itemsize, **shapes)
    least = roofline.least_seconds(ops + p_ops, bytes_ + p_bytes,
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
