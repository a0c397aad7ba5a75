"""The KDA step kernel's share of its roofline over the traced slice:
the least time a v5e could take for the decode steps the slice did
(benchmark/harness/kda_work.py: bound by HBM bytes, the float32 matrix
state of every KDA layer read and written once a decoded token, 2 x 2
MiB a layer a row at the published widths, plus the token's vectors)
over the device time of the kernel's events (``kda_step``, by op name).

Decode work is counted from the clients' rows (``ssd_work.
decoded_row_steps``: tokens that reached a client inside the slice),
while the kernel also copies the rows that did not advance (padding, a
finished row waiting for its window) in and out, so the share errs low;
it is a floor's share and cannot pass 100%. A configuration without KDA
layers, a run without a trace and a program without the kernel report
nothing."""

from benchmark.harness import host_trace, kda_work, roofline, ssd_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    shapes = kda_work.kda_shapes(raw["model"]["config"])
    if shapes is None:
        return None
    seconds = host_trace.op_seconds(raw, r"kda_step", __file__)
    if not seconds:
        return None
    ops, bytes_ = kda_work.kda_decode(
        ssd_work.decoded_row_steps(raw),
        itemsize=raw["model"]["kv_itemsize"], **shapes)
    least = roofline.least_seconds(ops, bytes_, raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
