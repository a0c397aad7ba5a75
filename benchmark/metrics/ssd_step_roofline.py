"""The Mamba-2 step kernel's share of its roofline over the traced slice:
the least time a v5e could take for the decode steps the slice did
(benchmark/harness/ssd_work.py: bound by HBM bytes, the float32 matrix
state of every Mamba-2 layer read and written once a decoded token) over
the device time of the ops under the scope ``ssm.scan`` in
``jit(decode_window)``: the kernel on the state pool and the few ops
that make its operands.

Decode work is counted from the clients' rows (``ssd_work.
decoded_row_steps``), so the share errs low; it is a floor's share and
cannot pass 100%. A configuration without Mamba-2 layers, a run without
a trace and a program without the scope report nothing."""

from benchmark.harness import roofline, ssd_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    shapes = ssd_work.mamba2_shapes(raw["model"]["config"])
    if shapes is None:
        return None
    seconds = ssd_work.scope_seconds_in(raw, "ssm.scan", "decode_window",
                                        __file__)
    if not seconds:
        return None
    ops, bytes_ = ssd_work.ssd_decode(
        ssd_work.decoded_row_steps(raw),
        itemsize=raw["model"]["kv_itemsize"], **shapes)
    least = roofline.least_seconds(ops, bytes_, raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
