"""The chunked Mamba-2 scan's share of its roofline over the traced
slice: the least time a v5e could take for the prompt tokens the slice
prefilled (benchmark/harness/ssd_work.py: the recurrence's own
operations, and of the bytes a token's vectors only) over the device
time of the ops under the scope ``ssm.scan`` in ``jit(prefill_step)``.

Prompt work is the engine's ``prefill_tokens_total`` over the window,
taken at the slice's share of the window. The chunked form spends more
arithmetic than the recurrence needs (matrix products over a chunk's
[Q, Q] pairs) and holds a [Q, Q, heads] table of decays, so the share
reads low by design: it says how far the form is from what the
recurrence costs, not how well its products use the MXU. A configuration
without Mamba-2 layers, a run without a trace and a program without the
scope report nothing."""

from benchmark.harness import counters, roofline, ssd_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    shapes = ssd_work.mamba2_shapes(raw["model"]["config"])
    if shapes is None:
        return None
    seconds = ssd_work.scope_seconds_in(raw, "ssm.scan", "prefill_step",
                                        __file__)
    prompt = counters.delta(raw, "prefill_tokens_total")
    if not seconds or not prompt:
        return None
    a, b = raw["trace_slice"]
    ops, bytes_ = ssd_work.ssd_prefill(
        prompt * (b - a) / raw["window_s"],
        itemsize=raw["model"]["kv_itemsize"], **shapes)
    least = roofline.least_seconds(ops, bytes_, raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
