"""The chunked delta rule's share of its roofline over the traced slice:
the least time a v5e could take for the prompt tokens the slice
prefilled (benchmark/harness/kda_work.py: the larger of the recurrence's
own operations over the bf16 peak and a token's vectors over the HBM
peak) over the device time of the ops under the scope ``kda.scan`` in
``jit(prefill_step)``.

Prompt work is the engine's ``prefill_tokens_total`` over the window,
taken at the slice's share of the window. The chunked form spends other
arithmetic than the recurrence needs (two [Q, Q] tables a head whose
entries each sum d_k decayed products on the vector unit, a triangular
solve, products with the state a chunk) and reads and writes the state a
chunk, in plain XLA, so the share reads low by design: it says how far
the form is from what the recurrence costs. A configuration without KDA
layers, a run without a trace and a program without the scope report
nothing."""

from benchmark.harness import counters, kda_work, roofline, ssd_work


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    shapes = kda_work.kda_shapes(raw["model"]["config"])
    if shapes is None:
        return None
    seconds = ssd_work.scope_seconds_in(raw, "kda.scan", "prefill_step",
                                        __file__)
    prompt = counters.delta(raw, "prefill_tokens_total")
    if not seconds or not prompt:
        return None
    a, b = raw["trace_slice"]
    ops, bytes_ = kda_work.kda_prefill(
        prompt * (b - a) / raw["window_s"],
        itemsize=raw["model"]["kv_itemsize"], **shapes)
    least = roofline.least_seconds(ops, bytes_, raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
