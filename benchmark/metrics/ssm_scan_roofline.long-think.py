"""``ssm_scan_roofline`` for ``phi-4-mini-flash-reasoning.long-think``:
the accepted entry's count (benchmark/harness/ssm_work.py: the float32
state of every Mamba layer read and written once a decoded token, the
per-token vectors of a prompt token) over the device time of the ops
under ``ssm.scan``, with THIS family's shapes
(benchmark/harness/sambay_work.py: the Mamba layers are the even layers
up to L/2, 9 of 32, d_inner 5,120, state 16: jamba2-3b's mixer exactly),
under a name of its own because the accepted entry's list is pinned to
its one cell by tests/benchmark/test_bm_jamba.py and its reader counts
the Mamba layers from Jamba's ``attn_layer_period``. Decode row-steps
are the clients' (``window_attn_work.decode_contexts``). Prompt tokens
are NOT the window's at the slice's share of it, as the accepted entry
takes them: this cell's 48 clients start together, so the first third
of a window is one lump of prefill and a slice in the middle may hold
none (my chip run, PR 63: the share read 105% so). They are the prefill
programs that ran IN the slice (``raw["trace"]["modules"]``) times the
live tokens of a dispatch over the window (``prefill_tokens_total`` /
``prefill_dispatches_total``). It errs low as the accepted entry does."""

from benchmark.harness import (counters, roofline, sambay_work, scope_ops,
                               ssm_work, trace, window_attn_work)


def read(raw):
    if not raw.get("trace") or not raw.get("trace_slice"):
        return None
    found = sambay_work.shapes(raw["model"]["config"])
    if found is None:
        return None
    seconds = scope_ops.path_seconds(raw, "ssm.scan", __file__)
    if not seconds:
        return None
    shape = {"d_inner": found["d_inner"], "d_state": found["d_state"],
             "layers": found["mamba"],
             "itemsize": raw["model"]["kv_itemsize"]}
    ops, bytes_ = ssm_work.selective_scan_decode(
        len(window_attn_work.decode_contexts(raw)), **shape)
    ran = trace.module_stats(raw["trace"], trace.PREFILL_MODULE) \
        if raw["trace"].get("modules") else None
    a_dispatch = counters.ratio(raw, "prefill_tokens_total",
                                "prefill_dispatches_total") or 0.0
    p_ops, p_bytes = ssm_work.selective_scan_prefill(
        (ran["count"] if ran else 0) * a_dispatch, **shape)
    least = roofline.least_seconds(ops + p_ops, bytes_ + p_bytes,
                                   raw["device"]["kind"])
    return 100.0 * least["seconds"] / seconds
