"""Mean rows doing useful work per decode step, over the traced slice:
output tokens the clients received in the slice / (decode-window
executions in the trace x decode_steps). First tokens come from prefill,
so they are not counted."""

from benchmark.harness import trace


def read(raw):
    if not raw["trace"] or not raw["trace_slice"]:
        return None
    win = trace.module_stats(raw["trace"], trace.WINDOW_MODULE)
    if not win:
        return None
    a, b = raw["trace_slice"]
    toks = sum(n for r in raw["rows"]
               for k, (t, n) in enumerate(zip(r["chunk_s"], r["chunk_n"]))
               if a <= t <= b and k > 0)
    return toks / (win["count"] * raw["engine"]["decode_steps"])
