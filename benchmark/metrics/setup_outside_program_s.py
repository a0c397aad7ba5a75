"""Seconds of ``setup_s`` that no span of the program brackets:
``setup_s`` less the union of the set-up ledger's depth-0 spans
(``stats()["setup_spans"]``: ``[name, start, end, depth]`` on
``time.monotonic()``, the clock of ``run.py``'s ``T_START`` and of the
load generator's ``open``), each cut to the interval from ``t_open -
setup_s`` to ``t_open``. What is left is the harness's own (the device
check, the weight draw, the agreement check and its reference, the warm
request, the load generator's start) and whatever of the program nobody
thought to name; the gaps between the spans, in ``setup_spans``' order,
say which. None on a program without the ledger."""


def read(raw):
    spans = raw["stats0"].get("setup_spans")
    if spans is None:
        return None
    t1 = raw["t_open"]
    t0 = t1 - raw["setup_s"]
    covered, edge = 0.0, t0
    for _name, start, end, depth in sorted(spans, key=lambda s: s[1]):
        end = min(end, t1)
        if depth == 0 and end > max(start, edge):
            covered += end - max(start, edge)
            edge = end
    return raw["setup_s"] - covered
