"""Share of the step thread's time that is the host's own work: every
phase of ``step_phase_seconds_total`` except the two readbacks (the
thread blocked on the device) and ``idle`` (no work queued), over all of
them. What is left of 100% is how much host the chip has in hand."""

from benchmark.harness import counters

WAITING = ("readback_window", "readback_prefill", "idle")


def read(raw):
    d = counters.phase_deltas(raw)
    if not d:
        return None
    total = sum(d.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in d.items() if k not in WAITING) / total
