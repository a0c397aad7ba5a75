"""Mean gap between two scheduler iterations while work was queued: the
``between_steps`` phase (the ``run_in_executor`` hop through the event
loop that also writes the SSE streams, ``_reap``) over
step_iterations_total."""

from benchmark.harness import counters


def read(raw):
    d = counters.phase_deltas(raw)
    n = counters.delta(raw, "step_iterations_total")
    if not d or not n or "between_steps" not in d:
        return None
    return 1000.0 * d["between_steps"] / n
