"""Share of the prefill dispatches of the window behind which the same
scheduler iteration enqueued a decode window (``JaxEngine._step_window``:
nothing was left to prefill after the dispatch and admission, so the
device goes from the prompt's program straight to the rows' next window
and does not wait a host iteration): delta ``prefill_window_topups_total``
/ delta ``prefill_dispatches_total``, both counted on the step thread,
between the two ``stats()`` reads around the window. Prompts that queue
behind one another or run in chunks read low by design: only the last
program of a run of prefills carries a window. A program without the
counter reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "prefill_window_topups_total",
                          "prefill_dispatches_total", 100.0)
