"""Share of the requests answered in the window whose recurrent state
started from a page's snapshot: rows whose first prefill chunk read the
state at the end of the last page of a prefix hit
(``state_restores_total``, counted in ``JaxEngine._dispatch_prefill``)
over first tokens emitted (``first_tokens_total``), as deltas between
the two ``stats()`` reads around the window. A row preempted and resumed
on its own pages counts a restore and no second first token, so the share
can pass 100 by the preemptions of the window. A program that keeps no
state, or none by the page, reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "state_restores_total", "first_tokens_total",
                          100.0)
