"""Share of the prefill dispatches of the window in which no row's
logits were wanted, so the program computed no head (and, for a family
that runs its stack's suffix on the last position alone, none of that
suffix: ``llama.prefill_logits``, models/phi4flash.py ``forward``): delta
``prefill_logits_skipped_total`` / delta ``prefill_dispatches_total``,
both counted on the step thread (``JaxEngine._dispatch_prefill``: a
dispatch whose ``last_idx`` has no entry >= 0), between the two
``stats()`` reads around the window. A chunk that ends no prompt, a
preemption-resume and every prefill of a model that generates by blocks
skip; prompts of one chunk read 0. A program without the counter reports
nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "prefill_logits_skipped_total",
                          "prefill_dispatches_total", 100.0)
