"""Of the prefill chunks the engine dispatched in the window, a row
each, the share that started past position 0: from what the row's
earlier chunks left, its pages and, for a model with recurrent state,
the state in its slot (``prefill_row_chunks_carried_total`` over
``prefill_row_chunks_total``, as deltas between the two ``stats()`` reads
around the window). How much of the traffic reaches the chunk-to-chunk
carry of the state: a prompt within one chunk never does. A program
without the counters reports nothing."""

from benchmark.harness import counters


def read(raw):
    return counters.ratio(raw, "prefill_row_chunks_carried_total",
                          "prefill_row_chunks_total", 100.0)
