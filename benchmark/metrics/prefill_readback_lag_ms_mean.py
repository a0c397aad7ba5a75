"""Mean time from the end of a ``prefill_step`` execution on the device
to the return of the ``dyn.readback_prefill`` that fetched its tokens:
the readback is deferred by an iteration, so the step thread first
dispatches, admits and processes a window
(``harness/gap_causes.py prefill_lives``)."""

from benchmark.harness import gap_causes


def read(raw):
    return gap_causes.prefill_ms_mean(raw, "readback_lag_s", __file__)
