"""Mean time a ``prefill_step`` program waited on the device's queue:
from the end of the ``dyn.dispatch_prefill`` that enqueued it (the call
has returned) to the start of its execution (``XLA Modules``), 0 where
the device had started before the bracket closed. The window in flight
is what it waits behind (``harness/gap_causes.py prefill_lives``)."""

from benchmark.harness import gap_causes


def read(raw):
    return gap_causes.prefill_ms_mean(raw, "device_wait_s", __file__)
