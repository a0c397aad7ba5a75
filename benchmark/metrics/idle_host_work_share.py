"""Share of the traced span in which the device idled (a gap of 50 us or
more between two ops) while the step thread was inside a work phase: any
phase of a ``dyn.step`` but a readback (``admit``, the two dispatches,
``process_window``, ``process_prefill``, ``kv_tier``, ``other``). Split
by overlap (``harness/gap_causes.py idle_split``); with its two
siblings, the gaps under 50 us and the slice's edges it adds up to
``device_idle_share``."""

from benchmark.harness import gap_causes


def read(raw):
    return gap_causes.idle_share(raw, "host_work", __file__)
