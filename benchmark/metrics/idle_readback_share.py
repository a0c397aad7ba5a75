"""Share of the traced span in which the device idled (a gap of 50 us or
more between two ops) while the step thread was inside
``dyn.readback_window`` / ``dyn.readback_prefill``: the device had
finished what the host was still fetching, and nothing was queued behind
it. Split by overlap (``harness/gap_causes.py idle_split``); with its
two siblings, the gaps under 50 us and the slice's edges it adds up to
``device_idle_share``."""

from benchmark.harness import gap_causes


def read(raw):
    return gap_causes.idle_share(raw, "readback", __file__)
