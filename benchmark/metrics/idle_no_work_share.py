"""Share of the traced span in which the device idled (a gap of 50 us or
more between two ops) while the step thread was outside every
``dyn.step``: between two iterations (the hop through the event loop) or
asleep with nothing queued. Split by overlap (``harness/gap_causes.py
idle_split``, which also says how much of it a stream bracket ran in);
with its two siblings, the gaps under 50 us and the slice's edges it
adds up to ``device_idle_share``."""

from benchmark.harness import gap_causes


def read(raw):
    return gap_causes.idle_share(raw, "no_work", __file__)
