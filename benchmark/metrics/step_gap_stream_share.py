"""Of the time between two ``dyn.step`` events of the traced slice (the
step thread waiting for the event loop to hand it the next iteration),
the share in which a stream bracket ran on another thread:
``dyn.loop.deliver`` / ``dyn.loop.encode_write`` on the loop thread or
``dyn.detok`` on a detokeniser worker (``harness/gap_causes.py``)."""

from benchmark.harness import gap_causes


def read(raw):
    loaded = gap_causes.of_run(raw, __file__)
    got = gap_causes.step_gap_split(loaded) if loaded else None
    if not got or got["gap_s"] <= 0:
        return None
    return 100.0 * got["stream_s"] / got["gap_s"]
