"""Mean time a request waited in the scheduler's queue before its first
prefill: the engine's queue_wait_seconds_total over the window, per
request sent."""


def read(raw):
    n = len(raw["rows"])
    if not n:
        return None
    d = (raw["stats1"]["queue_wait_seconds_total"]
         - raw["stats0"]["queue_wait_seconds_total"])
    return 1000.0 * d / n
