"""Share of the traced slice in which no operation ran on the device:
1 - union of the device-op intervals / traced span."""


def read(raw):
    t = raw["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
