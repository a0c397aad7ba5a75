"""The paged-attention decode kernel's events as a share of the time an
operation ran on the device, in the traced slice."""


def read(raw):
    t = raw["trace"]
    if not t or t["busy_s"] <= 0 or t["kernel_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
