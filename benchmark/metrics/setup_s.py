"""Process start to the opening of the measured window: imports, weights,
compile or cache reads, warm-up, the agreement check, server start."""


def read(raw):
    return raw["setup_s"]
