"""Seconds ``JaxEngine.warmup()`` took (compile or cache reads of the
cell's grid): the part of ``setup_s`` the program itself times."""


def read(raw):
    return raw["stats1"].get("warmup_seconds") or None
