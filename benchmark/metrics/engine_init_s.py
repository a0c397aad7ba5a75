"""Seconds of set-up inside the program's ``engine_init`` span:
``JaxEngine.__init__``: the K/V, window and state pools (eager programs of
their own), the page managers, the jit wrappers
(``stats()["setup_span_seconds_total"]["engine_init"]``). None on a
program without the set-up ledger."""


def read(raw):
    return (raw["stats0"].get("setup_span_seconds_total") or {}).get(
        "engine_init")
