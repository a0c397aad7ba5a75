"""Seconds the process spent reading executables back from the
persistent compile cache before the window opened
(``stats()["jit_stage_seconds_total"]["cache_read"]``: every
``cache_retrieval_time_sec`` event, one a program on a warm machine).
None on a program without the set-up ledger."""


def read(raw):
    return (raw["stats0"].get("jit_stage_seconds_total") or {}).get(
        "cache_read")
