"""Seconds the process spent in the backend's compiler before the window
opened (``stats()["jit_stage_seconds_total"]["backend_compile"]``:
``backend_compile_duration`` less the cache read reported inside it on
a hit, so ~0 on a warm machine and most of ``first_setup_s - setup_s``
on a cold one). None on a program without the set-up ledger."""


def read(raw):
    return (raw["stats0"].get("jit_stage_seconds_total") or {}).get(
        "backend_compile")
