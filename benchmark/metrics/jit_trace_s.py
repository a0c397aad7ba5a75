"""Seconds the process spent tracing Python into jaxprs before the
window opened (``stats()["jit_stage_seconds_total"]["trace"]``: every
top-level ``jaxpr_trace_duration`` event since the process started; a
Pallas kernel's body is traced here, once an instance). Not cached: a
warm machine pays it in full. None on a program without the set-up
ledger."""


def read(raw):
    return (raw["stats0"].get("jit_stage_seconds_total") or {}).get(
        "trace")
