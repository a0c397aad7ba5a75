"""Seconds the process spent lowering jaxprs to MLIR modules before the
window opened (``stats()["jit_stage_seconds_total"]["lower"]``: every
top-level ``jaxpr_to_mlir_module_duration`` event since the process
started; a Pallas kernel's own lowering lies here). Not cached: a warm
machine pays it in full. None on a program without the set-up ledger."""


def read(raw):
    return (raw["stats0"].get("jit_stage_seconds_total") or {}).get(
        "lower")
