"""Seconds of set-up inside the program's ``http_start`` span:
``run.run_http`` from its imports to the socket listening
(``stats()["setup_span_seconds_total"]["http_start"]``). None on a
program without the set-up ledger."""


def read(raw):
    return (raw["stats0"].get("setup_span_seconds_total") or {}).get(
        "http_start")
