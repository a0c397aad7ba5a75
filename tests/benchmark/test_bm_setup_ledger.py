"""The seven readers of the program's set-up ledger (PR 52): each returns
the number computed by hand from a hand-made ``raw``, and nothing on the
``stats()`` of a program without the ledger (the driver runs this
benchmark code against the parent commit too); ``setup_outside_program_s``
counts only what lies between ``T_START`` and the opening of the window;
and each is one entry that every cell reports."""

import json
import os

import pytest

from bm_paths import ROOT

from benchmark.harness import cells

STAGES = {"jit_trace_s": "trace", "jit_lower_s": "lower",
          "compile_cache_read_s": "cache_read",
          "backend_compile_s": "backend_compile"}
SPANS = {"engine_init_s": "engine_init", "http_start_s": "http_start"}
NAMES = [*STAGES, *SPANS, "setup_outside_program_s"]

# T_START at 1000.0, the window opens at 1040.0
T_OPEN, SETUP_S = 1040.0, 40.0
STATS0 = {
    "jit_stage_seconds_total": {"trace": 2.5, "lower": 4.25,
                                "cache_read": 1.5, "backend_compile": 0.03},
    "setup_span_seconds_total": {"jax_import": 3.0, "engine_setup": 0.5,
                                 "engine_init": 2.25, "warmup": 11.0,
                                 "warmup.prefill": 4.0, "http_start": 0.75},
    "setup_spans": [
        ["jax_import", 1000.5, 1003.5, 0],
        ["engine_setup", 1010.0, 1010.5, 0],
        ["engine_init", 1010.5, 1012.75, 0],
        ["warmup", 1020.0, 1031.0, 0],
        ["warmup.prefill", 1020.0, 1024.0, 1],
        ["http_start", 1031.5, 1032.25, 0]],
}
PARENT = {"warmup_seconds": 11.0}


def _raw(stats0, **over):
    return {"stats0": stats0, "stats1": stats0, "window_s": 50.0,
            "t_open": T_OPEN, "setup_s": SETUP_S, "rows": [],
            "trace": None, "trace_slice": None, **over}


@pytest.mark.parametrize("name", NAMES)
def test_nothing_on_a_program_without_the_ledger(name):
    assert cells.load_reader(name)(_raw(PARENT)) is None


@pytest.mark.parametrize("name,stage", STAGES.items())
def test_a_stage_reader_reads_its_slot(name, stage):
    assert cells.load_reader(name)(_raw(STATS0)) == \
        STATS0["jit_stage_seconds_total"][stage]


@pytest.mark.parametrize("name,span", SPANS.items())
def test_a_span_reader_reads_its_span(name, span):
    assert cells.load_reader(name)(_raw(STATS0)) == \
        STATS0["setup_span_seconds_total"][span]


def test_outside_is_setup_less_the_depth_0_spans():
    covered = 3.0 + 0.5 + 2.25 + 11.0 + 0.75     # the nested span: no
    got = cells.load_reader("setup_outside_program_s")(_raw(STATS0))
    assert got == pytest.approx(SETUP_S - covered)


def test_outside_ignores_spans_before_t_start_and_after_t_open():
    stats0 = dict(STATS0, setup_spans=[
        ["jax_import", 990.0, 999.0, 0],          # a process before
        ["engine_setup", 998.0, 1002.0, 0],       # across T_START
        *STATS0["setup_spans"][2:],
        ["warmup", 1039.0, 1045.0, 0],            # across t_open
        ["http_start", 1050.0, 1051.0, 0]])       # after it
    covered = 2.0 + 2.25 + 11.0 + 0.75 + 1.0
    got = cells.load_reader("setup_outside_program_s")(_raw(stats0))
    assert got == pytest.approx(SETUP_S - covered)


def test_outside_counts_overlapping_spans_once():
    stats0 = dict(STATS0, setup_spans=[
        ["http_start", 1005.0, 1030.0, 0],
        ["warmup", 1010.0, 1020.0, 0],            # opened on another thread
        ["engine_init", 1025.0, 1035.0, 0]])
    got = cells.load_reader("setup_outside_program_s")(_raw(stats0))
    assert got == pytest.approx(SETUP_S - 30.0)


@pytest.mark.parametrize("name", NAMES)
def test_one_entry_that_every_cell_reports(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1 and "workloads" not in entries[0]
    assert entries[0]["moves"] == "setup_s"
    assert entries[0]["unit"] == "s" and entries[0]["better"] == "lower"
    assert entries[0]["layer"] == "process start-up"
    assert entries[0]["source"] == (
        "program_counter" if name in STAGES else "program_span")
    assert bench["per_layer"].index(entries[0]) >= 81
