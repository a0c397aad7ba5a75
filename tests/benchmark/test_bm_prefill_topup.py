"""``prefill_topup_share`` (PR 51): the number computed by hand from a
hand-made ``raw``, nothing on the ``stats()`` of a program without the
counter (the driver runs this benchmark code against the parent commit
too) or in a window without a prefill, and one entry that every cell
reports."""

import json
import os

import pytest

from bm_paths import ROOT

from benchmark.harness import cells

NAME = "prefill_topup_share"
STATS0 = {"prefill_dispatches_total": 40, "prefill_window_topups_total": 10}
STATS1 = {"prefill_dispatches_total": 440, "prefill_window_topups_total": 350}
PARENT = {"prefill_dispatches_total": 440}


def _raw(stats0, stats1):
    return {"stats0": stats0, "stats1": stats1, "window_s": 50.0,
            "rows": [], "trace": None, "trace_slice": None}


@pytest.mark.parametrize("stats0,stats1,want", [
    (STATS0, STATS1, 100.0 * 340 / 400),
    (PARENT, PARENT, None),
    ({"prefill_dispatches_total": 40}, PARENT, None),
    (STATS1, STATS1, None),
], ids=["by_hand", "parent", "parent_that_prefilled", "nothing_prefilled"])
def test_reader(stats0, stats1, want):
    got = cells.load_reader(NAME)(_raw(stats0, stats1))
    assert got == (want if want is None else pytest.approx(want))


def test_one_entry_that_every_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1 and "workloads" not in entries[0]
    assert entries[0]["moves"] == "tpot_p50_ms"
    assert entries[0]["source"] == "program_counter"
