"""``prefill_logits_skipped_share`` (PR 64): the number computed by hand
from a hand-made ``raw``, nothing on the ``stats()`` of a program without
the counter (the driver runs this benchmark code against the parent
commit too) or in a window without a prefill, and one entry that every
cell reports."""

import json
import os

import pytest

from bm_paths import ROOT

from benchmark.harness import cells

NAME = "prefill_logits_skipped_share"
STATS0 = {"prefill_dispatches_total": 40, "prefill_logits_skipped_total": 30}
STATS1 = {"prefill_dispatches_total": 440,
          "prefill_logits_skipped_total": 396}
PARENT = {"prefill_dispatches_total": 440}
NONE_SKIPPED = {"prefill_dispatches_total": 480,
                "prefill_logits_skipped_total": 396}


def _raw(stats0, stats1):
    return {"stats0": stats0, "stats1": stats1, "window_s": 50.0,
            "rows": [], "trace": None, "trace_slice": None}


@pytest.mark.parametrize("stats0,stats1,want", [
    (STATS0, STATS1, 100.0 * 366 / 400),
    (STATS1, NONE_SKIPPED, 0.0),
    (PARENT, PARENT, None),
    ({"prefill_dispatches_total": 40}, PARENT, None),
    (STATS1, STATS1, None),
], ids=["by_hand", "every_program_ends_a_prompt", "parent",
        "parent_that_prefilled", "nothing_prefilled"])
def test_reader(stats0, stats1, want):
    got = cells.load_reader(NAME)(_raw(stats0, stats1))
    assert got == (want if want is None else pytest.approx(want))


def test_one_entry_that_every_cell_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1 and "workloads" not in entries[0]
    assert entries[0]["moves"] == "tpot_p50_ms"
    assert entries[0]["layer"] == "step programs"
    assert entries[0]["source"] == "program_counter"
    assert bench["per_layer"][-1] is entries[0]
