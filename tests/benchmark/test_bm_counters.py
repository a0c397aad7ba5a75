"""The readers PR 24 added over the engine's own counters: each gives
the number computed by hand from a hand-made ``raw``, and None on the
``stats()`` of a program that does not have the key yet (the driver runs
this benchmark code against the parent commit too)."""

import pytest

from bm_paths import ROOT  # noqa: F401 — puts the repo on sys.path

from benchmark.harness import cells, counters

PHASES0 = {"admit": 1.0, "kv_tier": 0.0, "dispatch_window": 2.0,
           "dispatch_prefill": 1.0, "readback_window": 10.0,
           "process_window": 1.0, "readback_prefill": 4.0,
           "process_prefill": 0.5, "between_steps": 0.5, "idle": 30.0,
           "other": 0.25}
# over the window: 50 s, of which 30 + 8 blocked on the device, 2 idle
PHASES1 = {"admit": 1.5, "kv_tier": 0.0, "dispatch_window": 5.0,
           "dispatch_prefill": 2.5, "readback_window": 40.0,
           "process_window": 2.0, "readback_prefill": 12.0,
           "process_prefill": 1.0, "between_steps": 2.5, "idle": 32.0,
           "other": 1.75}
STATS0 = {"queue_wait_seconds_total": 1.0, "step_phase_seconds_total": PHASES0,
          "step_iterations_total": 100, "prefill_wait_seconds_total": 2.0,
          "first_token_seconds_total": 3.0, "engine_ttft_seconds_total": 6.0,
          "first_tokens_total": 10, "prefill_tokens_total": 1000,
          "prefill_slots_total": 4000, "decode_rows_total": 500,
          "decode_slots_total": 1000, "warmup_seconds": 31.5}
STATS1 = {"queue_wait_seconds_total": 9.0, "step_phase_seconds_total": PHASES1,
          "step_iterations_total": 900, "prefill_wait_seconds_total": 8.0,
          "first_token_seconds_total": 15.0, "engine_ttft_seconds_total": 32.0,
          "first_tokens_total": 110, "prefill_tokens_total": 31000,
          "prefill_slots_total": 64000, "decode_rows_total": 20500,
          "decode_slots_total": 51000, "warmup_seconds": 31.5}
# what PR 23's program reports: none of the keys above but the first
PARENT = {"queue_wait_seconds_total": 1.0, "prompt_tokens_total": 5}


def _row(due, first):
    return {"cut": False, "due_s": due, "chunk_s": [first, first + 0.1]}


def _raw(stats0, stats1):
    return {"stats0": stats0, "stats1": stats1, "window_s": 50.0,
            "rows": [_row(0.0, 0.25), _row(1.0, 1.35), _row(2.0, 2.3),
                     {"cut": True, "due_s": 3.0, "chunk_s": []}],
            "trace": None, "trace_slice": None}


BY_HAND = {
    "prefill_wait_ms_mean": 1000.0 * 6.0 / 100,
    "first_token_ms_mean": 1000.0 * 12.0 / 100,
    # the clients' mean TTFT is 300 ms, the engine's own 260 ms
    "ttft_outside_engine_ms_mean": 300.0 - 1000.0 * 26.0 / 100,
    # 50 s of phases, 30 + 8 + 2 of them waiting
    "host_step_busy_share": 100.0 * 10.0 / 50.0,
    "step_gap_ms_mean": 1000.0 * 2.0 / 800,
    "decode_slot_fill_share": 100.0 * 20000 / 50000,
    "prefill_slot_fill_share": 100.0 * 30000 / 60000,
    "warmup_s": 31.5,
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_gives_the_number_computed_by_hand(name):
    read = cells.load_reader(name)
    assert read(_raw(STATS0, STATS1)) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND) + ["moe_busy_share",
                                                    "sampler_busy_share"])
def test_reader_gives_none_on_the_parents_stats(name):
    read = cells.load_reader(name)
    assert read(_raw(PARENT, PARENT)) is None


@pytest.mark.parametrize("name", ["prefill_wait_ms_mean",
                                  "first_token_ms_mean",
                                  "ttft_outside_engine_ms_mean",
                                  "step_gap_ms_mean",
                                  "decode_slot_fill_share",
                                  "prefill_slot_fill_share"])
def test_reader_gives_none_when_nothing_was_counted(name):
    """A window in which the denominator did not move."""
    assert cells.load_reader(name)(_raw(STATS1, STATS1)) is None


def test_phase_deltas_add_up_to_the_window():
    d = counters.phase_deltas(_raw(STATS0, STATS1))
    assert sum(d.values()) == pytest.approx(50.0)
    assert counters.phase_deltas(_raw(PARENT, PARENT)) is None
    assert counters.delta(_raw(STATS0, PARENT), "first_tokens_total") is None
    assert counters.ratio(_raw(STATS0, STATS1), "decode_rows_total",
                          "decode_slots_total") == pytest.approx(0.4)
