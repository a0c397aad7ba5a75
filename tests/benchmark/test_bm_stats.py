"""TTFT from the due time, gaps, TPOT and percentiles on hand-made rows,
and the metric readers on a hand-made run."""

import math

import pytest

import bm_paths  # noqa: F401

from benchmark.harness import cells, stats


def row(due, chunks, n_per_chunk=1, want=None, **over):
    toks = n_per_chunk * len(chunks)
    want = toks if want is None else want
    r = {"i": 0, "due_s": due, "sent_s": due + 0.001, "prompt_len": 10,
         "output_len": want, "status": 200, "done": True,
         "finish": "length", "tokens": toks,
         "usage": {"completion_tokens": toks}, "chunk_s": list(chunks),
         "chunk_n": [n_per_chunk] * len(chunks), "end_s": None,
         "cut": False, "error": None}
    r.update(over)
    return r


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.pctile(xs, 0.95) == 95 and stats.pctile(xs, 0.5) == 50
    assert stats.pctile(xs, 1.0) == 100 and stats.pctile([], 0.5) is None
    assert stats.pctile([3.0], 0.99) == 3.0
    assert stats.pctile(range(1, 21), 0.95) == 19


def test_ttft_counts_from_the_due_time_not_the_send():
    r = row(2.0, [2.5, 2.6], sent_s=2.3)
    assert stats.ttft_s(r) == pytest.approx(0.5)
    assert stats.late_s(r) == pytest.approx(0.3)


def test_gaps_and_tpot():
    r = row(0.0, [1.0, 1.1, 1.4, 1.5], n_per_chunk=4)
    assert stats.gaps_s(r) == pytest.approx([0.1, 0.3, 0.1])
    # 16 tokens, 12 after the first chunk, over 0.5 s
    assert stats.tpot_s(r) == pytest.approx(0.5 / 12)
    assert stats.tpot_s(row(0.0, [1.0])) is None


@pytest.mark.parametrize("over", [
    {"status": 500}, {"done": False}, {"tokens": 3},
    {"error": "ClientError"}, {"finish": None},
    {"usage": {"completion_tokens": 1}}])
def test_a_request_not_answered_in_full_fails(over):
    r = row(0.0, [0.1, 0.2], **over)
    assert not stats.ok(r) and stats.failed(r)
    assert stats.tpot_s(r) is None


def test_failed_request_is_the_worst_ttft_and_cut_is_not_failed():
    dead = row(0.0, [], want=4, status=503, done=False)
    assert math.isinf(stats.ttft_s(dead)) and stats.failed(dead)
    cut = row(0.0, [0.1], want=9, done=False, cut=True)
    assert not stats.failed(cut) and not stats.ok(cut)
    assert stats.finite_ms(math.inf) is None
    assert stats.finite_ms(0.25) == 250.0


def test_tokens_in_window_counts_arrivals_not_finished_requests():
    rows = [row(0.0, [1.0, 9.0, 11.0], n_per_chunk=4),
            row(0.0, [12.0], n_per_chunk=4)]
    assert stats.tokens_in_window(rows, 10.0) == 8


def _raw():
    rows = [row(float(i), [i + 0.2 + 0.01 * i, i + 0.3 + 0.01 * i,
                           i + 0.5 + 0.01 * i], n_per_chunk=4)
            for i in range(20)]
    return {"rows": rows, "window_s": 20.0, "setup_s": 31.5,
            "traffic": {"slo": {"ttft_ms": 300, "gap_ms": 150}},
            "stats0": {"queue_wait_seconds_total": 1.0,
                       "prompt_tokens_total": 100,
                       "prefix_hit_tokens_total": 0},
            "stats1": {"queue_wait_seconds_total": 2.0,
                       "prompt_tokens_total": 1100,
                       "prefix_hit_tokens_total": 800},
            "pool_samples": [{"t": 1.0, "active": 100, "cached": 10,
                              "total": 400},
                             {"t": 2.0, "active": 300, "cached": 10,
                              "total": 400}],
            "engine": {"decode_steps": 4}, "trace_slice": [5.0, 10.0],
            "trace": {"busy_s": 4.0, "window_s": 5.0, "kernel_s": 0.5,
                      "modules": {
                          "decode_window": {"count": 10, "mean_s": 0.08,
                                            "total_s": 0.8},
                          "prefill_step": {"count": 4, "mean_s": 0.1,
                                           "total_s": 0.4}}}}


@pytest.mark.parametrize("name,want", [
    ("ttft_p95_ms", 1000 * (0.2 + 0.18)),
    # TTFT = 200 + 10 i ms for i = 0..19
    ("ttft_mean_ms", 295.0),
    ("kv_pool_fill_share", 50.0),
    ("chunk_gap_p99_ms", 200.0),
    ("tpot_p50_ms", 1000 * 0.3 / 8),
    ("output_tok_s", 12 * 20 / 20.0),
    ("setup_s", 31.5),
    ("gen_late_p95_ms", 1.0),
    ("queue_wait_ms_mean", 50.0),
    # TTFT <= 300 ms holds for i <= 10; every row has one 200 ms gap
    ("slo_met_share", 0.0),
    ("prefix_hit_share", 80.0),
    ("prefill_ms_mean", 100.0),
    ("window_ms_mean", 80.0),
    ("paged_attn_busy_share", 12.5),
    ("device_idle_share", 20.0),
    # chunks after the first, arriving in [5, 10]: rows 5..9 -> 2 x 4 each
    ("decode_rows_mean", 5 * 8 / (10 * 4)),
])
def test_reader(name, want):
    assert cells.load_reader(name)(_raw()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "decode_rows_mean", "prefill_ms_mean", "window_ms_mean",
    "paged_attn_busy_share", "device_idle_share"])
def test_a_trace_reader_with_nothing_to_read_returns_nothing(name):
    raw = dict(_raw(), trace=None)
    assert cells.load_reader(name)(raw) is None


def test_slo_share_counts_a_failed_request_as_a_miss():
    raw = _raw()
    raw["traffic"]["slo"] = {"ttft_ms": 1000, "gap_ms": 1000}
    assert cells.load_reader("slo_met_share")(raw) == 100.0
    raw["rows"][0]["status"] = 500
    assert cells.load_reader("slo_met_share")(raw) == 95.0
    # a request that never produced a token: no finite mean or tail
    raw["rows"][0]["chunk_s"] = []
    raw["rows"][1]["chunk_s"] = []
    assert cells.load_reader("ttft_p95_ms")(raw) is None
    assert cells.load_reader("ttft_mean_ms")(raw) is None
