"""What PR 27 added to the benchmark for a model with recurrent state,
on the CPU: a ``tiny-jamba`` configuration ADDED to a copy of the
benchmark by files alone (its reference is the repo's
``configs/jamba2-3b/reference.py``) and run end to end; the readers of
the scopes ``ssm`` / ``ssm.scan`` and of the state pool on a hand-made
trace and hand-made counters, each number counted by hand; the
operation-and-byte functions on hand-worked cases."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import (TINY_ENGINE, TRAFFIC, _dump, _last_line,  # noqa: F401
                         _run)
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import cells, counters, scope_ops, ssm_work

CELL = "tiny-jamba.tiny-closed"
LIKE = "jamba2-3b.reason-decode"
# one whole period of 14 layers (attention at layer 7), d_state 16,
# d_conv 4, as tests/test_jamba.py; served in bf16 like the cells
TINY_JAMBA = {
    "model_type": "jamba", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 14,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 8, "attn_layer_period": 14, "attn_layer_offset": 7,
    "num_experts": 1, "num_experts_per_tok": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": False,
    "max_position_embeddings": 2048}
# the scales of configs/jamba2-3b/about.json at this size: A_log is drawn
# with std 1/sqrt(d_inner), so 24x at d_inner 128 is the std of 2.1 that
# 150x gives at 5,120; embed at unit rms (sqrt(512)); the projections
# that write to the residual stream at 1/sqrt(2 x 14 layers)
ABOUT = {"reference": "benchmark/configs/jamba2-3b/reference.py",
         "weight_scales": {"A_log": 24.0, "d_skip": 5.0, "embed": 22.6,
                           "w_out": 0.19, "w_down": 0.19, "wo": 0.19}}


@pytest.fixture(scope="module")
def jroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration, one traffic mix, one cell that reports what the
    repo's own Jamba cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_jamba"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-jamba"))
    _dump(os.path.join(bdir, "configs", "tiny-jamba", "config.json"),
          TINY_JAMBA)
    _dump(os.path.join(bdir, "configs", "tiny-jamba", "about.json"), ABOUT)
    b["configs"].append({
        "name": "tiny-jamba", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-jamba/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"),
          TRAFFIC["tiny-closed"])
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-jamba", "traffic": "tiny-closed", "chips": 1,
        "engine": TINY_ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-jamba",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_jamba_cell_end_to_end(jroot):
    """``correct`` true on the CPU: the engine (bf16, state pool, window
    of 4) against the repo's plain Jamba reference under the harness's
    one rule, and a closed-loop window with no failed request."""
    proc = _run(jroot, CELL, 0, seconds=3)
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] >= 3
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p50_ms", "output_tok_s",
                                    "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    agree = next(n for n in notes if n["note"] == "agree")
    assert agree["positions"] == 27 and agree["ok"]
    assert next(n for n in notes if n["note"] == "correct")[
        "post_warmup_compiles"] == 0


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert {"ssm_busy_share", "ssm_scan_busy_share", "ssm_scan_roofline",
            "state_pool_fill_share", "paged_attn_roofline.hybrid",
            "host_step_busy_share", "warmup_s"} <= mine
    assert not {"paged_attn_roofline", "paged_attn_busy_share",
                "moe_busy_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "output_tok_s", "setup_s"}
    # the cell's alone: the scan's two readers and its own roofline
    for m in bench["per_layer"]:
        if m["name"] in ("ssm_scan_busy_share", "ssm_scan_roofline",
                         "paged_attn_roofline.hybrid"):
            assert m["workloads"] == [LIKE], m["name"]


def test_the_tiny_jamba_cell_reports_what_the_jamba_cell_reports(jroot):
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    per_layer = {m["name"] for m in cells.metrics_for(CELL, "per_layer",
                                                      jroot)}
    assert per_layer == {m["name"] for m in cells.metrics_for(
        LIKE, "per_layer", ROOT)}
    # a variant without a file of its own is read by its quantity's
    assert cells.reader_path("warmup_s.reason-decode", jroot).endswith(
        os.path.join("metrics", "warmup_s.py"))
    assert cells.reader_path("paged_attn_roofline.hybrid", jroot).endswith(
        "paged_attn_roofline.hybrid.py")


def test_a_traced_run_on_the_cpu_is_refused_after_the_readers_ran(jroot):
    """No /device:TPU plane on the CPU: every trace reader of the cell
    (``ssm_*`` among them) returns None by its own rule and none raises;
    the run is then refused as no measurement."""
    proc = _run(jroot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


# ------------------------------------ the readers, on a hand-made trace

SCAN = "jit(decode_window)/ssm/ssm.scan/mul:"
SCAN_LOOP = "jit(prefill_step)/while/body/ssm/ssm.scan/while/body/add:"
PROJ = "jit(decode_window)/while/body/ssm/ssm.proj/dot_general:"
ATTN = "jit(decode_window)/attn/dot_general:"
OPS = {1: "%fusion.1 = f32[128,16,5120]{2,1,0} fusion(f32[128] %p)",
       2: "%fusion.2 = f32[8,16,16,5120]{3,2,1,0} fusion(f32[8] %p)",
       3: "%fusion.3 = bf16[128,10240]{1,0} fusion(bf16[128] %p)",
       4: "%fusion.4 = bf16[128,2560]{1,0} fusion(bf16[128] %p)",
       5: "%copy.5 = f32[26,128,16,5120]{3,2,1,0} copy(f32[26] %s)",
       6: "%while.6 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: scan 0-300 and (inside a prefill's
    loop) 300-400, projections 400-600, attention 600-700, an unscoped
    copy of the rows' state 700-1000; a while spans 300-400. Placed as
    the newest traced run under a root, beside a reader's path there."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, SCAN))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, SCAN_LOOP))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, ATTN))
        + _event_meta(5, OPS[5])
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, SCAN_LOOP))
        + _line("XLA Ops", [(1, 0, 300), (6, 300, 100), (2, 300, 100),
                            (3, 400, 200), (4, 600, 100), (5, 700, 300)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


STATS = {"stats1": {counters.PHASES_KEY: {"idle": 1.0}}}
RAW = {"trace": {"busy_s": 1000e-6}, **STATS}


def test_seconds_and_share_of_a_scope_host_trace_does_not_list(traced):
    us = 1e-6
    assert scope_ops.path_seconds(RAW, "ssm", traced) == \
        pytest.approx(600 * us)         # scan + projections, no container
    assert scope_ops.path_seconds(RAW, "ssm.scan", traced) == \
        pytest.approx(400 * us)
    assert scope_ops.path_seconds(RAW, "ssm.conv", traced) == 0.0
    assert scope_ops.path_share(RAW, "ssm", traced) == pytest.approx(60.0)
    assert scope_ops.path_share(RAW, "ssm.scan", traced) == \
        pytest.approx(40.0)
    # a component is matched whole: "ssm" is not "ssm.scan"
    assert scope_ops.path_seconds(RAW, "scan", traced) == 0.0


def test_the_scope_readers_guards(traced, tmp_path):
    # not traced; another run's trace (its busy time differs); a program
    # without the phases in its stats(); no trace under the reader's root
    assert scope_ops.path_share({"trace": None, **STATS}, "ssm",
                                traced) is None
    assert scope_ops.path_share({"trace": {"busy_s": 2.0}, **STATS},
                                "ssm", traced) is None
    assert scope_ops.path_share({"trace": RAW["trace"], "stats1": {}},
                                "ssm", traced) is None
    assert scope_ops.path_share(
        RAW, "ssm", str(tmp_path / "benchmark" / "metrics" / "m.py")) is None


def _reader(name):
    return cells.load_reader(name, ROOT)


def test_the_busy_share_readers_call_the_helper(traced, monkeypatch):
    """``read(raw)`` of the two files, with the files' own ``__file__``
    swapped for the reader path beside the hand-made trace."""
    for name, want in (("ssm_busy_share", 60.0),
                       ("ssm_scan_busy_share", 40.0)):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read(RAW) == pytest.approx(want)
        assert read({"trace": None, **STATS}) is None


JAMBA = {"num_hidden_layers": 28, "attn_layer_offset": 7,
         "attn_layer_period": 14, "hidden_size": 2560, "mamba_expand": 2,
         "mamba_d_state": 16}


def test_operations_and_bytes_of_the_scan_by_hand():
    # d_inner 4 x d_state 2 = 8 elements; 5 layers; bf16 vectors
    ops, bytes_ = ssm_work.selective_scan_decode(
        3, d_inner=4, d_state=2, layers=5, itemsize=2)
    assert ops == 3 * 5 * (6 * 8 + 2 * 4) == 840
    # state read + written in float32 (2 x 8 x 4) + x, dt, y (3 x 4) and
    # B, C (2 x 2) in bf16
    assert bytes_ == 3 * 5 * (64 + 16 * 2) == 1440
    ops, bytes_ = ssm_work.selective_scan_prefill(
        10, d_inner=4, d_state=2, layers=5, itemsize=2)
    assert ops == 10 * 5 * 56 == 2800
    assert bytes_ == 10 * 5 * 32 == 1600     # no state traffic in a chunk
    assert ssm_work.mamba_shapes(JAMBA) == {
        "d_inner": 5120, "d_state": 16, "layers": 26}
    # the cell's own numbers: one row-step moves 26 x 655 KB of state
    _, per_step = ssm_work.selective_scan_decode(
        1, itemsize=2, **ssm_work.mamba_shapes(JAMBA))
    assert per_step == 26 * (2 * 5120 * 16 * 4 + (3 * 5120 + 32) * 2)


def _rows():
    """Two requests: 3 + 2 + 2 tokens at 1.0 / 2.0 / 9.0 s (the first
    chunk holds token 0, from prefill), and 1 + 4 at 2.5 / 3.0 s."""
    return [{"prompt_len": 100, "chunk_s": [1.0, 2.0, 9.0],
             "chunk_n": [3, 2, 2]},
            {"prompt_len": 50, "chunk_s": [2.5, 3.0], "chunk_n": [1, 4]}]


def test_ssm_scan_roofline_by_hand(traced, monkeypatch):
    read = _reader("ssm_scan_roofline")
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    raw = {**RAW, "trace_slice": [1.5, 3.5], "window_s": 10.0,
           "rows": _rows(), "device": {"kind": "TPU v5 lite"},
           "model": {"config": JAMBA, "kv_itemsize": 2},
           "stats0": {"prefill_tokens_total": 1000},
           "stats1": {**STATS["stats1"], "prefill_tokens_total": 1500}}
    # in the slice: request 1's chunk at 2.0 (2 tokens), request 2's at
    # 2.5 (token 0: from prefill, not counted) and 3.0 (4) = 6 row-steps;
    # 500 prompt tokens x 2 s / 10 s = 100
    _, d_bytes = ssm_work.selective_scan_decode(
        6, itemsize=2, **ssm_work.mamba_shapes(JAMBA))
    _, p_bytes = ssm_work.selective_scan_prefill(
        100, itemsize=2, **ssm_work.mamba_shapes(JAMBA))
    least = (d_bytes + p_bytes) / 819e9       # memory-bound
    assert read(raw) == pytest.approx(100.0 * least / 400e-6)
    assert 0 < read(raw) < 100
    # no trace; a model without Mamba layers
    assert read({**raw, "trace": None}) is None
    assert read({**raw, "model": {"config": {"num_hidden_layers": 3},
                                  "kv_itemsize": 2}}) is None


def test_state_pool_fill_share_by_hand():
    read = _reader("state_pool_fill_share")
    raw = {"stats0": {"state_slots_held_total": 5,
                      "state_slots_seen_total": 40},
           "stats1": {"state_slots_held_total": 65,
                      "state_slots_seen_total": 120}}
    assert read(raw) == pytest.approx(75.0)     # 60 slots held of 80 seen
    # a program without a state pool; a window without a decode dispatch
    assert read({"stats0": {}, "stats1": {}}) is None
    assert read({"stats0": raw["stats0"], "stats1": raw["stats0"]}) is None


def test_paged_attn_roofline_hybrid_counts_the_attending_layers():
    """Against the accepted reader on the same raw material: the same
    share with 2 layers in place of 28."""
    plain, hybrid = (_reader("paged_attn_roofline"),
                     _reader("paged_attn_roofline.hybrid"))
    raw = {"trace": {"kernel_s": 2e-3}, "trace_slice": [1.5, 3.5],
           "rows": _rows(), "device": {"kind": "TPU v5 lite"},
           "model": {"num_layers": 28, "num_heads": 20, "num_kv_heads": 1,
                     "head_dim": 128, "page_size": 64, "kv_itemsize": 2,
                     "config": JAMBA}}
    assert hybrid(raw) == pytest.approx(plain(raw) * 2 / 28)
    assert hybrid({**raw, "trace": None}) is None
    assert hybrid({**raw, "model": {**raw["model"], "config": {}}}) is None
