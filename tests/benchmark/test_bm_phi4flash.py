"""What PR 63 added to the benchmark for a decoder-hybrid-decoder (Mamba-1
and differential window attention, ONE full-attention layer whose K/V
pages the cross layers read, gated memory units; a sequence that owns a
state slot AND pages of two pools), on the CPU: a ``tiny-phi4flash``
configuration ADDED to a copy of the benchmark by files alone (its
reference is the repo's ``configs/phi-4-mini-flash-reasoning/
reference.py``, its traffic a small closed loop) and run end to end
through ``serve.agree``; the repo's own configuration and cell against
the catalog, against ``BENCHMARK.json`` and against the issue's traffic;
``harness/sambay_work.py`` against a hand count; the new readers on
hand-made counters and a hand-made trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, counters, roofline, sambay_work,
                               ssm_work, window_attn_work)

CELL = "tiny-phi4flash.tiny-closed"
LIKE = "phi-4-mini-flash-reasoning.long-think"
NAME = "phi-4-mini-flash-reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "Phi-4-mini-flash-reasoning"
TINY = {
    "model_type": "phi4flash", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 12,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "sliding_window": 32, "layer_norm_eps": 1e-05, "mb_per_layer": 2,
    "tie_word_embeddings": False, "mlp_bias": False, "lm_head_bias": False,
    "embd_pdrop": 0, "resid_pdrop": 0, "mamba_d_state": 8,
    "mamba_dt_rank": 4, "max_position_embeddings": 2048}
ENGINE = {"page_size": 16, "num_pages": 64, "max_batch": 4,
          "batch_buckets": [4], "prefill_chunk": 64,
          "prefill_buckets": [64], "page_buckets": [8],
          "max_prefill_batch": 4, "warmup_logprobs": False}
TRAFFIC = {"loop": "closed", "clients": 3, "pool": 64, "base_seed": 1,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 90},
           "output_len": {"dist": "uniform", "min": 6, "max": 14}}


def _about() -> dict:
    with open(os.path.join(BENCH, "configs", NAME, "about.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def proot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales, an embedding of unit RMS at
    this width), one traffic mix, one cell that reports what the repo's
    own cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_phi4flash"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-phi4flash"))
    _dump(os.path.join(bdir, "configs", "tiny-phi4flash", "config.json"),
          TINY)
    _dump(os.path.join(bdir, "configs", "tiny-phi4flash", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=22.6)})
    b["configs"].append({
        "name": "tiny-phi4flash", "source": "test", "reduced": [],
        "why": "test",
        "file": "benchmark/configs/tiny-phi4flash/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-phi4flash", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-phi4flash",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_phi4flash_cell_end_to_end(proot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64, past the window of 32: state carried in the
    slot, window pages given back, the cross half on one position a row;
    windows over both pools) against the repo's plain reference under
    the harness's one rule, and a closed-loop window with no failed
    request."""
    proc = _run(proot, CELL, 0, seconds=4)
    line = _last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    agree = next(n for n in notes if n["note"] == "agree")
    assert agree["positions"] == 27 and agree["ok"]
    assert next(n for n in notes if n["note"] == "correct")[
        "post_warmup_compiles"] == 0


def test_a_traced_run_reads_every_counter_metric_then_is_refused(proot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    return None by their own rule, none raises, and the run is refused
    as no measurement."""
    proc = _run(proot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


NEW = {"cross_attn_busy_share", "shared_kv_attn_roofline", "gmu_busy_share",
       "prefill_cross_rows_share", "attn_diff_busy_share",
       # accepted quantities under names of the cell's own: their
       # accepted entries' lists are pinned to one cell each by
       # test_bm_smallthinker.py / test_bm_jamba.py
       "attn_window_busy_share.long-think",
       "window_attn_roofline.long-think",
       "kv_window_pool_fill_share.long-think",
       "ssm_scan_roofline.long-think"}
# the accepted quantities the cell is appended to
SHARED = {"ssm_busy_share", "state_pool_fill_share",
          "paged_attn_busy_share", "output_tok_s.tpot"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert NEW | SHARED <= mine
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share", "chunk_gap_p99_ms",
            "host_step_busy_share", "step_gap_ms_mean", "warmup_s",
            "sampler_busy_share", "idle_no_work_share"} <= mine
    # no experts; and the accepted attention roofline counts one layer a
    # pool slice, where here eight layers read one
    assert "moe_busy_share" not in mine
    assert not {m for m in mine if m.startswith("paged_attn_roofline")}
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [LIKE], m["name"]      # this cell's
            assert m["moves"] == "tpot_p50_ms"
    for name in ("output_tok_s", "ttft_mean_ms"):
        assert LIKE not in next(m for m in bench["end_to_end"]
                                if m["name"] == name)["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == LIKE)
    assert entry["chips"] == 1
    assert "8 layers a step" in entry["why"]
    assert NAME in [c["name"] for c in bench["configs"]]


def test_the_cell_reports_its_readers():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    for name in NEW:        # a file of its own each, not the quantity's
        assert cells.reader_path(name, ROOT).endswith(name + ".py")
    assert len(NEW) <= 9


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_one_named_key():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in ``tie_word_embeddings`` and in nothing
    else: every layer, every width and the whole vocabulary are held."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CATALOG_NAME)
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    assert about["reduced"] == ["tie_word_embeddings"]
    assert set(about["reduced_why"]) == {"tie_word_embeddings"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == {"tie_word_embeddings"}
    assert (published["tie_word_embeddings"], run["tie_word_embeddings"]) \
        == (True, False)
    assert (run["num_hidden_layers"], run["hidden_size"],
            run["intermediate_size"], run["num_attention_heads"],
            run["num_key_value_heads"], run["sliding_window"],
            run["vocab_size"], run["mb_per_layer"]) == (
                32, 2560, 10240, 40, 20, 512, 200064, 2)
    for key in ("assumed", "stands_for", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    for key in ("mamba_sizes", "layer_order", "attention_biases",
                "differential_attention", "no_positions",
                "residual_and_state_dtype", "leaf_names"):
        assert about["assumed"][key], key
    assert "zeros" in about["assumed"]["attention_biases"].lower()
    assert "WHOLE" in about["reduced_why"]["tie_word_embeddings"]
    mem = about["memory"]
    assert mem["fits"] and mem["peak_gb"] < 15.75
    # a quarter of one chip's memory, by what is resident alone
    assert mem["resident_gb"] > 0.25 * 15.75
    # ONE layer's K and V a token of context: 5 KiB, not 40
    e = cell["engine"]
    assert mem["kv_pool_gb"] * 2 ** 30 / (e["num_pages"] * e["page_size"]) \
        == 5 * 1024
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == about["source"]
    assert entry["reduced"] == about["reduced"]


def test_the_cells_three_places_agree_and_the_traffic_is_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == LIKE)
    cell = cells.load_cell(LIKE, ROOT)          # refuses a disagreement
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "long-think", 1) == (cell["config"], cell["traffic"],
                                   cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"]) == ("closed", 48, 1024)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 6144,
                               "sigma": 0.7, "min": 1024, "max": 24576}
    assert t["output_len"] == {"dist": "uniform", "min": 384, "max": 1152}
    seeds = set()
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            seeds.add((json.load(f)["base_seed"], name))
    assert [s for s, n in seeds].count(t["base_seed"]) == 1     # its own
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 25728
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 48
    assert e["prefill_chunk"] == 512 and e["page_size"] == 64
    assert e["page_buckets"][-1] * e["page_size"] >= longest
    assert e["max_prefill_batch"] in e["batch_buckets"]
    # a window AND at most one prefill program an iteration
    assert e["prefill_token_budget"] \
        == e["max_prefill_batch"] * e["prefill_chunk"]
    assert set(cell["engine_why"]) >= set(e)
    # the window pool: rows x the table's slots (window + chunk, in
    # pages, + 1) + the page padding reads
    slots = -(-(512 + e["prefill_chunk"]) // e["page_size"]) + 1
    assert e["window_pages"] == e["max_batch"] * slots + 1 == 817


def test_reference_imports_nothing_of_the_programs_models():
    with open(os.path.join(BENCH, "configs", NAME, "reference.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "dynamo_tpu" not in code and "pallas" not in code
    assert "import jax" in code and "lax.scan" in code
    assert "Departures from the published description" in src
    ref = cells.load_reference({
        "reference_file": os.path.join(BENCH, "configs", NAME,
                                       "reference.py"), "config": NAME})
    assert callable(ref.reference_logits) and callable(ref.layer)
    assert {"window_ignored", "lam_fixed", "a2_from_k1"} <= set(ref.FAULTS)


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


PHI = {"model_type": "phi4flash", "num_hidden_layers": 32,
       "hidden_size": 2560, "num_attention_heads": 40,
       "num_key_value_heads": 20, "sliding_window": 512}


def test_sambay_work_by_hand():
    """The kinds by the family's rule; and the issue's count of the
    shared read: 48 rows at 8,100 tokens of context read 127 pages of 64
    tokens x 5 KiB, once a READING layer, 8 a step: 1.99 GB a reading
    layer, 15.9 GB a step, 19.5 ms at 819 GB/s; bound by the bytes."""
    found = sambay_work.shapes(PHI)
    assert [found[k] for k in ("mamba", "window", "cross", "gmu",
                               "readers")] == [9, 8, 7, 7, 8]
    assert (found["heads"], found["kv_heads"], found["head_dim"],
            found["size"], found["d_inner"], found["d_state"]) == (
                40, 20, 64, 512, 5120, 16)
    assert found["kinds"][16:20] == ["mamba", "full", "gmu", "cross"]
    assert sambay_work.shapes({"model_type": "jamba"}) is None
    assert sambay_work.shapes({}) is None
    shape = dict(in_buffer=4, num_heads=40, num_kv_heads=20, head_dim=64,
                 page_size=64)
    ops, bytes_ = sambay_work.shared_kv_decode([8100] * 48, readers=8,
                                               **shape)
    pages = -(-(8100 - 4) // 64)
    assert pages == 127
    assert bytes_ == 8 * 48 * (2 * pages * 64 * 20 * 64 + 3 * 40 * 64) * 2
    assert bytes_ == pytest.approx(15.98e9, rel=0.01)
    assert ops == 8 * 48 * 6 * 40 * 64 * (8100 - 4)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(19.5e-3, rel=0.01)
    # one reading layer is an eighth; a context inside the buffer reads
    # q and o alone
    one = sambay_work.shared_kv_decode([8100] * 48, readers=1, **shape)
    assert (8 * one[0], 8 * one[1]) == (ops, bytes_)
    assert sambay_work.shared_kv_decode([3], readers=1, **shape) == (
        0.0, 3 * 40 * 64 * 2)


def test_the_counter_readers_by_hand():
    cross = _reader("prefill_cross_rows_share")
    fill = _reader("kv_window_pool_fill_share.long-think")
    raw = {"model": {"config": PHI},
           "stats0": {"self_rows_total": 512, "cross_rows_total": 1,
                      "kv_window_pages_held_total": 100,
                      "kv_window_pages_seen_total": 816},
           "stats1": {"self_rows_total": 512 * 101, "cross_rows_total": 101,
                      "kv_window_pages_held_total": 100 + 408 * 50,
                      "kv_window_pages_seen_total": 816 * 51}}
    assert cross(raw) == pytest.approx(100.0 / 512)
    assert fill(raw) == pytest.approx(50.0)
    # the parent's program (no counters), and another family's run
    assert cross({**raw, "stats0": {}, "stats1": {}}) is None
    assert fill({**raw, "stats0": {}, "stats1": {}}) is None
    assert fill({**raw, "model": {"config": {"model_type": "jamba"}}}) \
        is None


W = "jit(decode_window)/while/body/"
KERNEL = "paged_attention_decode_layered/pallas_call:"
CROSS = W + "attn/attn.cross/" + KERNEL
CROSS_MERGE = W + "attn/attn.cross/exp:"
FULL = "jit(decode_window)/attn/attn.full/" + KERNEL
WIN = W + "attn/attn.window/" + KERNEL
DIFF = W + "attn/attn.diff/subtract:"
GMU = W + "gmu/dot_general:"
SCAN = W + "ssm/ssm.scan/jit(selective_scan_step)/pallas_call:"
SCAN_P = "jit(prefill_step)/while/body/ssm/ssm.scan/while/body/multiply:"
MLP = W + "mlp/dot_general:"
OPS = {1: "%paged_attention_decode_layered.1 = (f32[48,40,128]) "
          "custom-call()",
       2: "%fusion.2 = f32[48,10,4,4]{3,2,1,0} fusion(f32[48] %p)",
       3: "%paged_attention_decode_layered.3 = (f32[48,40,128]) "
          "custom-call()",
       4: "%paged_attention_decode_layered.4 = (f32[48,40,128]) "
          "custom-call()",
       5: "%fusion.5 = f32[48,1,20,128]{3,2,1,0} fusion(f32[48] %p)",
       6: "%fusion.6 = f32[48,1,5120]{2,1,0} fusion(bf16[48] %p)",
       7: "%selective_scan_step.7 = (f32[48,5120], f32[49,9,16,5120]) "
          "custom-call()",
       8: "%fusion.8 = f32[1,16,16,5120]{3,2,1,0} fusion(f32[1] %p)",
       9: "%fusion.9 = bf16[48,1,10240]{2,1,0} fusion(bf16[48] %p)"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the cross layers' kernel 0-300 and their
    merge 300-350, the full layer's kernel 350-400, the window layers'
    kernel 400-500, the subtraction and pair norm 500-540, the memory
    units 540-600, the scan step 600-650 (decode_window), the chunk scan
    650-750 (prefill_step), the MLPs 750-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, CROSS))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, CROSS_MERGE))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, FULL))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, WIN))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, DIFF))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, GMU))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, SCAN))
        + _event_meta(8, OPS[8], _int(1, 1) + _msg(5, SCAN_P))
        + _event_meta(9, OPS[9], _int(1, 1) + _msg(5, MLP))
        + _line("XLA Ops", [(1, 0, 300), (2, 300, 50), (3, 350, 50),
                            (4, 400, 100), (5, 500, 40), (6, 540, 60),
                            (7, 600, 50), (8, 650, 100), (9, 750, 250)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_phi4flash")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


def _raw():
    """One client row: a prompt of 1,000 tokens whose tokens 1-3 arrive
    inside the slice (token 0 came from prefill): three decode row-steps
    at contexts 1,001-1,003."""
    rows = [{"prompt_len": 1000, "chunk_s": [11.0, 12.0, 29.0],
             "chunk_n": [2, 2, 4]}]
    return {"trace": {"busy_s": 1000e-6, "modules": {
                "prefill_step": {"count": 2, "mean_s": 50e-6},
                "decode_window": {"count": 1, "mean_s": 900e-6}}},
            "trace_slice": [10.0, 15.0],
            "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "engine": {"decode_steps": 4},
            "stats0": {"prefill_tokens_total": 0,
                       "prefill_dispatches_total": 0},
            "stats1": {"prefill_tokens_total": 2560,
                       "prefill_dispatches_total": 20,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "page_size": 64, "num_heads": 40,
                      "num_kv_heads": 20, "head_dim": 64, "config": PHI}}


def _at(read, traced, monkeypatch):
    """The reader looking for the trace beside the hand-made root."""
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    return read


def test_the_scope_readers_by_hand(traced, monkeypatch):
    """``cross_attn_busy_share`` 35% (kernel and merge), ``gmu_busy_share``
    6%, ``attn_diff_busy_share`` 4%; the window layers' 10% through the
    accepted reader; the accepted ``ssm`` reader finds the module's
    scopes. Silent for another family, an untraced run, a program
    without the phases."""
    raw = _raw()
    for name, want in (("cross_attn_busy_share", 35.0),
                       ("gmu_busy_share", 6.0),
                       ("attn_diff_busy_share", 4.0),
                       ("ssm_busy_share", 15.0)):
        read = _at(_reader(name), traced, monkeypatch)
        assert read(raw) == pytest.approx(want), name
    for name in ("cross_attn_busy_share", "gmu_busy_share",
                 "attn_diff_busy_share"):
        read = _at(_reader(name), traced, monkeypatch)
        assert read({**raw, "model": {**raw["model"], "config": {
            "model_type": "jamba"}}}) is None
        assert read({**raw, "stats1": {}}) is None
    window = _at(_reader("attn_window_busy_share"), traced, monkeypatch)
    assert window(raw) is None      # the accepted reader asks for its keys
    mine = _reader("attn_window_busy_share.long-think")
    monkeypatch.setattr(cells, "load_reader", lambda name, root=ROOT: {
        "attn_window_busy_share": window}[name])
    assert mine(raw) == pytest.approx(10.0)


def test_the_three_roofline_readers_by_hand(traced, monkeypatch):
    """``shared_kv_attn_roofline``: three row-steps' pages of the ONE
    layer, 8 reading layers, over the 350 us of the kernel under
    ``attn.full`` + ``attn.cross`` (the merge's 50 us are XLA's, not the
    kernel's); ``window_attn_roofline.long-think``: the same rows'
    window pages in 8 layers over the 100 us under ``attn.window``,
    through the accepted reader; ``ssm_scan_roofline.long-think``: three
    row-steps x 9 layers of state and the vectors of the 2 prefill
    programs IN the slice x 128 live tokens a dispatch over the 150 us
    under ``ssm.scan`` (none where the slice held no prefill program). All under 100%; silent for another
    configuration and an untraced run."""
    raw = _raw()
    shared = _at(_reader("shared_kv_attn_roofline"), traced, monkeypatch)
    monkeypatch.setitem(window_attn_work.__dict__, "__file__", traced)
    ops, bytes_ = sambay_work.shared_kv_decode(
        [1001, 1002, 1003], readers=8, in_buffer=4, num_heads=40,
        num_kv_heads=20, head_dim=64, page_size=64)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert shared(raw) == pytest.approx(100.0 * least["seconds"] / 350e-6)
    assert 0 < shared(raw) <= 100
    accepted = _at(_reader("window_attn_roofline"), traced, monkeypatch)
    assert accepted(raw) is None
    mine = _reader("window_attn_roofline.long-think")
    monkeypatch.setattr(cells, "load_reader",
                        lambda name, root=ROOT: accepted)
    ops, bytes_ = window_attn_work.attention_decode(
        [1001, 1002, 1003], window=512, in_buffer=4, num_heads=40,
        num_kv_heads=20, head_dim=64, page_size=64)
    least = roofline.least_seconds(8 * ops, 8 * bytes_, "TPU v5 lite")
    assert mine(raw) == pytest.approx(100.0 * least["seconds"] / 100e-6)
    assert 0 < mine(raw) <= 100
    monkeypatch.undo()
    scan = _at(_reader("ssm_scan_roofline.long-think"), traced, monkeypatch)
    shape = dict(d_inner=5120, d_state=16, layers=9, itemsize=2)
    d_ops, d_bytes = ssm_work.selective_scan_decode(3, **shape)
    p_ops, p_bytes = ssm_work.selective_scan_prefill(256, **shape)
    least = roofline.least_seconds(d_ops + p_ops, d_bytes + p_bytes,
                                   "TPU v5 lite")
    assert scan(raw) == pytest.approx(100.0 * least["seconds"] / 150e-6)
    assert 0 < scan(raw) <= 100
    none = {**raw, "trace": {"busy_s": 1000e-6, "modules": {
        "decode_window": {"count": 1, "mean_s": 900e-6}}}}
    least = roofline.least_seconds(d_ops, d_bytes, "TPU v5 lite")
    assert scan(none) == pytest.approx(100.0 * least["seconds"] / 150e-6)
    other = {**raw, "model": {**raw["model"],
                              "config": {"model_type": "jamba",
                                         "mamba_d_state": 16}}}
    for read in (scan, _at(_reader("shared_kv_attn_roofline"), traced,
                           monkeypatch)):
        assert read(other) is None
        assert read({**raw, "trace": None}) is None
