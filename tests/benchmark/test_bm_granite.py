"""What PR 40 added to the benchmark for a model with Mamba-2 mixers and
the chip's share of a layer's experts, on the CPU: a ``tiny-granite``
configuration ADDED to a copy of the benchmark by files alone (its
reference is the repo's ``configs/granite-4.0-h-small/reference.py``,
its traffic a small closed loop) and run end to end through
``serve.agree``; the repo's own configuration and cell against the
catalog, against ``BENCHMARK.json`` and against the issue's traffic; the
work functions against a hand count; the new readers on hand-made
counters and a hand-made trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import cells, counters, roofline, ssd_work

CELL = "tiny-granite.tiny-closed"
LIKE = "granite-4.0-h-small.rag-decode"
NAME = "granite-4.0-h-small"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_GRANITE = {
    "model_type": "granitemoehybrid", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 10,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "num_local_experts": 6,
    "router_num_experts": 12, "first_local_expert": 0,
    "num_experts_per_tok": 3, "shared_intermediate_size": 48,
    "embedding_multiplier": 12, "attention_multiplier": 0.0625,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope", "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "max_position_embeddings": 2048}
# the cell's scales; embed x (sqrt(512) / 12) = unit RMS after the
# multiplier at this vocabulary
ABOUT = {"reference": f"benchmark/configs/{NAME}/reference.py",
         "weight_scales": {"A_log": 6.3, "d_skip": 3.0, "embed": 1.886,
                           "lm_head": 16.0, "w_router": 2.0}}
ENGINE = {"page_size": 16, "num_pages": 64, "max_batch": 4,
          "batch_buckets": [4], "prefill_chunk": 64,
          "prefill_buckets": [64], "page_buckets": [8],
          "max_prefill_batch": 4, "warmup_logprobs": False}
TRAFFIC = {"loop": "closed", "clients": 3, "pool": 64, "base_seed": 1,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 90},
           "output_len": {"dist": "uniform", "min": 6, "max": 14}}


@pytest.fixture(scope="module")
def groot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration, one traffic mix, one cell that reports what the
    repo's own granite cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_granite"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-granite"))
    _dump(os.path.join(bdir, "configs", "tiny-granite", "config.json"),
          TINY_GRANITE)
    _dump(os.path.join(bdir, "configs", "tiny-granite", "about.json"), ABOUT)
    b["configs"].append({
        "name": "tiny-granite", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-granite/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-granite", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-granite",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_granite_cell_end_to_end(groot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64 = sixteen scan chunks of 8, the state carried
    through the pool; windows on gathered rows; half of every token's
    expert pairs routed to experts that are not here) against the repo's
    plain reference given the same share, under the harness's one rule,
    and a closed-loop window with no failed request."""
    proc = _run(groot, CELL, 0, seconds=4)
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


def test_a_traced_run_reads_every_counter_metric_then_is_refused(groot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    (``ssd_step_roofline``, ``ssd_chunk_roofline`` among them) return
    None by their own rule, none raises, and the run is refused as no
    measurement."""
    proc = _run(groot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


NEW = {"ssd_step_roofline", "ssd_chunk_roofline", "moe_held_pair_share"}
# the accepted quantities listed beside them: ``<quantity>.rag-decode``
# until PR 45, now the quantity's one entry (the rate: ``.tpot``)
SHARED = {"ssm_busy_share", "moe_busy_share", "output_tok_s.tpot"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position: the
    cell, its configuration and its entries ARE there, wherever."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert NEW | SHARED <= mine
    # every accepted metric without a ``workloads`` list is the cell's
    # too: since PR 45 the fourteen host, slot, idle and thread readers
    # among them, which the cap of 128 had kept from it
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share", "chunk_gap_p99_ms",
            "host_step_busy_share", "step_gap_ms_mean", "warmup_s",
            "sampler_busy_share", "idle_no_work_share"} <= mine
    assert not {"paged_attn_roofline", "paged_attn_busy_share",
                "ssm_scan_roofline", "state_pool_fill_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            # this cell's alone
            assert m["workloads"] == [LIKE], m["name"]
            assert m["moves"] == "tpot_p50_ms"
    # the rate is a per-layer line (`output_tok_s.tpot`), not the
    # end-to-end entry: a new bounded metric in a cell is a re-rating
    for name in ("output_tok_s", "ttft_mean_ms"):
        assert LIKE not in next(m for m in bench["end_to_end"]
                                if m["name"] == name)["workloads"]
    assert LIKE in [w["name"] for w in bench["workloads"]]
    assert NAME in [c["name"] for c in bench["configs"]]


def test_the_cell_reports_what_fits_under_the_benchmarks_cap():
    """Until PR 45 the cell listed six of the twenty-six readers ISSUE 40
    names (the cap of 128 was reached); now its three new readers, the
    three accepted quantities its ``.rag-decode`` variants stood for, and
    every entry without a ``workloads`` list."""
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    assert cells.reader_path("output_tok_s.tpot", ROOT).endswith(
        "output_tok_s.py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the three keys ``reduced`` names and
    in nothing else, and states the share beside the published count;
    every width is as published."""
    cell = cells.load_cell(LIKE, ROOT)
    with open(os.path.join(cell["model_path"], "about.json")) as f:
        about = json.load(f)
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == NAME)
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "num_local_experts",
               "tie_word_embeddings"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    share = {"router_num_experts", "first_local_expert"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(reduced) | share
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (40, 10)
    assert (published["num_local_experts"], run["num_local_experts"],
            run["router_num_experts"], run["first_local_expert"]) \
        == (72, 36, 72, 0)
    assert published["tie_word_embeddings"] and \
        not run["tie_word_embeddings"]
    # one whole period of the pattern, the attending layer at index 5
    kinds = run["layer_types"][:run["num_hidden_layers"]]
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert kinds == run["layer_types"][10:20] == run["layer_types"][30:40]
    # the guide's floors: a whole period and four layers, 8 experts
    assert run["num_hidden_layers"] >= 4 and run["num_local_experts"] >= 8
    assert (run["hidden_size"], run["mamba_n_heads"], run["mamba_d_head"],
            run["mamba_d_state"], run["mamba_d_conv"],
            run["mamba_chunk_size"], run["num_attention_heads"],
            run["num_key_value_heads"], run["intermediate_size"],
            run["num_experts_per_tok"], run["shared_intermediate_size"],
            run["vocab_size"]) == (4096, 128, 64, 128, 4, 256, 32, 8, 768,
                                   10, 1536, 100352)
    assert (run["embedding_multiplier"], run["attention_multiplier"],
            run["residual_multiplier"], run["logits_scaling"]) == (
                12, 0.0078125, 0.22, 16)
    for key in ("assumed", "stands_for", "caveat", "memory", "reference",
                "weight_scales"):
        assert about[key], key
    assert "2 chips share each layer" in about["stands_for"]
    assert about["memory"]["fits"] and about["memory"]["peak_gb"] < 15.75
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
        NAME, "rag-decode", 1) == (cell["config"], cell["traffic"],
                                   cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 64, 1024, 20260927)
    assert "shared_prefix" not in t
    assert (t["prompt_len"]["min"], t["prompt_len"]["max"]) == (512, 2048)
    assert (t["output_len"]["min"], t["output_len"]["max"]) == (384, 1152)
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 3200 < 4096               # the mix keeps its context
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 64
    assert e["page_buckets"][-1] * 64 >= longest
    assert e["num_pages"] >= e["max_batch"] * -(-longest // 64)
    assert e["max_prefill_batch"] in e["batch_buckets"]


def test_reference_imports_nothing_of_the_programs_models():
    with open(os.path.join(BENCH, "configs", NAME, "reference.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "dynamo_tpu" not in code and "pallas" not in code
    assert "import jax" in code and "lax.scan" in code
    ref = cells.load_reference({
        "reference_file": os.path.join(BENCH, "configs", NAME,
                                       "reference.py"), "config": NAME})
    assert callable(ref.reference_logits) and callable(ref.layer)


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


GRANITE = {"mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
           "num_hidden_layers": 10,
           "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}
SHAPES = {"heads": 128, "head_dim": 64, "d_state": 128, "layers": 9}


def test_ssd_work_by_hand():
    """One row-step through one layer at 128 x 64 x 128: 5 operations an
    element of the 1,048,576-element state + 2 a channel for the skip;
    the float32 state read and written (2 x 4 MiB) + x in, y out, dt, B
    and C in bf16. A prompt token: the same operations, the vectors
    only. Shapes from the configuration as it is run: 9 of the first 10
    layers are Mamba-2 layers."""
    assert ssd_work.mamba2_shapes(GRANITE) == SHAPES
    assert ssd_work.mamba2_shapes({"mamba_d_state": 16}) is None
    one = dict(SHAPES, layers=1)
    ops, bytes_ = ssd_work.ssd_decode(1, **one)
    assert ops == 5 * 128 * 64 * 128 + 2 * 128 * 64
    assert bytes_ == 2 * 4 * 2 ** 20 + (2 * 8192 + 128 + 2 * 128) * 2
    p_ops, p_bytes = ssd_work.ssd_prefill(1, **one)
    assert p_ops == ops and p_bytes == (2 * 8192 + 128 + 2 * 128) * 2
    # ISSUE 40's floor: 64 rows x 9 layers x 4 MiB x 2 at 819 GB/s
    ops, bytes_ = ssd_work.ssd_decode(64, **SHAPES)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(5.9e-3, rel=0.01)


def test_moe_held_pair_share_by_hand():
    read = _reader("moe_held_pair_share")
    raw = {"stats0": {"moe_pairs_routed_total": 1000,
                      "moe_pairs_held_total": 520},
           "stats1": {"moe_pairs_routed_total": 7400,
                      "moe_pairs_held_total": 3720}}
    assert read(raw) == pytest.approx(50.0)
    # a program without the counters (the parent; a model whose experts
    # are all here), and a window in which nothing was routed
    assert read({"stats0": {}, "stats1": {}}) is None
    assert read({"stats0": raw["stats0"], "stats1": raw["stats0"]}) is None


STEP = ("jit(decode_window)/while/body/ssm/ssm.scan/jit(ssd_step)/"
        "pallas_call:")
STEP_PREP = "jit(decode_window)/while/body/ssm/ssm.scan/exp:"
CHUNK = "jit(prefill_step)/while/body/ssm/ssm.scan/while/body/dot_general:"
PROJ = "jit(decode_window)/while/body/ssm/ssm.proj/dot_general:"
EXPERTS = "jit(decode_window)/while/body/moe/moe.experts/dot_general:"
OPS = {1: "%ssd_step.1 = (f32[64,1,8192], f32[65,9,128,8192]) custom-call()",
       2: "%fusion.2 = f32[64,8192]{1,0} fusion(f32[64,128] %p)",
       3: "%fusion.3 = f32[4,256,128,64]{3,2,1,0} fusion(f32[4] %p)",
       4: "%fusion.4 = f32[64,16768]{1,0} fusion(bf16[64] %p)",
       5: "%fusion.5 = f32[64,1,36,768]{3,2,1,0} fusion(bf16[64] %p)",
       6: "%while.6 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the step kernel 0-300 and the ops that
    make its operands 300-340 (decode_window), the chunked scan inside
    its loop 340-540 (prefill_step; the while that spans it is a
    container), the mixer's projections 540-700, the experts 700-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, STEP))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, STEP_PREP))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, CHUNK))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, CHUNK))
        + _line("XLA Ops", [(1, 0, 300), (2, 300, 40), (6, 340, 200),
                            (3, 340, 200), (4, 540, 160), (5, 700, 300)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_granite")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


def _raw():
    """Three tokens after a first arrive inside the slice (of 2 + 4 that
    the row's chunks in it hold, one is the request's first); 2,560
    prompt tokens over a 50 s window of which the slice is 5 s: 256."""
    rows = [{"prompt_len": 700, "chunk_s": [11.0, 12.0, 29.0],
             "chunk_n": [2, 2, 4]}]
    return {"trace": {"busy_s": 1000e-6}, "trace_slice": [10.0, 15.0],
            "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "stats0": {"prefill_tokens_total": 0},
            "stats1": {"prefill_tokens_total": 2560,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "config": GRANITE}}


def test_the_two_roofline_readers_by_hand(traced, monkeypatch):
    """``ssd_step_roofline``: 3 row-steps x 9 layers of state read and
    written over the 340 us under ``ssm.scan`` in ``decode_window``;
    ``ssd_chunk_roofline``: 256 prompt tokens' vectors over the 200 us
    under ``ssm.scan`` in ``prefill_step``: one scope, told apart by the
    program. Both silent for another configuration, an untraced run and
    a program without the phases."""
    raw = _raw()
    assert ssd_work.decoded_row_steps(raw) == 3
    step, chunk = _reader("ssd_step_roofline"), _reader("ssd_chunk_roofline")
    for read in (step, chunk):
        monkeypatch.setitem(read.__globals__, "__file__", traced)
    assert ssd_work.scope_seconds_in(raw, "ssm.scan", "decode_window",
                                     traced) == pytest.approx(340e-6)
    assert ssd_work.scope_seconds_in(raw, "ssm.scan", "prefill_step",
                                     traced) == pytest.approx(200e-6)
    least = roofline.least_seconds(*ssd_work.ssd_decode(3, **SHAPES),
                                   "TPU v5 lite")
    assert step(raw) == pytest.approx(100.0 * least["seconds"] / 340e-6)
    least = roofline.least_seconds(*ssd_work.ssd_prefill(256, **SHAPES),
                                   "TPU v5 lite")
    assert chunk(raw) == pytest.approx(100.0 * least["seconds"] / 200e-6)
    assert 0 < step(raw) <= 100 and 0 < chunk(raw) <= 100
    other = {**raw, "model": {**raw["model"],
                              "config": {"mamba_d_state": 16}}}
    for read in (step, chunk):
        assert read(other) is None
        assert read({**raw, "trace": None}) is None
        assert read({**raw, "stats1": {"prefill_tokens_total": 2560}}) \
            is None


def test_the_shared_scope_readers_see_the_new_module(traced, monkeypatch):
    """The accepted readers find the module's scopes: ``ssm`` = kernel
    + its operands + the chunked scan + the projections, ``moe`` the
    experts."""
    for name, want in (("ssm_busy_share", 70.0),
                       ("moe_busy_share", 30.0)):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read(_raw()) == pytest.approx(want), name
