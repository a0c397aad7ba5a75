"""What PR 54 added to the benchmark for a model whose KDA layers (64
heads, beta in (0, 2)) stand beside gated GQA layers without positions,
with the chip's share of its experts and of its vocabulary, on the CPU:
a ``tiny-solar`` configuration ADDED to a copy of the benchmark by files
alone (its reference is the repo's
``configs/solar-open2-250b/reference.py``, its traffic a small closed
loop) and run end to end through ``serve.agree``; the repo's own
configuration and cell against the catalog, against ``BENCHMARK.json``
and against the issue's traffic; ``harness/solar_work.py`` against a hand
count; the new readers on hand-made counters and a hand-made trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, counters, kda_work, roofline,
                               solar_work, ssd_work)

CELL = "tiny-solar.tiny-closed"
LIKE = "solar-open2-250b.long-reason"
NAME = "solar-open2-250b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_SOLAR = {
    "model_type": "solar_open2", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4,
                           "num_kv_heads": None},
    "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
    "n_routed_experts": 8, "router_num_experts": 16,
    "first_local_expert": 0, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-05, "tie_word_embeddings": False}
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
def sroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales, an embedding of unit RMS at
    this vocabulary), one traffic mix, one cell that reports what the
    repo's own Solar Open 2 cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_solar"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-solar"))
    _dump(os.path.join(bdir, "configs", "tiny-solar", "config.json"),
          TINY_SOLAR)
    _dump(os.path.join(bdir, "configs", "tiny-solar", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=22.6)})
    b["configs"].append({
        "name": "tiny-solar", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-solar/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-solar", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-solar",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_solar_cell_end_to_end(sroot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64, the state carried through the pool and K/V
    through the one attending layer's pages; windows on gathered rows;
    half of every token's expert pairs routed to experts that are not
    here) against the repo's plain reference given the same share, under
    the harness's one rule, and a closed-loop window with no failed
    request."""
    proc = _run(sroot, CELL, 0, seconds=4)
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


NEW = {"attn_gate_busy_share",
       # the conv tails' small ops: PERF.md section 5 names them the
       # first target of a perf_opt issue on the cell
       "kda_conv_busy_share",
       # accepted quantities under names of the cell's own: their
       # accepted entries' lists are pinned to one cell each by
       # test_bm_kimi_linear.py / test_bm_granite.py / test_bm_kanana.py,
       # and the KDA readers ask for kimi_linear's keys
       "kda_busy_share.long-reason", "kda_step_roofline.long-reason",
       "kda_chunk_roofline.long-reason",
       "state_carried_chunk_share.long-reason",
       "moe_held_pair_share.long-reason", "paged_attn_roofline.long-reason",
       "moe_shared_busy_share.long-reason"}
# the accepted quantities the cell is appended to
SHARED = {"moe_busy_share", "state_pool_fill_share", "output_tok_s.tpot",
          "paged_attn_busy_share"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position: the
    cell, its configuration and its entries ARE there, wherever."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert NEW | SHARED <= mine
    # every accepted metric without a ``workloads`` list is the cell's
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share", "chunk_gap_p99_ms",
            "host_step_busy_share", "step_gap_ms_mean", "warmup_s",
            "sampler_busy_share", "idle_no_work_share",
            "prefill_topup_share", "jit_trace_s"} <= mine
    # the accepted entries whose readers ask for another family's keys,
    # multiply by the depth, or whose lists accepted tests hold shut
    assert not {"kda_busy_share", "kda_step_roofline", "kda_chunk_roofline",
                "state_carried_chunk_share", "moe_held_pair_share",
                "moe_shared_busy_share", "paged_attn_roofline",
                "paged_attn_roofline.hybrid", "latent_attn_roofline",
                "latent_attn_busy_share", "ssm_busy_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [LIKE], m["name"]      # this cell's alone
            assert m["moves"] == "tpot_p50_ms" and m["unit"] == "%"
            assert os.path.isfile(os.path.join(
                BENCH, "metrics", m["name"] + ".py"))   # a file of its own
    for name in ("output_tok_s", "ttft_mean_ms"):
        assert LIKE not in next(m for m in bench["end_to_end"]
                                if m["name"] == name)["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == LIKE)
    assert entry["chips"] == 1 and entry["traffic"] == "long-reason"
    assert NAME in [c["name"] for c in bench["configs"]]
    # names test_bm_contract.py appends as a LATER configuration's
    assert not {"window_attn_busy_share", "window_pool_fill_share"} & {
        m["name"] for m in bench["per_layer"]
        if LIKE in m.get("workloads", [])}
    assert LIKE != "next-config.long-decode"


def test_the_cell_reports_its_readers_and_each_has_a_file(sroot):
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    assert {m["name"] for m in cells.metrics_for(CELL, "per_layer", sroot)} \
        == {m["name"] for m in cells.metrics_for(LIKE, "per_layer", ROOT)}


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the three keys ``reduced`` names and
    in nothing else, and states the share beside the published count;
    every width is as published; ``gqa_layers`` is kept whole."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Solar-Open2-250B")
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    share = {"router_num_experts", "first_local_expert"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(reduced) | share
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (48, 4)
    assert (published["n_routed_experts"], run["n_routed_experts"],
            run["router_num_experts"], run["first_local_expert"]) \
        == (320, 40, 320, 0)
    assert (published["vocab_size"], run["vocab_size"]) == (196608, 24576)
    # one whole period of the pattern: GQA, KDA, KDA, KDA
    assert run["gqa_layers"] == published["gqa_layers"]
    assert run["linear_attn_config"] == published["linear_attn_config"]
    assert solar_work.shapes(run) == {"heads": 64, "head_dim": 128,
                                      "layers": 3, "attending": 1}
    # the guide's floors: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary
    assert run["num_hidden_layers"] >= 4 and run["n_routed_experts"] >= 8
    assert run["vocab_size"] * 8 >= published["vocab_size"]
    assert (run["hidden_size"], run["moe_intermediate_size"],
            run["num_attention_heads"], run["num_key_value_heads"],
            run["head_dim"], run["num_experts_per_tok"],
            run["n_shared_experts"], run["routed_scaling_factor"]) == (
        4096, 1280, 64, 8, 128, 8, 1, 1)
    for key in ("assumed", "stands_for", "caveat", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    assert "8 chips share each layer" in about["stands_for"]
    assert "96" in about["stands_for"] and "25.6" in about["caveat"]
    assert about["memory"]["fits"] and about["memory"]["peak_gb"] < 15.75
    assert about["memory"]["resident_gb"] > 15.75 / 2
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
        NAME, "long-reason", 1) == (cell["config"], cell["traffic"],
                                    cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 128, 1024, 20261003)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 0.8, "min": 512, "max": 16384}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 17920 == 140 * e["page_size"] \
        <= cells.context_tokens(cell)
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 128
    assert e["max_prefill_batch"] in e["batch_buckets"]
    # nearly every prompt is longer than one prefill chunk: its state is
    # carried from chunk to chunk; a few pass 8,192
    from benchmark.harness import traffic

    lens = [r["prompt_len"] for r in traffic.schedule(t, 50)]
    assert sum(n > e["prefill_chunk"] for n in lens) > 0.9 * len(lens)
    assert 0.02 < sum(n > 8192 for n in lens) / len(lens) < 0.06
    assert 2600 < sum(lens) / len(lens) < 3000


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


SOLAR = {"num_hidden_layers": 4, "use_gqa_gate": True, "n_shared_experts": 1,
         "gqa_layers": [0, 4, 8, 12],
         "linear_attn_config": {"num_heads": 64, "head_dim": 128,
                                "num_kv_heads": None}}
SHAPES = {"heads": 64, "head_dim": 128, "layers": 3, "attending": 1}


def test_solar_work_by_hand():
    """Shapes from the configuration as it is run: of the first 4 layers
    layer 0 attends and 3 are KDA; under kimi_linear's keys the same
    counts, from 1. One row-step through one layer at 64 x 128 x 128
    reads and writes 2 x 4 MiB of float32 state; ISSUE 54's floor for a
    step of 128 rows: 3 layers x 8 MiB a row = 3.0 GiB at 819 GB/s."""
    assert solar_work.shapes(SOLAR) == SHAPES
    assert solar_work.shapes({"mamba_n_heads": 128}) is None
    assert solar_work.shapes({"linear_attn_config": {
        "kda_layers": [1], "num_heads": 4, "head_dim": 16}}) is None
    as_kimi = solar_work._kimi_keys(dict(SOLAR, num_hidden_layers=8))
    assert as_kimi["linear_attn_config"]["full_attn_layers"] == [1, 5]
    assert as_kimi["linear_attn_config"]["kda_layers"] == [2, 3, 4, 6, 7, 8]
    assert kda_work.kda_shapes(solar_work._kimi_keys(SOLAR)) == SHAPES
    _, bytes_ = kda_work.kda_decode(1, **dict(SHAPES, layers=1))
    assert bytes_ == 2 * 4 * 2 ** 20 + (5 * 8192 + 64) * 2
    ops, bytes_ = kda_work.kda_decode(128, **SHAPES)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(3.95e-3, rel=0.01)


STEP = ("jit(decode_window)/while/body/kda/kda.scan/jit(kda_step)/"
        "pallas_call:")
CHUNK = ("jit(prefill_step)/while/body/kda/kda.scan/jit(kda_chunk)/"
         "pallas_call:")
PROJ = "jit(decode_window)/while/body/kda/kda.proj/dot_general:"
CONV = "jit(decode_window)/while/body/kda/kda.conv/dynamic_update_slice:"
ATTN = ("jit(decode_window)/attn/"
        "jit(paged_attention_decode_layered)/pallas_call:")
GATE = "jit(decode_window)/attn/attn.gate/dot_general:"
SHARED_OP = "jit(decode_window)/while/body/moe/moe.shared/dot_general:"
EXPERTS = "jit(decode_window)/while/body/moe/moe.experts/dot_general:"
OPS = {1: "%kda_step.1 = (f32[128,1,8192], f32[129,3,128,8192]) "
          "custom-call()",
       2: "%kda_chunk.2 = (f32[8,512,8192], f32[8,128,8192]) custom-call()",
       3: "%fusion.3 = f32[128,24576]{1,0} fusion(bf16[128] %p)",
       4: "%paged_attention_decode_layered.4 = (f32[128,64,128]) "
          "custom-call()",
       5: "%fusion.5 = f32[128,8192]{1,0} fusion(bf16[128] %p)",
       6: "%fusion.6 = f32[128,1,1280]{2,1,0} fusion(bf16[128] %p)",
       7: "%fusion.7 = f32[128,1,40,1280]{3,2,1,0} fusion(bf16[128] %p)",
       8: "%fusion.8 = bf16[128,3,24576]{2,1,0} fusion(bf16[128] %p)"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the step kernel 0-200 (decode_window),
    the chunk kernel 200-360 (prefill_step), the mixer's projections
    360-380 and a conv tail's write 380-400, the GQA decode kernel
    400-500, the gate 500-550, the shared expert 550-600, the routed
    experts 600-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, STEP))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, CHUNK))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, ATTN))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, GATE))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, SHARED_OP))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(8, OPS[8], _int(1, 1) + _msg(5, CONV))
        + _line("XLA Ops", [(1, 0, 200), (2, 200, 160), (3, 360, 20),
                            (8, 380, 20), (4, 400, 100), (5, 500, 50),
                            (6, 550, 50), (7, 600, 400)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_solar")
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
    return {"trace": {"busy_s": 1000e-6, "kernel_s": 100e-6},
            "trace_slice": [10.0, 15.0], "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "stats0": {"prefill_tokens_total": 0,
                       "prefill_row_chunks_total": 10,
                       "prefill_row_chunks_carried_total": 4,
                       "moe_pairs_routed_total": 0,
                       "moe_pairs_held_total": 0},
            "stats1": {"prefill_tokens_total": 2560,
                       "prefill_row_chunks_total": 110,
                       "prefill_row_chunks_carried_total": 98,
                       "moe_pairs_routed_total": 800,
                       "moe_pairs_held_total": 104,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "num_heads": 64, "num_kv_heads": 8,
                      "head_dim": 128, "num_layers": 4, "page_size": 128,
                      "config": SOLAR}}


@pytest.fixture
def steered(traced, monkeypatch):
    """The accepted readers, which the variants load from the repo's
    root and which find their trace by their own file: steered to the
    hand-made one."""
    load = cells.load_reader

    def steer(name, root=ROOT):
        read = load(name, root)
        read.__globals__["__file__"] = traced
        return read

    monkeypatch.setattr(cells, "load_reader", steer)
    return steer


def test_the_three_roofline_readers_by_hand(steered):
    """``kda_step_roofline.long-reason``: 3 row-steps x 3 layers of 2 x 4
    MiB over the kernel's own 200 us; ``kda_chunk_roofline.long-reason``:
    256 prompt tokens over the 160 us under ``kda.scan`` in
    ``prefill_step``; ``paged_attn_roofline.long-reason``: the accepted
    reader's count at ONE layer where the configuration has four, a
    quarter of what the accepted reader reads. All silent for another
    configuration and an untraced run."""
    raw = _raw()
    assert ssd_work.decoded_row_steps(raw) == 3
    step, chunk, attn = (steered(n) for n in (
        "kda_step_roofline.long-reason", "kda_chunk_roofline.long-reason",
        "paged_attn_roofline.long-reason"))
    least = roofline.least_seconds(*kda_work.kda_decode(3, **SHAPES),
                                   "TPU v5 lite")
    assert step(raw) == pytest.approx(100.0 * least["seconds"] / 200e-6)
    least = roofline.least_seconds(*kda_work.kda_prefill(256, **SHAPES),
                                   "TPU v5 lite")
    assert chunk(raw) == pytest.approx(100.0 * least["seconds"] / 160e-6)
    ops, bytes_ = roofline.paged_attention_decode(
        [701, 702, 703], num_heads=64, num_kv_heads=8, head_dim=128,
        page_size=128, itemsize=2)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert attn(raw) == pytest.approx(100.0 * least["seconds"] / 100e-6)
    assert steered("paged_attn_roofline")(raw) == pytest.approx(
        4 * attn(raw))
    for read in (step, chunk, attn):
        assert 0 < read(raw) <= 100
        other = {**raw, "model": {**raw["model"],
                                  "config": {"mamba_n_heads": 128}}}
        assert read(other) is None
    for read in (step, chunk):
        assert read({**raw, "trace": None}) is None
    # the accepted KDA readers read nothing of this configuration's keys
    assert steered("kda_step_roofline")(raw) is None


def test_the_scope_and_counter_readers_by_hand(steered):
    """``kda_busy_share.long-reason``: both kernels, the projections and
    the conv tail = 400 of 1,000 us, of which ``kda_conv_busy_share`` 20;
    ``attn_gate_busy_share`` 50, the shared expert 50,
    the accepted ``moe_busy_share`` 450 and ``paged_attn_busy_share`` 100;
    98 - 4 of the window's 100 row-chunks started past position 0; 104 of
    800 pairs were held (13%); silent for a program without the counters
    or the scope (the parent) and for a configuration without the
    gate."""
    for name, want in (("kda_busy_share.long-reason", 40.0),
                       ("kda_conv_busy_share", 2.0),
                       ("attn_gate_busy_share", 5.0),
                       ("moe_shared_busy_share.long-reason", 5.0),
                       ("moe_busy_share", 45.0),
                       ("paged_attn_busy_share", 10.0),
                       ("state_carried_chunk_share.long-reason", 94.0),
                       ("moe_held_pair_share.long-reason", 13.0)):
        assert steered(name)(_raw()) == pytest.approx(want), name
    bare = {**_raw(), "stats0": {}, "stats1": {}}
    for name in ("state_carried_chunk_share.long-reason",
                 "moe_held_pair_share.long-reason", "attn_gate_busy_share",
                 "kda_busy_share.long-reason", "kda_conv_busy_share"):
        assert steered(name)(bare) is None, name
    ungated = {**_raw(), "model": {"config": {"gqa_layers": [0]}}}
    assert steered("attn_gate_busy_share")(ungated) is None
    assert steered("kda_busy_share.long-reason")(
        {**_raw(), "model": {"config": {}}}) is None
