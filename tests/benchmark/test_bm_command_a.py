"""What PR 56 added to the benchmark for a model whose layers are a
parallel block under one LayerNorm, with window layers (interleaved
RoPE) beside full layers without positions on a K/V pool a kind, and the
chip's share of sigmoid-routed experts beside averaged shared experts,
on the CPU: a ``tiny-command-a`` configuration ADDED to a copy of the
benchmark by files alone (its reference is the repo's
``configs/command-a-plus-05-2026/reference.py``, its traffic a small
closed loop past its window) and run end to end through ``serve.agree``;
the repo's own configuration and cell against the catalog, against
``BENCHMARK.json`` and against the issue's traffic;
``harness/cohere_work.py`` against a hand count; the new readers on
hand-made counters and a hand-made trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, cohere_work, counters, roofline,
                               window_attn_work)

CELL = "tiny-command-a.tiny-closed-long"
LIKE = "command-a-plus-05-2026.rag-long"
NAME = "command-a-plus-05-2026"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = {
    "model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 32, "rope_theta": 10000.0, "rotary_pct": 1,
    "position_embedding_type": "rope_gptj", "layer_norm_eps": 1e-05,
    "use_parallel_block": True, "use_qk_norm": False,
    "first_k_dense_replace": 0, "num_experts": 8, "router_num_experts": 16,
    "first_local_expert": 0, "num_experts_per_tok": 4,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "num_shared_experts": 2, "shared_expert_combination_strategy": "average",
    "hidden_act": "silu", "use_gated_activation": True, "logit_scale": 1,
    "tie_word_embeddings": False}
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
def croot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales, an embedding of unit RMS at
    this vocabulary), one traffic mix, one cell that reports what the
    repo's own Command A+ cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_command_a"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-command-a"))
    _dump(os.path.join(bdir, "configs", "tiny-command-a", "config.json"),
          TINY)
    _dump(os.path.join(bdir, "configs", "tiny-command-a", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=22.6)})
    b["configs"].append({
        "name": "tiny-command-a", "source": "test", "reduced": [],
        "why": "test",
        "file": "benchmark/configs/tiny-command-a/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed-long.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-command-a", "traffic": "tiny-closed-long",
        "chips": 1, "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-command-a",
                           "traffic": "tiny-closed-long", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_command_a_cell_end_to_end(croot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64 and up to three times the window of 32, so
    window-pool pages are given back; half of every token's expert pairs
    routed to experts that are not here) against the repo's plain
    reference given the same share, under the harness's one rule, and a
    closed-loop window with no failed request."""
    proc = _run(croot, CELL, 0, seconds=4)
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


NEW = {"attn_proj_busy_share",
       # accepted quantities under names of the cell's own: their
       # accepted entries' lists are pinned to one cell each by
       # test_bm_smallthinker.py / test_bm_granite.py / test_bm_kanana.py,
       # and their readers ask for another family's keys
       "attn_window_busy_share.rag-long", "window_attn_roofline.rag-long",
       "full_attn_roofline.rag-long", "kv_window_pool_fill_share.rag-long",
       "window_pages_released_share.rag-long",
       "rows_past_window_share.rag-long", "moe_held_pair_share.rag-long",
       "moe_shared_busy_share.rag-long"}
# the accepted quantities the cell is appended to
SHARED = {"moe_busy_share", "paged_attn_busy_share", "output_tok_s.tpot"}


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
    # the accepted entries whose lists accepted tests hold shut, or
    # whose readers ask for another family's keys
    assert not {"attn_window_busy_share", "window_attn_roofline",
                "full_attn_roofline", "kv_window_pool_fill_share",
                "window_pages_released_share", "rows_past_window_share",
                "moe_held_pair_share", "moe_shared_busy_share",
                "paged_attn_roofline", "state_pool_fill_share",
                "ssm_busy_share", "kda_busy_share"} & mine
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
    assert entry["chips"] == 1 and entry["traffic"] == "rag-long"
    assert NAME in [c["name"] for c in bench["configs"]]
    # names test_bm_contract.py appends as a LATER configuration's
    assert not {"window_attn_busy_share", "window_pool_fill_share"} & {
        m["name"] for m in bench["per_layer"]
        if LIKE in m.get("workloads", [])}
    assert LIKE != "next-config.long-decode"


def test_the_cell_reports_its_readers_and_each_has_a_file(croot):
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    assert {m["name"] for m in cells.metrics_for(CELL, "per_layer", croot)} \
        == {m["name"] for m in cells.metrics_for(LIKE, "per_layer", ROOT)}


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the four keys ``reduced`` names and
    in nothing else, and states the share beside the published count;
    every width is as published; ``layer_types`` is kept whole."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == NAME)
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "num_experts", "vocab_size",
               "tie_word_embeddings"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    beside = set(run["assumed"]) | {"assumed"}
    assert beside == {"router_num_experts", "first_local_expert",
                      "torch_dtype", "architectures", "assumed"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(reduced) | beside
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (32, 4)
    assert (published["num_experts"], run["num_experts"],
            run["router_num_experts"], run["first_local_expert"]) \
        == (128, 16, 128, 0)
    assert (published["vocab_size"], run["vocab_size"]) == (262144, 32768)
    assert (published["tie_word_embeddings"], run["tie_word_embeddings"]) \
        == (True, False)
    # one whole period of the pattern: window, window, window, full
    assert run["layer_types"] == published["layer_types"]
    assert run["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    # the guide's floors: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary
    assert run["num_hidden_layers"] >= 4 and run["num_experts"] >= 8
    assert run["vocab_size"] * 8 >= published["vocab_size"]
    assert (run["hidden_size"], run["intermediate_size"],
            run["num_attention_heads"], run["num_key_value_heads"],
            run["head_dim"], run["sliding_window"],
            run["num_experts_per_tok"], run["num_shared_experts"]) == (
        4096, 4096, 128, 8, 128, 4096, 8, 4)
    for key in ("assumed", "stands_for", "caveat", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    assert "average" in about["assumed"] and "vision_tower" in \
        about["assumed"]
    assert "(routed + " in about["assumed"]["average"]
    assert "8 chips share each layer" in about["stands_for"]
    assert "64" in about["stands_for"]
    assert "2 pairs" in about["caveat"] and "4 of 32" in about["caveat"]
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
        NAME, "rag-long", 1) == (cell["config"], cell["traffic"],
                                 cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 32, 1024, 20261003)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.7, "min": 2048, "max": 32768}
    assert t["output_len"] == {"dist": "uniform", "min": 384, "max": 1152}
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 33920 == 530 * e["page_size"] \
        <= cells.context_tokens(cell)
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 32
    assert e["max_prefill_batch"] in e["batch_buckets"]
    assert set(cell["engine_why"]) == set(e)
    # most prompts are past the window on arrival, a sixth past 16,384
    from benchmark.harness import traffic

    lens = [r["prompt_len"] for r in traffic.schedule(t, 50)]
    window = cell["model_config"]["sliding_window"]
    assert 0.78 < sum(n > window for n in lens) / len(lens) < 0.90
    assert 0.10 < sum(n > 16384 for n in lens) / len(lens) < 0.22
    assert 9400 < sum(lens) / len(lens) < 11000


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
    assert len(ref.CONTROLS) == 8


# ---------------------------------------------------------- the readers


COHERE = {"num_hidden_layers": 4, "use_parallel_block": True,
          "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
          "sliding_window": 4096, "num_attention_heads": 128,
          "num_key_value_heads": 8, "head_dim": 128, "num_experts": 16,
          "router_num_experts": 128, "num_shared_experts": 4}


def test_cohere_work_by_hand():
    """Shapes from the configuration as it is run: of the first 4 layers
    three are held to the window and one sees everything; under
    SmallThinker's keys the same counts. One row at a context of 10,000
    with 4 steps in the buffer: a window layer's kernel reads the 64
    pages that intersect (5,904, 9,996), the full layer's the 157 pages
    of [0, 9,996), 2 x 8 KV heads x 64 x 128 x 2 B = 256 KiB a page."""
    found = cohere_work.shapes(COHERE)
    assert found == {"window": 3, "full": 1, "size": 4096,
                     "layout": [1, 1, 1, 0], "heads": 128, "kv_heads": 8,
                     "head_dim": 128, "experts_held": 16,
                     "router_width": 128, "shared_experts": 4}
    assert cohere_work.shapes(dict(COHERE, num_hidden_layers=8))[
        "full"] == 2
    assert cohere_work.shapes({"sliding_window_layout": [0, 1],
                               "num_hidden_layers": 2}) is None
    assert cohere_work.shapes({"layer_types": ["conv", "full_attention"],
                               "num_hidden_layers": 2,
                               "use_parallel_block": True}) is None
    keys = cohere_work._accepted_keys(COHERE, found)
    assert window_attn_work.layers_of(keys) == {"window": 3, "full": 1,
                                                "size": 4096}
    assert window_attn_work.layers_of(COHERE) is None
    assert keys["n_shared_experts"] == 4
    shape = dict(in_buffer=4, num_heads=128, num_kv_heads=8, head_dim=128,
                 page_size=64)
    _, win = window_attn_work.attention_decode([10000], window=4096, **shape)
    _, full = window_attn_work.attention_decode([10000], window=None,
                                                **shape)
    page = 2 * 8 * 64 * 128 * 2
    q_out = 2 * 128 * 128 * 2
    assert win == (9996 // 64 + 1 - 5904 // 64) * page + q_out
    assert win == 65 * page + q_out
    assert full == 157 * page + q_out
    # ISSUE 56's estimate of a step's pages at a mean context of 10.5k
    # and 32 rows: the full layer ~41 MiB a row, three window layers
    # ~49 MiB; 2.8 GiB a step
    _, w = window_attn_work.attention_decode([10500] * 32, window=4096,
                                             **shape)
    _, f = window_attn_work.attention_decode([10500] * 32, window=None,
                                             **shape)
    assert (3 * w + f) / 2 ** 30 == pytest.approx(2.8, abs=0.1)


WIN = ("jit(decode_window)/while/body/attn/attn.window/"
       "jit(paged_attention_decode_layered)/pallas_call:")
FULL = ("jit(decode_window)/while/body/attn/attn.full/"
        "jit(paged_attention_decode_layered)/pallas_call:")
MERGE = "jit(decode_window)/while/body/attn/attn.window/reduce:"
PROJ = "jit(decode_window)/while/body/attn/attn.proj/dot_general:"
PROJ_P = "jit(prefill_step)/while/body/attn/attn.proj/dot_general:"
SHARED_OP = "jit(decode_window)/while/body/moe/moe.shared/dot_general:"
EXPERTS = "jit(decode_window)/while/body/moe/moe.experts/dot_general:"
OPS = {1: "%paged_attention_decode_layered.1 = (f32[32,128,128]) "
          "custom-call()",
       2: "%paged_attention_decode_layered.2 = (f32[32,128,128]) "
          "custom-call()",
       3: "%fusion.3 = f32[32,128,128]{2,1,0} fusion(bf16[32] %p)",
       4: "%fusion.4 = bf16[32,16384]{1,0} fusion(bf16[32] %p)",
       5: "%fusion.5 = bf16[4096,16384]{1,0} fusion(bf16[32] %p)",
       6: "%fusion.6 = bf16[32,1,16384]{2,1,0} fusion(bf16[32] %p)",
       7: "%fusion.7 = f32[32,1,16,4096]{3,2,1,0} fusion(bf16[32] %p)"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the decode kernel under ``attn.window``
    0-250 and the merge with the window's buffer 250-270, the kernel
    under ``attn.full`` 270-470, the projections 470-570 (a window) and
    570-600 (a prefill), the shared experts 600-700, the routed experts
    700-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, WIN))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, FULL))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, MERGE))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, PROJ_P))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, SHARED_OP))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, EXPERTS))
        + _line("XLA Ops", [(1, 0, 250), (3, 250, 20), (2, 270, 200),
                            (4, 470, 100), (5, 570, 30), (6, 600, 100),
                            (7, 700, 300)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_command_a")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


def _raw():
    """Three tokens after a first arrive inside the slice (of 2 + 4 that
    the row's chunks in it hold, one is the request's first), at
    contexts of 10,001-10,003."""
    rows = [{"prompt_len": 10000, "chunk_s": [11.0, 12.0, 29.0],
             "chunk_n": [2, 2, 4]}]
    return {"trace": {"busy_s": 1000e-6, "kernel_s": 450e-6},
            "trace_slice": [10.0, 15.0], "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "engine": {"decode_steps": 4},
            "stats0": {"moe_pairs_routed_total": 0,
                       "moe_pairs_held_total": 0,
                       "kv_window_pages_held_total": 10,
                       "kv_window_pages_seen_total": 100,
                       "kv_window_pages_released_total": 5,
                       "kv_window_pages_allocated_total": 10,
                       "decode_row_steps_past_window_total": 0,
                       "decode_row_steps_total": 0},
            "stats1": {"moe_pairs_routed_total": 800,
                       "moe_pairs_held_total": 104,
                       "kv_window_pages_held_total": 1510,
                       "kv_window_pages_seen_total": 2100,
                       "kv_window_pages_released_total": 85,
                       "kv_window_pages_allocated_total": 110,
                       "decode_row_steps_past_window_total": 97,
                       "decode_row_steps_total": 100,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "num_heads": 128, "num_kv_heads": 8,
                      "head_dim": 128, "num_layers": 4, "page_size": 64,
                      "config": COHERE}}


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


def test_the_two_roofline_readers_by_hand(steered):
    """``window_attn_roofline.rag-long``: three row-steps x three window
    layers of the pages that intersect each row's window over the
    kernel's 250 us under ``attn.window`` (the merge is not the
    kernel's); ``full_attn_roofline.rag-long``: the same row-steps x one
    layer of the whole context over the 200 us under ``attn.full``. Both
    between 0 and 100%, silent for another configuration, an untraced
    run, and (the accepted readers) for this configuration's keys."""
    raw = _raw()
    win, full = (steered(n) for n in ("window_attn_roofline.rag-long",
                                      "full_attn_roofline.rag-long"))
    shape = dict(in_buffer=4, num_heads=128, num_kv_heads=8, head_dim=128,
                 page_size=64, itemsize=2)
    contexts = [10001, 10002, 10003]
    assert window_attn_work.decode_contexts(raw) == contexts
    ops, bytes_ = window_attn_work.attention_decode(contexts, window=4096,
                                                    **shape)
    least = roofline.least_seconds(3 * ops, 3 * bytes_, "TPU v5 lite")
    assert win(raw) == pytest.approx(100.0 * least["seconds"] / 250e-6)
    ops, bytes_ = window_attn_work.attention_decode(contexts, window=None,
                                                    **shape)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert full(raw) == pytest.approx(100.0 * least["seconds"] / 200e-6)
    for read in (win, full):
        assert 0 < read(raw) <= 100
        other = {**raw, "model": {**raw["model"],
                                  "config": {"mamba_n_heads": 128}}}
        assert read(other) is None
        assert read({**raw, "trace": None}) is None
    assert steered("window_attn_roofline")(raw) is None
    assert steered("full_attn_roofline")(raw) is None


def test_the_scope_and_counter_readers_by_hand(steered):
    """``attn_proj_busy_share``: the projections of the window and of the
    prefill, 130 of 1,000 us; ``attn_window_busy_share.rag-long``: the
    kernel and the merge, 270; the shared experts 100; the accepted
    ``moe_busy_share`` 400 and ``paged_attn_busy_share`` 450; 104 of 800
    pairs held (13%); the window pool held 1,500 of 2,000 pages seen
    (75%), 80 of 100 pages handed out were given back as the rows ran,
    97 of 100 row-steps were past the window; silent for a program
    without the counters or the scopes (the parent) and for another
    configuration."""
    for name, want in (("attn_proj_busy_share", 13.0),
                       ("attn_window_busy_share.rag-long", 27.0),
                       ("moe_shared_busy_share.rag-long", 10.0),
                       ("moe_busy_share", 40.0),
                       ("paged_attn_busy_share", 45.0),
                       ("moe_held_pair_share.rag-long", 13.0),
                       ("kv_window_pool_fill_share.rag-long", 75.0),
                       ("window_pages_released_share.rag-long", 80.0),
                       ("rows_past_window_share.rag-long", 97.0)):
        assert steered(name)(_raw()) == pytest.approx(want), name
    bare = {**_raw(), "stats0": {}, "stats1": {}}
    other = {**_raw(), "model": {"config": {"sliding_window_layout": [0, 1],
                                            "sliding_window_size": 8,
                                            "num_hidden_layers": 2}}}
    for name in NEW:
        assert steered(name)(bare) is None, name
        assert steered(name)(other) is None, name
    # the accepted readers of the pinned entries read nothing of this
    # configuration's keys where they ask for a family's
    assert steered("attn_window_busy_share")(_raw()) is None
    assert steered("moe_shared_busy_share")(_raw()) is None
