"""What PR 46 added to the benchmark for a model whose layers differ in
what they may see (window layers with a K/V pool of their own), on the
CPU: the repo's own configuration and cell against the catalog, against
``BENCHMARK.json`` and against the issue's traffic; the plain reference
at a tiny preset against the model module; the work file against a hand
count; the six new readers on hand-made counters and a hand-made
trace."""

import json
import os

import numpy as np
import pytest

from bm_paths import BENCH, ROOT
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, counters, roofline, traffic,
                               window_attn_work)

LIKE = "smallthinker-21b-a3b.mixed-length"
NAME = "smallthinker-21b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"attn_window_busy_share", "window_attn_roofline",
       "full_attn_roofline", "kv_window_pool_fill_share",
       "window_pages_released_share", "rows_past_window_share"}
SHARED = {"moe_busy_share", "paged_attn_busy_share", "output_tok_s.tpot"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert NEW | SHARED <= mine
    # every accepted metric without a ``workloads`` list is the cell's
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share", "chunk_gap_p99_ms",
            "host_step_busy_share", "step_gap_ms_mean", "warmup_s",
            "sampler_busy_share", "idle_no_work_share"} <= mine
    # its count assumes that every layer reads the whole context
    assert not {"paged_attn_roofline", "state_pool_fill_share",
                "prefix_hit_share", "ssm_busy_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [LIKE], m["name"]
            assert m["moves"] == "tpot_p50_ms" and m["unit"] == "%"
    for name in ("output_tok_s", "ttft_mean_ms"):
        assert LIKE not in next(m for m in bench["end_to_end"]
                                if m["name"] == name)["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == LIKE)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "mixed-length", 1)
    assert NAME in [c["name"] for c in bench["configs"]]
    assert not [w["name"] for w in bench["workloads"] if w["chips"] != 1]


def test_the_cell_reports_the_six_new_and_every_listless_entry():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    for name in NEW:        # each has a reader file of its own
        assert cells.reader_path(name, ROOT).endswith(name + ".py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_depth():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in ``num_hidden_layers`` and in nothing
    else (both layouts whole, all 52 entries: the first 8 run), and adds
    only the keys it names as assumed."""
    cell = cells.load_cell(LIKE, ROOT)
    with open(os.path.join(cell["model_path"], "about.json")) as f:
        about = json.load(f)
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    assert about["reduced"] == ["num_hidden_layers"]
    assert set(about["reduced_why"]) == {"num_hidden_layers"}
    assert {k for k in published if run.get(k) != published[k]} \
        == {"num_hidden_layers"}
    assert set(run) - set(published) == set(run["assumed"]) | {"assumed"}
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (52, 8)
    layout = run["sliding_window_layout"]
    assert layout == run["rope_layout"] == [0, 1, 1, 1] * 13
    assert layout[:8] == [0, 1, 1, 1] * 2       # two whole periods
    assert (run["hidden_size"], run["num_attention_heads"],
            run["num_key_value_heads"], run["head_dim"],
            run["moe_num_primary_experts"],
            run["moe_num_active_primary_experts"],
            run["moe_ffn_hidden_size"], run["vocab_size"],
            run["sliding_window_size"], run["max_position_embeddings"]) \
        == (2560, 28, 4, 128, 64, 6, 768, 151936, 4096, 16384)
    assert (run["model_type"], run["hidden_act"]) == ("smallthinker", "relu")
    for key in ("model_type", "router_input", "hidden_act",
                "secondary_experts", "tokenizer", "weights"):
        assert about["assumed"][key], key
    for key in ("stands_for", "caveat", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    assert about["memory"]["fits"] and about["memory"]["peak_gb"] < 15.75
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == about["source"]
    assert entry["reduced"] == about["reduced"]


def test_the_cells_three_places_agree_and_the_traffic_is_the_issues():
    cell = cells.load_cell(LIKE, ROOT)          # refuses a disagreement
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 48, 1024, 20261001)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096,
                               "sigma": 0.8, "min": 512, "max": 12288}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    sched = traffic.schedule(t, 50)
    prompts = [r["prompt_len"] for r in sched]
    # half of the prompts are past the window on arrival, 8% at the clip
    assert 0.49 < sum(p > 4096 for p in prompts) / len(prompts) < 0.51
    assert 0.07 < sum(p == 12288 for p in prompts) / len(prompts) < 0.10
    longest = max(r["prompt_len"] + r["output_len"] for r in sched)
    assert longest < cells.context_tokens(cell) == 13888 < 16384
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 48
    assert e["page_size"] if "page_size" in e else True
    assert (e["prefill_chunk"], e["page_buckets"]) == (512, [217])
    # every row at the most it can hold of the window layers' pool
    assert e["window_pages"] >= 48 * 73 + 1
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


def test_the_reference_is_the_model_module_at_a_tiny_preset():
    """One prompt of three windows through the model module's own
    ``prefill_step`` (both pools, the window layers' table counted from
    the row's first page) against the reference's last row, float32."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(dict(
        model_type="smallthinker", vocab_size=512, hidden_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
        sliding_window_size=16, sliding_window_layout=[0, 1, 1, 1] * 2,
        rope_layout=[0, 1, 1, 1] * 2, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False))
    cfg.dtype = "float32"
    params = weights.build_tree(llama, cfg, weights.seed_key(3),
                                {"embed": 22.6, "wq": 2.0, "wk": 2.0})
    ref = cells.load_reference(cells.load_cell(LIKE, ROOT))
    T, ps = 48, 4
    toks = np.random.default_rng(0).integers(1, 500, T)
    kv = llama.init_kv_cache(cfg, llama.KVCacheSpec(16, ps))
    wkv = llama.init_window_kv_cache(cfg, llama.KVCacheSpec(16, ps))
    pages = np.arange(1, 13, dtype=np.int32)        # 12 pages of 4
    pos = np.arange(T, dtype=np.int32)
    prefill, _ = llama.make_step_fns(cfg)
    logits, *_ = prefill(
        params, jnp.asarray(toks[None], jnp.int32), jnp.asarray(pos[None]),
        *kv, jnp.asarray(pages[None]),
        jnp.asarray((pages[pos // ps] * ps + pos % ps)[None]),
        jnp.asarray([T - 1], jnp.int32), jnp.asarray(pages[None]),
        wkv, (jnp.asarray(pages[None]), jnp.zeros(1, jnp.int32),
              jnp.asarray(pages[None])))
    with jax.default_matmul_precision("highest"):
        want = ref.reference_logits(params, cfg, toks.tolist(), last=1)
    assert float(jnp.max(jnp.abs(logits[0] - want[0]))) < 1e-4


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


CONFIG = {"num_hidden_layers": 8, "sliding_window_size": 4096,
          "sliding_window_layout": [0, 1, 1, 1] * 13}
SHAPE = dict(num_heads=28, num_kv_heads=4, head_dim=128, page_size=64,
             itemsize=2)
PAGE = 64 * 4 * 128 * 2         # one page of K (or of V), bytes
QO = 2 * 28 * 128 * 2           # q read, the output written


def test_window_attn_work_by_hand():
    """A row of context 10,000 at decode_steps 4: a window layer's kernel
    reads the pages that intersect [5,904, 9,996): 92 .. 156, 65 pages of
    K and of V; a full layer's [0, 9,996): 157 pages. A row inside the
    window reads the same in both kinds; a row shorter than the buffer
    nothing."""
    assert window_attn_work.layers_of(CONFIG) == {
        "window": 6, "full": 2, "size": 4096}
    assert window_attn_work.layers_of({"num_hidden_layers": 8}) is None
    ops, bytes_ = window_attn_work.attention_decode(
        [10000], window=4096, in_buffer=4, **SHAPE)
    assert bytes_ == 2 * 65 * PAGE + QO
    assert ops == 4 * 28 * 128 * (9996 - 5904)
    ops, bytes_ = window_attn_work.attention_decode(
        [10000], window=None, in_buffer=4, **SHAPE)
    assert bytes_ == 2 * 157 * PAGE + QO and ops == 4 * 28 * 128 * 9996
    short = [window_attn_work.attention_decode(
        [1000], window=w, in_buffer=4, **SHAPE) for w in (4096, None)]
    assert short[0] == short[1] and short[0][1] == 2 * 16 * PAGE + QO
    assert window_attn_work.attention_decode(
        [3], window=4096, in_buffer=4, **SHAPE) == (0.0, QO)


def test_the_three_counter_readers_by_hand():
    raw = {"stats0": {"kv_window_pages_held_total": 1000,
                      "kv_window_pages_seen_total": 3519,
                      "kv_window_pages_allocated_total": 100,
                      "kv_window_pages_released_total": 40,
                      "decode_row_steps_total": 480,
                      "decode_row_steps_past_window_total": 96},
           "stats1": {"kv_window_pages_held_total": 1000 + 5 * 2639,
                      "kv_window_pages_seen_total": 3519 * 6,
                      "kv_window_pages_allocated_total": 1100,
                      "kv_window_pages_released_total": 470,
                      "decode_row_steps_total": 480 + 9600,
                      "decode_row_steps_past_window_total": 96 + 5760}}
    assert _reader("kv_window_pool_fill_share")(raw) == pytest.approx(
        100 * 2639 / 3519)
    assert _reader("window_pages_released_share")(raw) \
        == pytest.approx(43.0)
    assert _reader("rows_past_window_share")(raw) == pytest.approx(60.0)
    for name in ("kv_window_pool_fill_share", "window_pages_released_share",
                 "rows_past_window_share"):
        # a program with one pool (the parent), and an idle window
        assert _reader(name)({"stats0": {}, "stats1": {}}) is None
        assert _reader(name)({"stats0": raw["stats0"],
                              "stats1": raw["stats0"]}) is None


WIN = ("jit(decode_window)/while/body/attn/attn.window/"
       "paged_attention_decode_layered/pallas_call:")
WIN_MERGE = "jit(decode_window)/while/body/attn/attn.window/exp:"
FULL = ("jit(decode_window)/while/body/attn/attn.full/"
        "paged_attention_decode_layered/pallas_call:")
WIN_PREFILL = ("jit(prefill_step)/while/body/attn/attn.window/"
               "paged_attention_prefill/pallas_call:")
EXPERTS = "jit(decode_window)/while/body/moe/moe.experts/dot_general:"
OPS = {1: "%paged_attention_decode_layered.1 = (f32[48,28,128]) "
          "custom-call()",
       2: "%fusion.2 = f32[48,4,7,4]{3,2,1,0} fusion(f32[48] %p)",
       3: "%paged_attention_decode_layered.3 = (f32[48,28,128]) "
          "custom-call()",
       4: "%paged_attention_prefill.4 = bf16[1,4,3584,128] custom-call()",
       5: "%fusion.5 = f32[48,1,64,768]{3,2,1,0} fusion(bf16[48] %p)"}
# one row-step at context 10,000: the floors of the six window layers
# and of the two full layers at 819 GB/s, in microseconds
FLOOR_WIN = 6 * (2 * 65 * PAGE + QO) / 819e9 * 1e6
FLOOR_FULL = 2 * (2 * 157 * PAGE + QO) / 819e9 * 1e6


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the decode kernel under ``attn.window``
    for EXACTLY its floor (a kernel at its floor) and the merge beside
    it 40 us, the kernel under ``attn.full`` for twice its floor, the
    prefill kernel under ``attn.window`` 100 us, the experts the rest."""
    w, f = round(FLOOR_WIN), round(2 * FLOOR_FULL)
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, WIN))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, WIN_MERGE))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, FULL))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, WIN_PREFILL))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, EXPERTS))
        + _line("XLA Ops", [(1, 0, w), (2, w, 40), (3, w + 40, f),
                            (4, w + 40 + f, 100),
                            (5, w + 140 + f, 1000 - (w + 140 + f))])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_smallthinker")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py"), w, f


def _raw():
    """One token after a first arrives inside the slice, at context
    10,000 (prompt 9,999, the request's second token)."""
    rows = [{"prompt_len": 9999, "chunk_s": [11.0], "chunk_n": [2]}]
    return {"trace": {"busy_s": 1000e-6, "kernel_s": 0.0},
            "trace_slice": [10.0, 15.0], "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "engine": {"decode_steps": 4},
            "stats0": {}, "stats1": {counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"num_layers": 8, "num_heads": 28, "num_kv_heads": 4,
                      "head_dim": 128, "page_size": 64, "kv_itemsize": 2,
                      "config": CONFIG}}


def test_the_three_trace_readers_by_hand(traced, monkeypatch):
    """The decode kernel's events are told apart by their scope: a
    kernel at its floor reads 100% (never more), one at twice its floor
    50%; the busy share takes everything under ``attn.window``, the
    prefill kernel too. ``paged_attn_roofline``'s count (every layer the
    whole context) set against the window layers' time would read 240%:
    why the work file has a count of its own. Silent for another
    configuration, an untraced run and a program without the phases."""
    file, w, f = traced
    raw = _raw()
    assert window_attn_work.decode_contexts(raw) == [10000]
    win, full, busy = (_reader("window_attn_roofline"),
                       _reader("full_attn_roofline"),
                       _reader("attn_window_busy_share"))
    for read in (win, full, busy):
        monkeypatch.setitem(read.__globals__, "__file__", file)
    assert window_attn_work.kernel_seconds(raw, "attn.window", file) \
        == pytest.approx(w * 1e-6)
    assert window_attn_work.kernel_seconds(raw, "attn.full", file) \
        == pytest.approx(f * 1e-6)
    assert win(raw) == pytest.approx(100.0 * FLOOR_WIN / w)
    assert 99.0 < win(raw) <= 100.5         # at its floor, not past it
    assert full(raw) == pytest.approx(100.0 * FLOOR_FULL / f, rel=1e-6)
    assert 49.0 < full(raw) < 51.0
    assert busy(raw) == pytest.approx(100.0 * (w + 40 + 100) / 1000)
    # the planted reading: the accepted count on the same kernel time
    ops, bytes_ = roofline.paged_attention_decode(
        [10000], num_heads=28, num_kv_heads=4, head_dim=128, page_size=64)
    planted = 100.0 * roofline.least_seconds(
        6 * ops, 6 * bytes_, "TPU v5 lite")["seconds"] / (w * 1e-6)
    assert planted > 200
    other = {**raw, "model": {**raw["model"], "config": {}}}
    for read in (win, full, busy):
        assert read(other) is None
        assert read({**raw, "trace": None}) is None
        assert read({**raw, "stats1": {}}) is None


def test_the_shared_scope_readers_see_the_cell(traced, monkeypatch):
    read = _reader("moe_busy_share")
    monkeypatch.setitem(read.__globals__, "__file__", traced[0])
    w, f = traced[1:]
    assert read(_raw()) == pytest.approx(
        100.0 * (1000 - (w + 140 + f)) / 1000)
