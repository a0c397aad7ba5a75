"""What PR 65 added to the benchmark for LongCat-Flash's language model (two
latent attentions and two dense MLPs a layer with ONE shortcut MoE, a
softmax router with a selection bias over real + identity experts, a
chip's share of the real ones), on the CPU: a ``tiny-longcat``
configuration ADDED to a copy of the benchmark by files alone (its
reference is the repo's ``configs/longcat-flash-omni/reference.py``, its
traffic a small closed loop) and run end to end through ``serve.agree``;
the repo's own configuration and cell against the catalog, against
``BENCHMARK.json`` and against the issue's traffic;
``harness/longcat_work.py`` against a hand count; the new readers on
hand-made counters and a hand-made trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, counters, latent_work, longcat_work,
                               roofline)

CELL = "tiny-longcat.tiny-closed"
LIKE = "longcat-flash-omni.omni-turns"
NAME = "longcat-flash-omni"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "LongCat-Flash-Omni"
TINY = {
    "model_type": "longcat_flash", "vocab_size": 512, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "router_num_experts": 16, "first_local_expert": 4,
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_bias": False, "max_position_embeddings": 2048}
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
def lroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales; an embedding of unit RMS and
    a selection bias sized for this router's 24 outputs), one traffic
    mix, one cell that reports what the repo's own cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_longcat"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-longcat"))
    _dump(os.path.join(bdir, "configs", "tiny-longcat", "config.json"),
          TINY)
    _dump(os.path.join(bdir, "configs", "tiny-longcat", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=22.6,
                              router_bias=0.0125)})
    b["configs"].append({
        "name": "tiny-longcat", "source": "test", "reduced": [],
        "why": "test",
        "file": "benchmark/configs/tiny-longcat/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-longcat", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-longcat",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_longcat_cell_end_to_end(lroot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64 over pools of 4 entries, the second share of
    four real experts beside 8 identity ones, windows) against the repo's
    plain reference given the same share, under the harness's one rule,
    and a closed-loop window with no failed request."""
    proc = _run(lroot, CELL, 0, seconds=4)
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


# the readers this PR brings as FILES and BENCHMARK.json does not list
# yet: test_bm_prefill_logits_skipped.py (PR 64) holds that its entry is
# the LAST of ``per_layer``, a ``model_config`` PR may neither edit that
# file nor put an entry anywhere but at the end, and the list has room
# for three beside what test_bm_contract.py appends to its copy. A
# ``benchmark`` PR lists them (PERF.md, section 7); the builder's traced
# chip runs read them through a scratch copy of BENCHMARK.json.
NEW = {"moe_zero_pick_share", "dense_ffn_busy_share",
       # an accepted quantity under a name of the cell's own: the
       # accepted entry's list is pinned to one cell by test_bm_kanana.py
       "latent_attn_roofline.omni-turns"}
# the accepted quantities the cell is appended to
SHARED = {"moe_busy_share", "output_tok_s.tpot"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert SHARED <= mine
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share", "chunk_gap_p99_ms",
            "host_step_busy_share", "step_gap_ms_mean", "warmup_s",
            "sampler_busy_share", "idle_no_work_share",
            "prefill_logits_skipped_share"} <= mine
    # no GQA pages, no state; and the accepted latent entries' lists are
    # held shut by accepted tests (their readers ask for
    # num_hidden_layers, which this family's published config lacks)
    assert not {m for m in mine if m.startswith("paged_attn")}
    assert not {"latent_attn_roofline", "latent_attn_busy_share",
                "state_pool_fill_share", "moe_held_pair_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    # room for what test_bm_contract.py appends as a LATER configuration
    assert len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        if m["name"] in NEW:        # once a benchmark PR lists them
            assert m["workloads"] == [LIKE], m["name"]      # this cell's
            assert m["moves"] == "tpot_p50_ms" and m["unit"] == "%"
    for name in ("output_tok_s", "ttft_mean_ms"):
        assert LIKE not in next(m for m in bench["end_to_end"]
                                if m["name"] == name)["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == LIKE)
    assert entry["chips"] == 1 and entry["traffic"] == "omni-turns"
    assert "4 of 28 layers" in entry["why"]
    assert "2 pairs a step for 64" in entry["why"]
    assert NAME in [c["name"] for c in bench["configs"]]


def test_the_cell_reports_its_readers():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    for name in NEW:        # a file of its own each, not the quantity's
        assert cells.reader_path(name, ROOT).endswith(name + ".py")
        assert os.path.isfile(cells.reader_path(name, ROOT))


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_three_named_keys():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the three keys of ``reduced`` and adds
    the family's ``model_type`` and this repo's two keys for a share: no
    width is cut."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CATALOG_NAME)
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_layers", "n_routed_experts", "vocab_size"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    assert {k for k in published if run.get(k) != published[k]} \
        == set(reduced)
    assert set(run) - set(published) == {
        "model_type", "router_num_experts", "first_local_expert"}
    assert [(published[k], run[k]) for k in reduced] == [
        (28, 4), (512, 16), (131072, 16384)]
    assert (run["router_num_experts"], run["first_local_expert"],
            run["zero_expert_num"], run["moe_topk"]) == (512, 0, 256, 12)
    assert (run["hidden_size"], run["ffn_hidden_size"],
            run["expert_ffn_hidden_size"], run["num_attention_heads"],
            run["q_lora_rank"], run["kv_lora_rank"],
            run["qk_nope_head_dim"], run["qk_rope_head_dim"],
            run["v_head_dim"]) == (6144, 12288, 2048, 64, 1536, 512, 128,
                                   64, 128)
    for key in ("assumed", "stands_for", "memory", "reference",
                "weight_scales", "weight_scales_why", "long_context"):
        assert about[key], key
    for key in ("model_type", "hidden_act", "norm_topk_prob",
                "router_bias_term", "tie_word_embeddings", "rotation",
                "rope_scaling", "softmax_scale", "leaf_names"):
        assert about["assumed"][key], key
    assert "224" in about["stands_for"] and "32" in about["stands_for"]
    assert about["weight_scales"]["router_bias"] > 0
    mem = about["memory"]
    assert mem["fits"] and mem["peak_gb"] < 15.75
    # over a quarter of one chip's memory, by what is resident alone
    assert mem["resident_gb"] > 0.25 * 15.75
    # 8 pool entries x (512 + 128 lanes) x 2 B a cached token: 10 KiB
    e = cell["engine"]
    assert mem["kv_pool_gb"] * 2 ** 30 / (e["num_pages"] * e["page_size"]) \
        == 10 * 1024
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
        NAME, "omni-turns", 1) == (cell["config"], cell["traffic"],
                                   cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"]) == ("closed", 128, 1024)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.8, "min": 256, "max": 7168}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    seeds = []
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            seeds.append(json.load(f)["base_seed"])
    assert seeds.count(t["base_seed"]) == 1     # its own
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 8704
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 128
    # 4, not the issue's 8: the wide prefill bucket is
    # bucket_batch(max_prefill_batch), and PB 8 does not fit (engine_why)
    assert e["batch_buckets"] == [1, 4, 128]
    assert e["prefill_chunk"] == 512 and e["page_size"] == 128
    assert e["page_buckets"] == [72]    # the latent kernels' one bucket
    assert e["page_buckets"][-1] * e["page_size"] >= longest
    assert e["max_prefill_batch"] == 4
    assert "2.74 GiB" in cell["engine_why"]["max_prefill_batch"]
    assert set(cell["engine_why"]) >= set(e)


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
    assert set(ref.FAULTS) == {"shortcut_early", "lora_scales_off",
                               "bias_unselected", "renormalised",
                               "identity_dropped"}


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


def _config():
    return cells.load_cell(LIKE, ROOT)["model_config"]


def test_longcat_work_by_hand():
    """The shapes of the configuration as run, and the issue's count of
    one decode step of 128 rows at 1.9k of context with a third of the
    picks identity: dense MLPs 3.6 GB, the two attentions' projections
    1.45 GB, latent pages 2.4 GB, ~86% of the 16 held experts touched a
    layer = 4.1 GB, the head 0.2 GB: ~12 GB, 14.5 ms at 819 GB/s."""
    s = longcat_work.shapes(_config())
    assert (s["layers"], s["sub_blocks"], s["heads"], s["held"], s["real"],
            s["zero"], s["top_k"]) == (4, 8, 64, 16, 512, 256, 12)
    assert longcat_work.shapes({"model_type": "deepseek_v3"}) is None
    assert longcat_work.shapes({}) is None
    parts = longcat_work.decode_step_bytes(s, 128, 1900, 1 / 3)
    assert parts["dense_ffn"] == 8 * 3 * 6144 * 12288 * 2
    assert parts["attn_proj"] == 8 * 2 * (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 8192 * 6144)
    assert parts["latent_pages"] == 8 * 128 * 1920 * 576 * 2
    touched = 16 * (1 - (1 - 1 / 512) ** (128 * 8))
    assert touched == pytest.approx(13.8, abs=0.1)
    assert parts["held_experts"] == pytest.approx(
        4 * touched * 3 * 6144 * 2048 * 2)
    assert parts["head"] == 6144 * 16384 * 2
    total = sum(parts.values())
    assert total == pytest.approx(11.9e9, rel=0.02)
    assert total / 819e9 == pytest.approx(14.5e-3, rel=0.03)
    # the latent kernel at 64 heads: 121 operations a cached byte
    ops, bytes_ = latent_work.latent_attention_decode(
        [128 * 1000], num_heads=64, kv_lora_rank=512, rope_dim=64,
        page_size=128)
    assert ops / bytes_ == pytest.approx(121, abs=1)


def test_the_counter_reader_by_hand():
    zero = _reader("moe_zero_pick_share")
    raw = {"model": {"config": _config()},
           "stats0": {"moe_pairs_routed_total": 1200,
                      "moe_pairs_held_total": 30,
                      "moe_pairs_identity_total": 400},
           "stats1": {"moe_pairs_routed_total": 13200,
                      "moe_pairs_held_total": 280,
                      "moe_pairs_identity_total": 4600}}
    assert zero(raw) == pytest.approx(35.0)
    # the parent's program and another family's (no third counter)
    assert zero({**raw, "stats0": {}, "stats1": {}}) is None
    assert zero({**raw, "stats1": {"moe_pairs_routed_total": 13200}}) is None


W = "jit(decode_window)/while/body/"
KERNEL = W + ("attn/attn.1/attn.latent/"
              "latent_attention_decode_layered/pallas_call:")
MERGE = W + "attn/attn.0/attn.latent/exp:"
PROJ = W + "attn/attn.0/attn.proj/dot_general:"
MLP = W + "mlp/dot_general:"
EXPERTS = W + "moe/moe.experts/dot_general:"
ZERO = W + "moe/moe.zero/multiply:"
MLP_P = "jit(prefill_step)/while/body/mlp/dot_general:"
OPS = {1: "%latent_attention_decode_layered.1 = (f32[128,64,512]{2,1,0}, "
          "f32[128,64,128]{2,1,0}) custom-call(bf16[128,64,512] %q)",
       2: "%fusion.2 = f32[128,1,64,512]{3,2,1,0} fusion(f32[128] %p)",
       3: "%fusion.3 = bf16[128,1,12288]{2,1,0} fusion(bf16[128] %p)",
       4: "%fusion.4 = bf16[128,1,12288]{2,1,0} fusion(bf16[128] %p)",
       5: "%fusion.5 = f32[128,1,16,2048]{3,2,1,0} fusion(bf16[128] %p)",
       6: "%fusion.6 = f32[128,1,6144]{2,1,0} fusion(f32[128] %p)",
       7: "%fusion.7 = bf16[8,512,12288]{2,1,0} fusion(bf16[8] %p)"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the latent kernel 0-200 and the merge
    200-250, the projections 250-400, the dense MLPs 400-650 (window)
    and 650-700 (prefill), the experts 700-950, the identity part
    950-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, KERNEL))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, MERGE))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, MLP))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, ZERO))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, MLP_P))
        + _line("XLA Ops", [(1, 0, 200), (2, 200, 50), (3, 250, 150),
                            (4, 400, 250), (7, 650, 50), (5, 700, 250),
                            (6, 950, 50)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_longcat")
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
                "decode_window": {"count": 1, "mean_s": 900e-6}}},
            "trace_slice": [10.0, 15.0],
            "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "engine": {"decode_steps": 4},
            "stats0": {}, "stats1": {counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "page_size": 128, "num_heads": 64,
                      "num_kv_heads": 64, "head_dim": 96,
                      "config": _config()}}


def _at(read, traced, monkeypatch):
    """The reader looking for the trace beside the hand-made root."""
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    return read


def test_the_scope_reader_by_hand(traced, monkeypatch):
    """``dense_ffn_busy_share`` 30% (the window's and the prefill's
    ``mlp``); the accepted ``moe`` reader finds the module's scopes, the
    identity part among them: 30%. Silent for another family, an
    untraced run, a program without the phases."""
    raw = _raw()
    dense = _at(_reader("dense_ffn_busy_share"), traced, monkeypatch)
    assert dense(raw) == pytest.approx(30.0)
    assert dense({**raw, "model": {**raw["model"], "config": {
        "model_type": "deepseek_v3"}}}) is None
    assert dense({**raw, "stats1": {}}) is None
    assert dense({**raw, "trace": None}) is None
    moe = _at(_reader("moe_busy_share"), traced, monkeypatch)
    assert moe(raw) == pytest.approx(30.0)


def test_the_roofline_reader_by_hand(traced, monkeypatch):
    """``latent_attn_roofline.omni-turns``: three row-steps' pages read
    in 8 SUB-BLOCKS (2 x num_layers) at 64 heads over the 200 us of the
    kernel's events (the merge's 50 us are XLA's, not the kernel's),
    through the accepted reader, which alone would ask this family's
    config for a key it does not have; under 100%; silent for another
    configuration and an untraced run."""
    raw = _raw()
    accepted = _at(_reader("latent_attn_roofline"), traced, monkeypatch)
    with pytest.raises(KeyError):
        accepted(raw)
    mine = _reader("latent_attn_roofline.omni-turns")
    monkeypatch.setattr(cells, "load_reader",
                        lambda name, root=ROOT: accepted)
    ops, bytes_ = latent_work.latent_attention_decode(
        [1001, 1002, 1003], num_heads=64, kv_lora_rank=512, rope_dim=64,
        page_size=128)
    least = roofline.least_seconds(8 * ops, 8 * bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert mine(raw) == pytest.approx(100.0 * least["seconds"] / 200e-6)
    assert 0 < mine(raw) <= 100
    assert mine({**raw, "model": {**raw["model"], "config": {
        "model_type": "deepseek_v3", "kv_lora_rank": 512}}}) is None
    assert mine({**raw, "trace": None}) is None
