"""What PR 50 added to the benchmark for a model whose layers keep a
matrix of state under a gated delta rule (KDA) beside latent attention
without positions, with the chip's share of its experts, on the CPU: a
``tiny-kimi`` configuration ADDED to a copy of the benchmark by files
alone (its reference is the repo's
``configs/kimi-linear-48b-a3b/reference.py``, its traffic a small closed
loop) and run end to end through ``serve.agree``; the repo's own
configuration and cell against the catalog, against ``BENCHMARK.json``
and against the issue's traffic; the work functions against a hand
count; the five new readers on hand-made counters and a hand-made
trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import (cells, counters, kda_work, latent_work,
                               roofline, ssd_work)

CELL = "tiny-kimi.tiny-closed"
LIKE = "kimi-linear-48b-a3b.doc-reason"
NAME = "kimi-linear-48b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LISTS = {"kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
         "full_attn_layers": [4, 8, 12]}
TINY_KIMI = {
    "model_type": "kimi_linear", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "linear_attn_config": dict(LISTS, num_heads=4, head_dim=16,
                               short_conv_kernel_size=4),
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "first_k_dense_replace": 1, "num_experts": 8, "router_num_experts": 16,
    "first_local_expert": 0, "num_experts_per_token": 4,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
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
def kroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales, an embedding of unit RMS at
    this vocabulary), one traffic mix, one cell that reports what the
    repo's own Kimi Linear cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_kimi"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-kimi"))
    _dump(os.path.join(bdir, "configs", "tiny-kimi", "config.json"),
          TINY_KIMI)
    _dump(os.path.join(bdir, "configs", "tiny-kimi", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=22.6)})
    b["configs"].append({
        "name": "tiny-kimi", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-kimi/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-kimi", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-kimi",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_kimi_cell_end_to_end(kroot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64 = eight scan chunks of 16, the state carried
    through the pool and the latents through their pages; windows on
    gathered rows; half of every token's expert pairs routed to experts
    that are not here) against the repo's plain reference given the same
    share, under the harness's one rule, and a closed-loop window with no
    failed request."""
    proc = _run(kroot, CELL, 0, seconds=4)
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


NEW = {"kda_busy_share", "kda_step_roofline", "kda_chunk_roofline",
       "state_carried_chunk_share", "latent_attn_roofline.hybrid",
       # two accepted quantities under names of the cell's own: their
       # accepted entries' lists are pinned to one cell each by
       # test_bm_granite.py / test_bm_kanana.py
       "moe_held_pair_share.doc-reason", "latent_attn_busy_share.doc-reason"}
# the accepted quantities the cell is appended to
SHARED = {"moe_busy_share", "state_pool_fill_share", "output_tok_s.tpot"}


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
            "sampler_busy_share", "idle_no_work_share"} <= mine
    # the accepted latent roofline multiplies by the depth: not this
    # cell's; ``moe_shared_busy_share`` asks the configuration for
    # DeepSeek's key ``n_shared_experts`` and reads nothing where the
    # key is ``num_shared_experts`` (my chip run, PR 50); nor the GQA
    # kernels' or another family's scan's
    assert not {"latent_attn_roofline", "moe_shared_busy_share",
                "moe_held_pair_share", "latent_attn_busy_share",
                "paged_attn_roofline",
                "paged_attn_busy_share", "ssm_busy_share",
                "ssd_step_roofline", "ssm_scan_roofline"} & mine
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
    assert entry["chips"] == 1
    assert NAME in [c["name"] for c in bench["configs"]]


def test_the_cell_reports_its_readers_and_each_has_a_file():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    # a reader file of its own, as paged_attn_roofline.hybrid is cell 4's
    assert cells.reader_path("latent_attn_roofline.hybrid", ROOT).endswith(
        "latent_attn_roofline.hybrid.py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the two keys ``reduced`` names and in
    nothing else, and states the share beside the published count; every
    width is as published; the two lists are kept whole."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "num_experts"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    share = {"router_num_experts", "first_local_expert"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(reduced) | share
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (27, 8)
    assert (published["num_experts"], run["num_experts"],
            run["router_num_experts"], run["first_local_expert"]) \
        == (256, 64, 256, 0)
    # two whole periods of the pattern: KDA, KDA, KDA, MLA, twice
    lin = run["linear_attn_config"]
    assert lin == published["linear_attn_config"]
    assert [l for l in lin["full_attn_layers"] if l <= 8] == [4, 8]
    assert [l for l in lin["kda_layers"] if l <= 8] == [1, 2, 3, 5, 6, 7]
    assert kda_work.kda_shapes(run) == {"heads": 32, "head_dim": 128,
                                        "layers": 6, "attending": 2}
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, the whole vocabulary
    assert run["num_hidden_layers"] - run["first_k_dense_replace"] >= 4
    assert run["num_experts"] >= 8
    assert (run["hidden_size"], run["intermediate_size"],
            run["moe_intermediate_size"], run["kv_lora_rank"],
            run["qk_nope_head_dim"], run["qk_rope_head_dim"],
            run["v_head_dim"], run["num_attention_heads"],
            run["num_experts_per_token"], run["num_shared_experts"],
            run["vocab_size"], run["routed_scaling_factor"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 32, 8, 1, 163840, 2.446)
    for key in ("assumed", "stands_for", "caveat", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    assert "4 chips share each layer" in about["stands_for"]
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
        NAME, "doc-reason", 1) == (cell["config"], cell["traffic"],
                                   cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 128, 1024, 20261002)
    assert "shared_prefix" not in t
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024,
                               "sigma": 0.8, "min": 256, "max": 7168}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    # within the latent kernels' one page bucket
    assert longest == 8704 <= cells.context_tokens(cell) == 72 * 128
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 128
    assert e["max_prefill_batch"] in e["batch_buckets"]
    # most prompts are longer than one prefill chunk: their state is
    # carried from chunk to chunk
    from benchmark.harness import traffic

    lens = [r["prompt_len"] for r in traffic.schedule(t, 50)]
    assert sum(n > e["prefill_chunk"] for n in lens) > 0.75 * len(lens)


def test_reference_imports_nothing_of_the_programs_models():
    with open(os.path.join(BENCH, "configs", NAME, "reference.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "dynamo_tpu" not in code and "pallas" not in code
    assert "import jax" in code and "lax.scan" in code
    ref = cells.load_reference({
        "reference_file": os.path.join(BENCH, "configs", NAME,
                                       "reference.py"), "config": NAME})
    assert callable(ref.reference_logits)


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


KIMI = {"num_hidden_layers": 8, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
        "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
            "full_attn_layers": [4, 8, 12], "num_heads": 32,
            "head_dim": 128}}
SHAPES = {"heads": 32, "head_dim": 128, "layers": 6, "attending": 2}


def test_kda_work_by_hand():
    """One row-step through one layer at 32 x 128 x 128: 7 operations an
    element of the 524,288-element state + 3 a channel; the float32 state
    read and written (2 x 2 MiB) + q, k, the decay, v in and o out and
    beta in bf16. A prompt token: the same operations, the vectors only.
    Shapes from the configuration as it is run: 6 of the first 8 layers
    are KDA layers, 2 attend. The bytes do not change with what
    implements the step (the function takes shapes and nothing of the
    program), and a kernel that takes exactly the floor's time reads
    100%, never more."""
    assert kda_work.kda_shapes(KIMI) == SHAPES
    assert kda_work.kda_shapes({"mamba_n_heads": 128}) is None
    one = dict(SHAPES, layers=1)
    ops, bytes_ = kda_work.kda_decode(1, **one)
    assert ops == 7 * 32 * 128 * 128 + 3 * 32 * 128
    assert bytes_ == 2 * 2 * 2 ** 20 + (5 * 4096 + 32) * 2
    p_ops, p_bytes = kda_work.kda_prefill(1, **one)
    assert p_ops == ops and p_bytes == (5 * 4096 + 32) * 2
    # ISSUE 50's floor: 128 rows x 6 layers x 2 MiB x 2 at 819 GB/s
    ops, bytes_ = kda_work.kda_decode(128, **SHAPES)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(3.96e-3, rel=0.01)
    assert 100.0 * least["seconds"] / least["seconds"] <= 100.0


STEP = ("jit(decode_window)/while/body/kda/kda.scan/jit(kda_step)/"
        "pallas_call:")
STEP_PREP = "jit(decode_window)/while/body/kda/kda.scan/exp:"
CHUNK = "jit(prefill_step)/while/body/kda/kda.scan/while/body/dot_general:"
PROJ = "jit(decode_window)/while/body/kda/kda.proj/dot_general:"
LATENT = ("jit(decode_window)/attn/attn.latent/"
          "jit(latent_attention_decode_layered)/pallas_call:")
EXPERTS = "jit(decode_window)/while/body/moe/moe.experts/dot_general:"
OPS = {1: "%kda_step.1 = (f32[128,1,4096], f32[129,6,128,4096]) "
          "custom-call()",
       2: "%fusion.2 = f32[128,128,32]{2,1,0} fusion(f32[128] %p)",
       3: "%fusion.3 = f32[8,32,16,128]{3,2,1,0} fusion(f32[4] %p)",
       4: "%fusion.4 = f32[128,12288]{1,0} fusion(bf16[128] %p)",
       5: "%latent_attention_decode_layered.5 = (f32[128,32,512]) "
          "custom-call()",
       6: "%fusion.6 = f32[128,1,64,1024]{3,2,1,0} fusion(bf16[128] %p)",
       7: "%while.7 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the step kernel 0-200 and the ops that
    make its operands 200-240 (decode_window), the chunked form inside
    its loop 240-400 (prefill_step; the while that spans it is a
    container), the mixer's projections 400-500, the latent decode
    kernel 500-600, the experts 600-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, STEP))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, STEP_PREP))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, CHUNK))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, LATENT))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, CHUNK))
        + _line("XLA Ops", [(1, 0, 200), (2, 200, 40), (7, 240, 160),
                            (3, 240, 160), (4, 400, 100), (5, 500, 100),
                            (6, 600, 400)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_kimi")
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
            "stats0": {"prefill_tokens_total": 0,
                       "prefill_row_chunks_total": 10,
                       "prefill_row_chunks_carried_total": 4},
            "stats1": {"prefill_tokens_total": 2560,
                       "prefill_row_chunks_total": 110,
                       "prefill_row_chunks_carried_total": 68,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "num_heads": 32, "page_size": 128,
                      "config": KIMI}}


def test_the_three_roofline_readers_by_hand(traced, monkeypatch):
    """``kda_step_roofline``: 3 row-steps x 6 layers of state read and
    written over the kernel's own 200 us; ``kda_chunk_roofline``: 256
    prompt tokens' vectors over the 160 us under ``kda.scan`` in
    ``prefill_step``; ``latent_attn_roofline.hybrid``: the accepted
    reader's count at TWO layers where the configuration has eight, a
    quarter of what the accepted reader reads. All silent for another
    configuration and an untraced run."""
    raw = _raw()
    assert ssd_work.decoded_row_steps(raw) == 3
    step, chunk, latent, accepted = (_reader(n) for n in (
        "kda_step_roofline", "kda_chunk_roofline",
        "latent_attn_roofline.hybrid", "latent_attn_roofline"))
    for read in (step, chunk, latent, accepted):
        monkeypatch.setitem(read.__globals__, "__file__", traced)
    least = roofline.least_seconds(*kda_work.kda_decode(3, **SHAPES),
                                   "TPU v5 lite")
    assert step(raw) == pytest.approx(100.0 * least["seconds"] / 200e-6)
    least = roofline.least_seconds(*kda_work.kda_prefill(256, **SHAPES),
                                   "TPU v5 lite")
    assert chunk(raw) == pytest.approx(100.0 * least["seconds"] / 160e-6)
    ops, bytes_ = latent_work.latent_attention_decode(
        [701, 702, 703], num_heads=32, kv_lora_rank=512, rope_dim=64,
        page_size=128, itemsize=2)
    least = roofline.least_seconds(2 * ops, 2 * bytes_, "TPU v5 lite")
    # the accepted reader is loaded by the hybrid one from the repo's
    # root and finds its trace by its own file: steer that load too
    load = cells.load_reader

    def steered(name, root=ROOT):
        read = load(name, root)
        read.__globals__["__file__"] = traced
        return read

    monkeypatch.setattr(cells, "load_reader", steered)
    assert latent(raw) == pytest.approx(100.0 * least["seconds"] / 100e-6)
    assert accepted(raw) == pytest.approx(4 * latent(raw))
    for read in (step, chunk, latent):
        assert 0 < read(raw) <= 100
        other = {**raw, "model": {**raw["model"],
                                  "config": {"mamba_n_heads": 128,
                                             "kv_lora_rank": 512}}}
        assert read(other) is None
        assert read({**raw, "trace": None}) is None


def test_the_scope_and_counter_readers_by_hand(traced, monkeypatch):
    """``kda_busy_share``: the kernel, its operands, the chunked form and
    the projections = 500 of 1,000 us; the accepted ``moe_busy_share``
    and ``latent_attn_busy_share`` find this module's scopes;
    ``state_carried_chunk_share``: 64 of the window's 100 row-chunks
    started past position 0; silent for a program without the counters
    (the parent)."""
    load = cells.load_reader

    def steered(name, root=ROOT):       # the readers the variants load
        read = load(name, root)
        read.__globals__["__file__"] = traced
        return read

    monkeypatch.setattr(cells, "load_reader", steered)
    for name, want in (("kda_busy_share", 50.0), ("moe_busy_share", 40.0),
                       ("latent_attn_busy_share.doc-reason", 10.0)):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read(_raw()) == pytest.approx(want), name
    held = _reader("moe_held_pair_share.doc-reason")
    assert held({"stats0": {"moe_pairs_routed_total": 0,
                            "moe_pairs_held_total": 0},
                 "stats1": {"moe_pairs_routed_total": 800,
                            "moe_pairs_held_total": 184}}) \
        == pytest.approx(23.0)
    read = _reader("state_carried_chunk_share")
    assert read(_raw()) == pytest.approx(64.0)
    assert read({"stats0": {}, "stats1": {}}) is None
    kda = _reader("kda_busy_share")
    assert kda({**_raw(), "model": {"config": {}}}) is None
