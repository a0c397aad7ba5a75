"""What PR 38 added to the benchmark for a model that generates by
diffusion over blocks, on the CPU: a ``tiny-sdar`` configuration ADDED to
a copy of the benchmark by files alone (its reference is the repo's
``configs/sdar-30b-a3b-chat/reference.py``, its traffic a small closed
loop) and run end to end through ``serve.agree``'s unedited form (the
reference handed ``prompt + toks[:-1]``, nine tokens = two blocks and the
first position of a third); the repo's own configuration and cell against
the catalog and against each other; the plain reference against the loop
it states, one forward a row; the new readers on hand-made counters and a
hand-made trace, each number counted by hand."""

import json
import os
import shutil

import numpy as np
import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import block_attn_work, cells, counters, roofline

CELL = "tiny-sdar.tiny-closed"
LIKE = "sdar-30b-a3b-chat.decode-heavy"
NAME = "sdar-30b-a3b-chat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_SDAR = {
    "model_type": "sdar_moe", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "max_position_embeddings": 2048,
    "block_length": 4, "denoising_steps": 4, "mask_token_id": 511,
    "remasking_strategy": "sequential", "confidence_threshold": 0.9}
ABOUT = {"reference": f"benchmark/configs/{NAME}/reference.py",
         "weight_scales": {"w_router": 2.0}}
ENGINE = {"page_size": 16, "num_pages": 64, "max_batch": 4,
          "batch_buckets": [4], "prefill_chunk": 128,
          "prefill_buckets": [128], "page_buckets": [8],
          "max_prefill_batch": 4, "warmup_logprobs": False,
          "decode_steps": 8}
TRAFFIC = {"loop": "closed", "clients": 3, "pool": 64, "base_seed": 1,
           "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
           "output_len": {"dist": "uniform", "min": 6, "max": 14}}


@pytest.fixture(scope="module")
def sroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration, one traffic mix, one cell that reports what the
    repo's own SDAR cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_sdar"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-sdar"))
    _dump(os.path.join(bdir, "configs", "tiny-sdar", "config.json"),
          TINY_SDAR)
    _dump(os.path.join(bdir, "configs", "tiny-sdar", "about.json"), ABOUT)
    b["configs"].append({
        "name": "tiny-sdar", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-sdar/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-sdar", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-sdar",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_sdar_cell_end_to_end(sroot):
    """``correct`` true on the CPU: the engine (bf16, block-causal
    prefill, windows of two blocks, prompts with tails, cuts at
    max_tokens inside a block, every emission a chunk of several
    characters) against the repo's plain reference under the harness's
    one rule, and a closed-loop window with no failed request."""
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


def test_a_traced_run_reads_every_counter_metric_then_is_refused(sroot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    (``commit_busy_share``, ``block_attn_roofline`` among them) return
    None by their own rule, none raises, and the run is refused as no
    measurement."""
    proc = _run(sroot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


NEW = {"denoise_forwards_per_token", "commit_busy_share",
       "unmask_busy_share", "block_attn_busy_share", "block_attn_roofline"}
# the accepted quantities the cell reports beside them: until PR 45 each
# listed again as ``<quantity>.diffusion``, now the quantity's one entry
SHARED = {"moe_busy_share", "sampler_busy_share", "host_step_busy_share",
          "step_gap_ms_mean", "decode_slot_fill_share",
          "prefill_slot_fill_share", "warmup_s", "loop_thread_busy_share",
          "emit_to_wire_ms_mean", "step_offcpu_share", "gc_pause_share",
          "step_gap_stream_share", "idle_host_work_share",
          "idle_readback_share", "idle_no_work_share"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert NEW | SHARED | {"output_tok_s.tpot"} <= mine
    # the accepted metrics without a workloads list: every cell's
    assert {"window_ms_mean", "decode_rows_mean", "prefill_ms_mean",
            "device_idle_share", "kv_pool_fill_share",
            "chunk_gap_p99_ms"} <= mine
    assert not {"paged_attn_roofline", "paged_attn_busy_share",
                "ssm_busy_share"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            # this cell's alone
            assert m["workloads"] == [LIKE], m["name"]
            assert m["moves"] == "tpot_p50_ms"
    # the rate is a per-layer line of the traced run
    # (`output_tok_s.tpot`, read by metrics/output_tok_s.py), not the
    # end-to-end entry: a new bounded metric in a cell is a re-rating
    assert LIKE not in next(m for m in bench["end_to_end"]
                            if m["name"] == "output_tok_s")["workloads"]


def test_the_cell_reports_what_the_issue_lists():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    assert cells.reader_path("output_tok_s.tpot", ROOT).endswith(
        "output_tok_s.py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cut():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in ``num_hidden_layers`` (6 of 48), in
    the generation keys listed under ``assumed``, and in nothing else."""
    cell = cells.load_cell(LIKE, ROOT)
    with open(os.path.join(cell["model_path"], "about.json")) as f:
        about = json.load(f)
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    assert about["reduced"] == ["num_hidden_layers"]
    generation = {"block_length", "denoising_steps", "mask_token_id",
                  "remasking_strategy", "confidence_threshold"}
    assert generation | {"no_shift"} <= set(about["assumed"])
    extra = {"architectures", "assumed"}
    assert {k for k in (set(run) | set(published)) - extra
            if run.get(k) != published.get(k)} == {
                "num_hidden_layers"} | generation
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) == (
        48, 6)
    assert (run["hidden_size"], run["num_attention_heads"],
            run["num_key_value_heads"], run["head_dim"],
            run["num_experts"], run["num_experts_per_tok"],
            run["moe_intermediate_size"], run["vocab_size"]) == (
                2048, 32, 4, 128, 128, 8, 768, 151936)
    assert (run["block_length"], run["denoising_steps"],
            run["mask_token_id"], run["remasking_strategy"]) == (
                4, 4, 151669, "sequential")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == about["source"]
    assert entry["reduced"] == about["reduced"]
    # the same weight bytes as the autoregressive cell beside it
    other = cells.load_cell("qwen3-30b-a3b.decode-heavy", ROOT)
    for k in ("hidden_size", "num_hidden_layers", "num_experts",
              "moe_intermediate_size", "vocab_size", "head_dim",
              "num_attention_heads", "num_key_value_heads"):
        assert run[k] == other["model_config"][k], k


def test_the_cells_three_places_agree_and_it_runs_cell_2s_traffic():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == LIKE)
    cell = cells.load_cell(LIKE, ROOT)          # refuses a disagreement
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "decode-heavy", 1) == (cell["config"], cell["traffic"],
                                     cell["chips"])
    other = cells.load_cell("qwen3-30b-a3b.decode-heavy", ROOT)
    assert cell["traffic_file"] == other["traffic_file"]
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 64, 1024, 20260927)
    # cell 2's engine data, and a window of whole blocks in place of its
    # default of four single steps
    assert {k: v for k, v in e.items() if k != "decode_steps"} == {
        k: v for k, v in other["engine"].items() if k != "decode_steps"}
    L = cell["model_config"]["block_length"]
    assert e["decode_steps"] % L == 0 and e["decode_steps"] >= L
    assert 64 % L == 0                          # EngineConfig.page_size
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest + 2 * e["decode_steps"] <= e["page_buckets"][-1] * 64
    assert e["num_pages"] >= e["max_batch"] * -(-longest // 64)


# ------------------------------------------------------- the reference


def _reference():
    return cells.load_reference({
        "reference_file": os.path.join(BENCH, "configs", NAME,
                                       "reference.py"), "config": NAME})


def _tiny():
    import jax

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config(TINY_SDAR)
    cfg.dtype = "float32"
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


@pytest.mark.parametrize("n_tokens", [12, 13, 14, 15])
def test_reference_logits_is_one_forward_a_row(n_tokens):
    """``reference_logits`` computes every row in L + 1 streams; each row
    equals the ONE forward its docstring names: the whole blocks before
    position i + 1, then its block with the tokens before i + 1 and the
    mask from there to the block's end, under the block mask."""
    import jax

    ref = _reference()
    cfg, params = _tiny()
    L = cfg.block_length
    tokens = np.random.default_rng(n_tokens).integers(
        1, 500, n_tokens).tolist()
    with jax.default_matmul_precision("highest"):
        rows = np.asarray(ref.reference_logits(params, cfg, tokens))
        assert rows.shape == (n_tokens, cfg.vocab_size)
        for i in range(n_tokens):
            t = i + 1
            end = (t // L + 1) * L
            x = tokens[:t] + [cfg.mask_token_id] * (end - t)
            one = np.asarray(ref.block_causal_logits(params, cfg, x))[t]
            assert np.abs(rows[i] - one).max() < 2e-5, i


def test_reference_mask_is_by_blocks():
    """A token changed inside a block moves the logits of every position
    of that block (bidirectional inside) and of later ones, and of no
    earlier block."""
    import jax

    ref = _reference()
    cfg, params = _tiny()
    a = np.random.default_rng(1).integers(1, 500, 16).tolist()
    b = list(a)
    b[10] = (a[10] + 7) % 500 + 1
    with jax.default_matmul_precision("highest"):
        la = np.asarray(ref.block_causal_logits(params, cfg, a))
        lb = np.asarray(ref.block_causal_logits(params, cfg, b))
    moved = np.abs(la - lb).max(axis=1) > 1e-6
    assert moved.tolist() == [False] * 8 + [True] * 8


def test_reference_imports_nothing_of_the_programs_models():
    with open(os.path.join(BENCH, "configs", NAME, "reference.py")) as f:
        src = f.read()
    assert "dynamo_tpu" not in src.split('"""', 2)[2]
    assert "import jax" in src


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


SDAR = {"block_length": 4, "num_hidden_layers": 6}


def test_denoise_forwards_per_token_by_hand():
    read = _reader("denoise_forwards_per_token")
    raw = {"stats0": {"diffusion_forwards_total": 100,
                      "diffusion_tokens_total": 80},
           "stats1": {"diffusion_forwards_total": 1100,
                      "diffusion_tokens_total": 880}}
    assert read(raw) == pytest.approx(1.25)
    # a program without the counters (the parent; a model of one token a
    # step), and a window in which nothing was emitted
    assert read({"stats0": {}, "stats1": {}}) is None
    assert read({"stats0": raw["stats0"], "stats1": raw["stats0"]}) is None


def test_block_attention_work_by_hand():
    """One row-forward at a pooled context of 100 positions, L 4, 32 / 4
    heads of 128, pages of 64, bf16: 4 x 4 x 32 x 128 x 100 operations;
    two pages of K and V (2 x 2 x 64 x 4 x 128 elements) + 4 queries in
    and 4 rows out (2 x 4 x 32 x 128), two bytes each."""
    ops, bytes_ = block_attn_work.block_attention_pool(
        [(100, 1.0)], block_length=4, num_heads=32, num_kv_heads=4,
        head_dim=128, page_size=64)
    assert ops == 4 * 4 * 32 * 128 * 100
    assert bytes_ == (2 * 2 * 64 * 4 * 128 + 2 * 4 * 32 * 128) * 2
    half, _ = block_attn_work.block_attention_pool(
        [(100, 0.5)], block_length=4, num_heads=32, num_kv_heads=4,
        head_dim=128, page_size=64)
    assert half == ops / 2
    # position 103 lies in the block that starts at 100; a window of 8
    # positions (two blocks) began at 96 at the earliest
    assert block_attn_work.pooled_context(103, 4, 8) == 96
    assert block_attn_work.pooled_context(103, 4, 4) == 100
    assert block_attn_work.pooled_context(2, 4, 8) == 0


def test_block_attn_readers_by_hand():
    """Two tokens arrive inside the slice, at positions 100 + 1 and
    100 + 2 (block start 100, window of 8: context 96), 1.25 row-forwards
    each, 6 layers; the kernel took 1 ms of 4 ms busy."""
    rows = [{"prompt_len": 100, "chunk_s": [1.0, 2.0, 9.0],
             "chunk_n": [1, 2, 4]}]
    raw = {"trace": {"kernel_s": 1e-3, "busy_s": 4e-3},
           "trace_slice": [1.5, 3.5], "rows": rows,
           "device": {"kind": "TPU v5 lite"},
           "engine": {"decode_steps": 8},
           "stats0": {"diffusion_forwards_total": 0,
                      "diffusion_tokens_total": 0},
           "stats1": {"diffusion_forwards_total": 125,
                      "diffusion_tokens_total": 100},
           "model": {"num_layers": 6, "num_heads": 32, "num_kv_heads": 4,
                     "head_dim": 128, "page_size": 64, "kv_itemsize": 2,
                     "config": SDAR}}
    ops, bytes_ = block_attn_work.block_attention_pool(
        [(96, 2.5)], block_length=4, num_heads=32, num_kv_heads=4,
        head_dim=128, page_size=64)
    least = roofline.least_seconds(ops * 6, bytes_ * 6, "TPU v5 lite")
    assert _reader("block_attn_roofline")(raw) == pytest.approx(
        100.0 * least["seconds"] / 1e-3)
    assert _reader("block_attn_busy_share")(raw) == pytest.approx(25.0)
    # another configuration's cell, an untraced run, the parent's stats
    other = {**raw, "model": {**raw["model"], "config": {}}}
    for name in ("block_attn_roofline", "block_attn_busy_share",
                 "commit_busy_share", "unmask_busy_share"):
        assert _reader(name)(other) is None, name
    assert _reader("block_attn_roofline")({**raw, "trace": None}) is None
    assert _reader("block_attn_roofline")(
        {**raw, "stats0": {}, "stats1": {}}) is None


COMMIT = "jit(decode_window)/diffusion.commit/while/body/attn/dot_general:"
DENOISE = ("jit(decode_window)/while/body/diffusion.denoise/while/body/"
           "moe/moe.experts/dot_general:")
UNMASK = "jit(decode_window)/while/body/diffusion.unmask/sample/sort:"
CARRY = "jit(decode_window)/diffusion.unmask/cumsum:"
OPS = {1: "%fusion.1 = bf16[64,4,2048]{2,1,0} fusion(bf16[64] %p)",
       2: "%fusion.2 = f32[64,4,128,768]{3,2,1,0} fusion(bf16[64] %p)",
       3: "%sort.3 = f32[256,151936]{1,0} sort(f32[256] %p)",
       4: "%fusion.4 = s32[64,4]{1,0} fusion(s32[64] %p)",
       5: "%while.5 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the commit forward 0-200, a denoising
    forward's experts (inside the block's loop, which a while spans)
    200-800, the sampling of the unmasking 800-950, the block's carry
    update 950-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, COMMIT))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, DENOISE))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, UNMASK))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, CARRY))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, DENOISE))
        + _line("XLA Ops", [(1, 0, 200), (5, 200, 750), (2, 200, 600),
                            (3, 800, 150), (4, 950, 50)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_sdar")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


RAW = {"trace": {"busy_s": 1000e-6}, "model": {"config": SDAR},
       "stats1": {counters.PHASES_KEY: {"idle": 1.0}}}


@pytest.mark.parametrize("name,want", [
    ("commit_busy_share", 20.0),                # 200
    ("unmask_busy_share", 20.0)])               # 150 + 50, no container
def test_the_scope_share_readers_by_hand(traced, monkeypatch, name, want):
    read = _reader(name)
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    assert read(RAW) == pytest.approx(want)
    # not traced; a program without the phases (an older parent)
    assert read({**RAW, "trace": None}) is None
    assert read({**RAW, "stats1": {}}) is None


def test_the_shared_scope_readers_see_through_the_new_scopes(traced,
                                                             monkeypatch):
    """``moe`` and ``sample`` nest under the new scopes and inside the
    block's loop; the accepted readers find them there."""
    for name, want in (("moe_busy_share", 60.0),
                       ("sampler_busy_share", 15.0)):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read(RAW) == pytest.approx(want), name
