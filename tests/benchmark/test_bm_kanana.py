"""What PR 31 added to the benchmark for a latent-attention model, on
the CPU: a ``tiny-kanana`` configuration ADDED to a copy of the
benchmark by files alone (its reference is the repo's
``configs/kanana-2-30b-a3b/reference.py``, its traffic a small ``doc-qa``:
an open loop over shared documents) and run end to end; the plain
reference against the program's own non-absorbed oracle and against a
hand-written router; the readers of the scopes ``attn.latent`` /
``moe.shared`` and of the latent kernel's events on a hand-made trace,
each number counted by hand; the operation-and-byte function on
hand-worked cases; the repo's own cell and configuration files."""

import json
import os
import shutil

import numpy as np
import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import cells, counters, latent_work

CELL = "tiny-kanana.tiny-doc-qa"
LIKE = "kanana-2-30b-a3b.doc-qa"
NAME = "kanana-2-30b-a3b"
# Kanana-2 in small: no query LoRA, one leading dense layer + 2 expert
# layers, sigmoid router with a selection bias, 2 shared experts; served
# in bf16 like the cells. routed_scaling_factor 1.0 as test_bm_e2e's
# tiny-mla has it (one swapped expert of 8 at width 64 moves a position
# by more than the published 2.448 lets the rule pass: a matter of the
# tiny size).
TINY_KANANA = {
    "model_type": "deepseek_v3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 1.0,
    "rope_theta": 1000000, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "tie_word_embeddings": False, "max_position_embeddings": 2048}
ABOUT = {"reference": f"benchmark/configs/{NAME}/reference.py",
         "weight_scales": {"router_bias": 0.05}}
# pages of 16: a 64-character document + BOS fills 4 whole pages, so the
# second ask of a document is a prefix hit on latent pages
ENGINE = {"page_size": 16, "num_pages": 96, "max_batch": 4,
          "batch_buckets": [4], "prefill_chunk": 64,
          "prefill_buckets": [32, 64], "page_buckets": [8],
          "max_prefill_batch": 4, "warmup_logprobs": False}
TRAFFIC = {"loop": "open", "rate_rps": 4.0, "base_seed": 1,
           "shared_prefix": {"count": 2, "chars": 64, "zipf": 1.0},
           "prompt_len": {"dist": "uniform", "min": 73, "max": 90},
           "output_len": {"dist": "uniform", "min": 4, "max": 10}}


@pytest.fixture(scope="module")
def kroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration, one traffic mix, one cell that reports what the
    repo's own Kanana cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_kanana"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-kanana"))
    _dump(os.path.join(bdir, "configs", "tiny-kanana", "config.json"),
          TINY_KANANA)
    _dump(os.path.join(bdir, "configs", "tiny-kanana", "about.json"), ABOUT)
    b["configs"].append({
        "name": "tiny-kanana", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-kanana/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-doc-qa.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-kanana", "traffic": "tiny-doc-qa", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-kanana",
                           "traffic": "tiny-doc-qa", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_kanana_cell_end_to_end(kroot):
    """``correct`` true on the CPU: the engine (bf16, latent pools read
    through mla's window, chunked prefill, prefix hits on latent pages)
    against the repo's plain reference under the harness's one rule, and
    an open-loop window with no failed request."""
    proc = _run(kroot, CELL, 0, seconds=4)
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] == 16
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    agree = next(n for n in notes if n["note"] == "agree")
    assert agree["positions"] == 27 and agree["ok"]
    assert next(n for n in notes if n["note"] == "correct")[
        "post_warmup_compiles"] == 0


def test_a_traced_run_reads_every_counter_metric_then_is_refused(kroot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    (``latent_attn_*``, ``moe_shared_busy_share`` among them) return
    None by their own rule, none raises, and the run is refused as no
    measurement. The documents were served from the prefix cache."""
    proc = _run(kroot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert {"latent_attn_busy_share", "latent_attn_roofline",
            "moe_shared_busy_share", "moe_busy_share",
            "prefix_hit_share.tpot", "ttft_mean_ms.tpot",
            "warmup_s", "window_ms_mean", "prefill_ms_mean",
            "decode_rows_mean", "device_idle_share"} <= mine
    # the base entries of the TTFT side move ttft_mean_ms, which this
    # cell does not report
    assert not {"paged_attn_roofline", "paged_attn_busy_share",
                "ssm_busy_share", "prefix_hit_share",
                "ttft_p95_ms"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "setup_s"}
    for m in bench["per_layer"]:
        if m["name"] in ("latent_attn_busy_share", "latent_attn_roofline",
                         "moe_shared_busy_share"):
            assert m["workloads"] == [LIKE], m["name"]


def test_the_tiny_cell_reports_what_the_kanana_cell_reports(kroot):
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    per_layer = {m["name"] for m in cells.metrics_for(CELL, "per_layer",
                                                      kroot)}
    mine = {m["name"] for m in cells.metrics_for(LIKE, "per_layer", ROOT)}
    assert per_layer == mine
    # a variant without a file of its own is read by its quantity's
    for name in mine:
        assert os.path.isfile(cells.reader_path(name, ROOT)), name
    assert cells.reader_path("prefix_hit_share.tpot", ROOT).endswith(
        os.path.join("metrics", "prefix_hit_share.py"))


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_keeps_every_published_number():
    cell = cells.load_cell(LIKE, ROOT)
    with open(os.path.join(cell["model_path"], "about.json")) as f:
        about = json.load(f)
    run, published = cell["model_config"], about["published"]
    assert about["reduced"] == ["num_hidden_layers"]
    assert {k for k in published if run.get(k) != published[k]} == {
        "num_hidden_layers"}
    assert published["num_hidden_layers"] == 48
    assert run["num_hidden_layers"] == 6       # 1 dense + 5 expert layers
    assert run["first_k_dense_replace"] == 1
    assert (run["kv_lora_rank"], run["qk_rope_head_dim"],
            run["q_lora_rank"]) == (512, 64, None)
    assert (run["n_routed_experts"], run["num_experts_per_tok"],
            run["n_shared_experts"], run["moe_intermediate_size"]) == (
                128, 6, 2, 768)
    assert (run["scoring_func"], run["topk_method"],
            run["routed_scaling_factor"]) == ("sigmoid", "noaux_tc", 2.448)
    # a zero selection bias would leave noaux_tc untested
    assert 0 < cell["weight_scales"]["router_bias"] < 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == about["source"] and entry["reduced"] == [
        "num_hidden_layers"]


def test_the_cell_offers_its_traffic_under_the_knee_and_its_pool_holds_it():
    cell = cells.load_cell(LIKE, ROOT)
    t, e = cell["traffic_params"], cell["engine"]
    sp = t["shared_prefix"]
    assert t["loop"] == "open" and (sp["count"], sp["chars"],
                                    sp["zipf"]) == (16, 8192, 1.0)
    # BOS + document + 64..256 unique characters; answers of 128..384
    assert (t["prompt_len"]["min"] - 1 - sp["chars"],
            t["prompt_len"]["max"] - 1 - sp["chars"]) == (64, 256)
    assert (t["output_len"]["min"], t["output_len"]["max"]) == (128, 384)
    knee = t["knee"]
    assert knee["offered_share_of_knee"] == 0.8
    assert t["rate_rps"] == pytest.approx(0.8 * knee["knee_rps"])
    # the page bucket covers the longest request; the pool holds every
    # document once and every row's own pages beside them
    ps = e["page_size"]
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert e["page_buckets"][-1] * ps >= longest
    own = -(-longest // ps) - sp["chars"] // ps
    need = sp["count"] * sp["chars"] // ps + e["max_batch"] * own
    # ... and little more (ISSUE 31: "+ slack"): a pool in which every
    # row could keep a copy of its document would hide what a cold
    # start costs the page manager (ROADMAP A9)
    assert need <= e["num_pages"] <= 1.25 * need
    assert e["prefill_chunk"] == 512 and e["max_batch"] == 64


def test_doc_qa_lengths_stay_inside_their_limits_and_the_cells_context():
    """What test_bm_traffic.py asserts of every mix (since PR 45 with the
    context of the cells that run it, as here), and what this mix asks
    of its documents."""
    from test_bm_traffic import _lengths_hold

    from benchmark.harness import traffic

    cell = cells.load_cell(LIKE, ROOT)
    p = cell["traffic_params"]
    assert cells.context_tokens(cell) == 72 * 128
    _lengths_hold(p, [cell])
    sched = traffic.schedule(p, 50)
    assert len(sched) == round(p["rate_rps"] * 50)
    # every document is asked for, the rarest a few times
    asks = [sum(r["prefix"] == d for r in sched) for d in range(16)]
    assert min(asks) >= 3 and max(asks) > 10 * min(asks) / 2


# ------------------------------------------------------- the reference


def _reference():
    return cells.load_reference(cells.load_cell(LIKE, ROOT))


def _tiny_cfg(**over):
    from dynamo_tpu.models.config import ModelConfig

    return ModelConfig.from_hf_config({**TINY_KANANA, **over})


def test_reference_route_against_a_hand_written_top_k():
    """scores + bias select, scores alone weigh, renormalised, scaled."""
    import jax.numpy as jnp

    ref = _reference()
    cfg = _tiny_cfg(routed_scaling_factor=2.448)
    s = np.array([[0.9, 0.8, 0.7, 0.6, 0.5, 0.4, 0.3, 0.2]], np.float32)
    none = np.asarray(ref.route(cfg, jnp.asarray(s), jnp.zeros(8)))
    want = np.zeros(8)
    want[[0, 1]] = 2.448 * s[0, [0, 1]] / (0.9 + 0.8)
    np.testing.assert_allclose(none[0], want, rtol=1e-6)
    bias = np.zeros(8, np.float32)
    bias[6] = 0.55          # 0.3 + 0.55 = 0.85: second, ahead of 0.8
    got = np.asarray(ref.route(cfg, jnp.asarray(s), jnp.asarray(bias)))
    want = np.zeros(8)
    want[[0, 6]] = 2.448 * s[0, [0, 6]] / (0.9 + 0.3)
    np.testing.assert_allclose(got[0], want, rtol=1e-6)


def test_reference_is_the_non_absorbed_forward():
    """The plain reference (no import from models/mla.py, queries in
    blocks, ``last=``) against the program's own non-absorbed oracle on
    a float32 tree with a live selection bias; more tokens than one
    query block."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import mla

    ref = _reference()
    with open(ref.__file__) as f:
        assert "models.mla" not in f.read().replace(
            "``dynamo_tpu/models/mla.py``", "")
    import dataclasses

    cfg = dataclasses.replace(_tiny_cfg(routed_scaling_factor=2.448),
                              dtype="float32")
    params = mla.init_params(cfg, jax.random.PRNGKey(0))
    params["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), params["router_bias"].shape)
    toks = np.random.RandomState(0).randint(1, 500, ref.Q_BLOCK + 44)
    want = np.asarray(mla.reference_forward(params, cfg,
                                            jnp.asarray(toks)[None]))[0]
    got = np.asarray(ref.reference_logits(params, cfg, toks))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    tail = np.asarray(ref.reference_logits(params, cfg, toks, last=5))
    np.testing.assert_array_equal(tail, got[-5:])
    with pytest.raises(NotImplementedError):
        ref.reference_logits(params, dataclasses.replace(cfg, n_group=4),
                             toks)


# ------------------------------------ the readers, on a hand-made trace

KERNEL = "jit(decode_window)/attn/attn.latent/pallas_call:"
MERGE = "jit(decode_window)/attn/attn.latent/div:"
POOL = "jit(prefill_step)/while/body/attn/attn.latent/while/body/dot:"
QKV = "jit(decode_window)/attn/dot_general:"
SHARED = "jit(decode_window)/moe/moe.shared/dot_general:"
EXPERTS = "jit(decode_window)/moe/moe.experts/dot_general:"
OPS = {1: "%latent_attention_decode_layered.3 = (f32[64,32,512]{2,1,0}, "
          "f32[64,32,128]{2,1,0}) custom-call(bf16[64,32,512] %q)",
       2: "%fusion.2 = f32[64,1,32,512]{3,2,1,0} fusion(f32[64] %p)",
       3: "%fusion.3 = f32[1,512,32,512]{3,2,1,0} fusion(bf16[8] %p)",
       4: "%fusion.4 = bf16[64,6144]{1,0} fusion(bf16[64] %p)",
       5: "%fusion.5 = bf16[64,2048]{1,0} fusion(bf16[64] %p)",
       6: "%fusion.6 = f32[64,1,128,768]{3,2,1,0} fusion(bf16[64] %p)",
       7: "%while.7 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the kernel 0-200, its merge 200-250, the
    prefill's pool arm 250-350 (inside a while that spans it), the
    query projections 350-500, the shared experts 500-600, the routed
    experts 600-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, KERNEL))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, MERGE))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, POOL))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, QKV))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, SHARED))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, POOL))
        + _line("XLA Ops", [(1, 0, 200), (2, 200, 50), (7, 250, 100),
                            (3, 250, 100), (4, 350, 150), (5, 500, 100),
                            (6, 600, 400)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_kanana")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


KANANA = {"num_hidden_layers": 6, "kv_lora_rank": 512,
          "qk_rope_head_dim": 64, "n_shared_experts": 2}
STATS = {"stats1": {counters.PHASES_KEY: {"idle": 1.0}}}
RAW = {"trace": {"busy_s": 1000e-6}, "model": {"config": KANANA}, **STATS}


def _reader(name):
    return cells.load_reader(name, ROOT)


def test_the_scope_share_readers_by_hand(traced, monkeypatch):
    for name, want in (("latent_attn_busy_share", 35.0),   # 200 + 50 + 100
                       ("moe_shared_busy_share", 10.0),
                       ("moe_busy_share", 50.0)):   # shared + routed
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read(RAW) == pytest.approx(want), name
        assert read({**RAW, "trace": None}) is None
    # another family's configuration: nothing to read, and no raise
    other = {**RAW, "model": {"config": {"num_hidden_layers": 3}}}
    for name in ("latent_attn_busy_share", "moe_shared_busy_share",
                 "latent_attn_roofline"):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read({**other, "trace_slice": [0.0, 1.0]}) is None


def test_operations_and_bytes_of_latent_decode_by_hand():
    # 2 heads, latent 4 + rope key 2, pages of 8, bf16; contexts 5 and 9
    ops, bytes_ = latent_work.latent_attention_decode(
        [5, 9], num_heads=2, kv_lora_rank=4, rope_dim=2, page_size=8,
        itemsize=2)
    # per token and head: 4 + 2 for the score, 4 for the value, x2
    assert ops == 2 * 2 * (2 * 4 + 2) * (5 + 9) == 560
    # 1 and 2 pages of 8 x 6 elements read once, the query's 2 x 6, and
    # 2 x 4 float32 written, a row
    assert bytes_ == ((8 + 2) * 6 * 2 + 32) + ((16 + 2) * 6 * 2 + 32) == 400
    # the cell's numbers: 60 FLOP a byte of cache at long contexts
    ops, bytes_ = latent_work.latent_attention_decode(
        [8192], num_heads=32, kv_lora_rank=512, rope_dim=64, page_size=64)
    assert ops == 8192 * 69632 and bytes_ == pytest.approx(
        8192 * 1152, rel=0.02)


def _rows():
    """Two requests: 3 + 2 + 2 tokens at 1.0 / 2.0 / 9.0 s (the first
    chunk holds token 0, from prefill), and 1 + 4 at 2.5 / 3.0 s."""
    return [{"prompt_len": 8300, "chunk_s": [1.0, 2.0, 9.0],
             "chunk_n": [3, 2, 2]},
            {"prompt_len": 8400, "chunk_s": [2.5, 3.0], "chunk_n": [1, 4]}]


def test_latent_attn_roofline_by_hand(traced, monkeypatch):
    read = _reader("latent_attn_roofline")
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    raw = {**RAW, "trace_slice": [1.5, 3.5], "rows": _rows(),
           "device": {"kind": "TPU v5 lite"},
           "model": {"config": KANANA, "num_heads": 32, "page_size": 64,
                     "kv_itemsize": 2}}
    # in the slice: request 1's tokens 3, 4 (contexts 8,303 and 8,304),
    # request 2's tokens 1..4 (8,401..8,404); token 0 came from prefill
    contexts = [8303, 8304, 8401, 8402, 8403, 8404]
    ops, bytes_ = latent_work.latent_attention_decode(
        contexts, num_heads=32, kv_lora_rank=512, rope_dim=64,
        page_size=64)
    least = max(6 * ops / 197e12, 6 * bytes_ / 819e9)
    assert least == 6 * bytes_ / 819e9            # memory-bound, just
    # over the KERNEL's 200 us alone, not the scope's 350
    assert read(raw) == pytest.approx(100.0 * least / 200e-6)
    assert read({**raw, "trace": None}) is None
    assert read({**raw, "trace_slice": None}) is None
