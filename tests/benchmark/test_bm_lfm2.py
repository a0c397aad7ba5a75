"""What PR 33 added to the benchmark for a model whose recurrent state is
snapshotted by the page, on the CPU: a ``tiny-lfm2`` configuration ADDED
to a copy of the benchmark by files alone (its reference is the repo's
``configs/lfm2-24b-a2b/reference.py``, its traffic a small ``agent-loop``:
a closed loop over shared system prompts) and run end to end, prefix hits
and state restores counted; the repo's own configuration, cell and mix
against the catalog and against each other; the plain reference against
a second, per-token recurrence form of the convolution and a hand-written
gate; the new readers on a hand-made trace and hand-made counters, each
number counted by hand."""

import json
import os
import shutil

import numpy as np
import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import cells, counters, traffic

CELL = "tiny-lfm2.tiny-agent-loop"
LIKE = "lfm2-24b-a2b.agent-loop"
NAME = "lfm2-24b-a2b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["conv", "conv", "full_attention", "conv"] * 2
# LFM2 in small: the cell's own eight layers, heads of 64 (packed two to
# a 128-lane row, as the cell's), 8 experts top-2; served in bf16
TINY_LFM2 = {
    "model_type": "lfm2_moe", "vocab_size": 512, "hidden_size": 256,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 8, "layer_types": KINDS + KINDS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_eps": 1e-05, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False, "max_position_embeddings": 2048}
ABOUT = {"reference": f"benchmark/configs/{NAME}/reference.py",
         "weight_scales": {"router_bias": 0.05, "embed": 22.6}}
# pages of 16: a 64-character system prompt + BOS fills 4 whole pages, so
# the second request behind a prompt is a hit on pages AND state
ENGINE = {"page_size": 16, "num_pages": 96, "max_batch": 4,
          "batch_buckets": [4], "prefill_chunk": 64,
          "prefill_buckets": [32, 64], "page_buckets": [8],
          "max_prefill_batch": 4, "warmup_logprobs": False}
TRAFFIC = {"loop": "closed", "clients": 3, "pool": 64, "base_seed": 1,
           "shared_prefix": {"count": 2, "chars": 64, "zipf": 1.0},
           "prompt_len": {"dist": "uniform", "min": 73, "max": 90},
           "output_len": {"dist": "uniform", "min": 4, "max": 10}}


@pytest.fixture(scope="module")
def lroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration, one traffic mix, one cell that reports what the
    repo's own LFM2 cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_lfm2"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-lfm2"))
    _dump(os.path.join(bdir, "configs", "tiny-lfm2", "config.json"),
          TINY_LFM2)
    _dump(os.path.join(bdir, "configs", "tiny-lfm2", "about.json"), ABOUT)
    b["configs"].append({
        "name": "tiny-lfm2", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/tiny-lfm2/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-agent-loop.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-lfm2", "traffic": "tiny-agent-loop", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-lfm2",
                           "traffic": "tiny-agent-loop", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_lfm2_cell_end_to_end(lroot):
    """``correct`` true on the CPU: the engine (bf16, conv state by slot
    and by page, packed KV pools, chunked prefill, prefix hits that hand
    over pages and state) against the repo's plain reference under the
    harness's one rule, and a closed-loop window with no failed
    request."""
    proc = _run(lroot, CELL, 0, seconds=4)
    line = _last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"tpot_p50_ms", "output_tok_s",
                                    "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    agree = next(n for n in notes if n["note"] == "agree")
    assert agree["positions"] == 27 and agree["ok"]
    assert next(n for n in notes if n["note"] == "correct")[
        "post_warmup_compiles"] == 0


def test_a_traced_run_reads_every_counter_metric_then_is_refused(lroot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    (``conv_busy_share``, ``state_snapshot_busy_share`` among them)
    return None by their own rule, none raises, and the run is refused as
    no measurement."""
    proc = _run(lroot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


OWN = {"conv_busy_share", "state_snapshot_busy_share",
       "state_restore_share", "paged_attn_roofline.agent-loop"}


def benchmark_lists_hold(bench: dict) -> None:
    """What this file asserts of BENCHMARK.json's lists, of a loaded
    dict: the repo's file here, a copy with a later configuration
    appended in test_bm_contract.py. Membership, never a position."""
    mine = {m["name"] for m in cells.metrics_in(bench, LIKE, "per_layer")}
    assert OWN | {"paged_attn_busy_share", "moe_busy_share",
                  "state_pool_fill_share", "prefix_hit_share.tpot",
                  "ttft_mean_ms.tpot", "warmup_s", "window_ms_mean",
                  "prefill_ms_mean", "decode_rows_mean",
                  "device_idle_share"} <= mine
    assert not {"paged_attn_roofline", "paged_attn_roofline.hybrid",
                "ssm_busy_share", "prefix_hit_share",
                "latent_attn_roofline"} & mine
    assert {m["name"] for m in cells.metrics_in(bench, LIKE, "end_to_end")
            } == {"tpot_p50_ms", "output_tok_s", "setup_s"}
    for m in bench["per_layer"]:
        if LIKE in m.get("workloads", []):
            assert m["moves"] == "tpot_p50_ms", m["name"]
        if m["name"] in OWN:
            # this cell's alone
            assert m["workloads"] == [LIKE], m["name"]
    assert LIKE in next(m for m in bench["end_to_end"]
                        if m["name"] == "output_tok_s")["workloads"]


def test_the_tiny_cell_reports_what_the_lfm2_cell_reports(lroot):
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    per_layer = {m["name"] for m in cells.metrics_for(CELL, "per_layer",
                                                      lroot)}
    mine = {m["name"] for m in cells.metrics_for(LIKE, "per_layer", ROOT)}
    assert per_layer == mine
    for name in mine:
        assert os.path.isfile(cells.reader_path(name, ROOT)), name
    assert cells.reader_path("paged_attn_roofline.agent-loop", ROOT
                             ).endswith("paged_attn_roofline.agent-loop.py")
    assert cells.reader_path("paged_attn_busy_share.agent-loop", ROOT
                             ).endswith("paged_attn_busy_share.py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the keys ``reduced`` names and in no
    other; ``layer_types`` is kept whole (the program runs the first
    ``num_hidden_layers`` entries)."""
    cell = cells.load_cell(LIKE, ROOT)
    with open(os.path.join(cell["model_path"], "about.json")) as f:
        about = json.load(f)
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    assert about["reduced"] == ["num_hidden_layers", "tie_word_embeddings"]
    assert set(about["reduced_why"]) == set(about["reduced"])
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(about["reduced"])
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) == (
        40, 8)
    assert "tie_word_embeddings" not in published
    assert run["tie_word_embeddings"] is False
    assert run["layer_types"][:8] == KINDS and len(run["layer_types"]) == 40
    assert (run["hidden_size"], run["num_attention_heads"],
            run["num_key_value_heads"], run["conv_L_cache"]) == (
                2048, 32, 8, 3)
    assert (run["num_experts"], run["num_experts_per_tok"],
            run["moe_intermediate_size"], run["intermediate_size"],
            run["num_dense_layers"], run["vocab_size"]) == (
                64, 4, 1536, 11776, 2, 65536)
    # what the catalog does not settle is written down
    assert {"tie_word_embeddings", "head_dim", "conv_split_order",
            "renorm_epsilon", "layer_types"} <= set(about["assumed"])
    # a zero selection bias would leave the gate's bias untested
    assert 0 < cell["weight_scales"]["router_bias"] < 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == about["source"]
    assert entry["reduced"] == about["reduced"]


def test_the_cells_three_places_agree_and_its_pool_holds_the_traffic():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"]
                     if w["name"] == LIKE)
    cell = cells.load_cell(LIKE, ROOT)          # refuses a disagreement
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "agent-loop", 1) == (cell["config"], cell["traffic"],
                                   cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    sp = t["shared_prefix"]
    # the traffic ISSUE 33 names, letter for letter
    assert (t["loop"], t["clients"], t["pool"], t["base_seed"]) == (
        "closed", 64, 2048, 20260927)
    assert (sp["count"], sp["chars"], sp["zipf"]) == (8, 3072, 1.0)
    assert t["prompt_len"] == {"dist": "uniform", "min": 3137, "max": 3329}
    assert t["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (t["prompt_len"]["min"] - 1 - sp["chars"],
            t["prompt_len"]["max"] - 1 - sp["chars"]) == (64, 256)
    assert t["clients"] == e["max_batch"] and 1 in e["batch_buckets"]
    ps = e["page_size"]
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert longest == 3585
    assert e["page_buckets"] == [4096 // ps]
    # the pool holds the 8 prompts once and every row's own pages, and
    # the cold start besides: 64 rows that each prefill a whole prompt
    # before the first is published (ROADMAP A9)
    own = -(-longest // ps) - sp["chars"] // ps
    assert e["num_pages"] >= sp["count"] * sp["chars"] // ps \
        + e["max_batch"] * own
    assert e["num_pages"] >= e["max_batch"] * -(-t["prompt_len"]["max"] // ps)
    assert e["decode_steps"] <= ps if "decode_steps" in e else True


def test_agent_loop_lengths_stay_under_4096_and_inside_the_page_bucket():
    cell = cells.load_cell(LIKE, ROOT)
    p, e = cell["traffic_params"], cell["engine"]
    context = e["page_buckets"][-1] * e["page_size"]
    sched = traffic.schedule(p, 50)
    assert len(sched) == p["pool"] == 2048
    for r in sched:
        assert r["due_s"] is None
        assert 3137 <= r["prompt_len"] <= 3329
        assert 64 <= r["output_len"] <= 256
        assert r["prompt_len"] + r["output_len"] < min(4096, context)
    # every system prompt is asked for; Zipf 1.0: the first about eight
    # times as often as the last
    asks = [sum(r["prefix"] == d for r in sched) for d in range(8)]
    assert sum(asks) == 2048 and min(asks) > 60
    assert 6 < asks[0] / asks[7] < 10
    # the shared part is the same text for every request behind a prompt
    a, b = (traffic.messages(p, 5, r)
            for r in [r for r in sched if r["prefix"] == 3][:2])
    assert a[0] == b[0] and len(a[0]["content"]) == 3072
    assert a[1] != b[1]


# ------------------------------------------------------- the reference


def _reference():
    return cells.load_reference(cells.load_cell(LIKE, ROOT))


def _tiny_cfg(**over):
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.from_hf_config({**TINY_LFM2, **over})
    cfg.dtype = "float32"
    return cfg


def test_reference_conv_against_a_per_token_recurrence():
    """The reference writes the convolution as K shifted products over
    the whole sequence; here the same operator token by token from a
    carried state of the last K - 1 gated inputs (what a serving program
    keeps), in numpy float64: the two forms agree to float32 rounding,
    for K = 3 and for K = 4."""
    import jax
    import jax.numpy as jnp

    ref = _reference()
    for K in (3, 4):
        cfg = _tiny_cfg(conv_L_cache=K)
        rng = np.random.default_rng(K)
        D, T = 256, 23
        params = {"w_in": rng.normal(size=(2, D, 3 * D)) / 16,
                  "conv_w": rng.normal(size=(2, K, D)),
                  "w_out": rng.normal(size=(2, D, D)) / 16}
        u = rng.normal(size=(T, D))
        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.short_conv(
                cfg, {k: jnp.asarray(v, jnp.float32)
                      for k, v in params.items()},
                jnp.asarray(u, jnp.float32), 1))
        state = np.zeros((K - 1, D))
        want = []
        for t in range(T):
            b, c, x = np.split(u[t] @ params["w_in"][1], 3)
            window = np.concatenate([state, (b * x)[None]], axis=0)
            conv = (params["conv_w"][1] * window).sum(axis=0)
            want.append((c * conv) @ params["w_out"][1])
            state = window[1:]
        assert np.abs(got - np.asarray(want)).max() < 2e-4


def test_reference_route_against_a_hand_written_top_k():
    import jax.numpy as jnp

    ref = _reference()
    cfg = _tiny_cfg()
    rng = np.random.default_rng(0)
    scores = 1 / (1 + np.exp(-rng.normal(size=(32, 8))))
    bias = 0.2 * rng.normal(size=8)
    got = np.asarray(ref.route(cfg, jnp.asarray(scores, jnp.float32),
                               jnp.asarray(bias, jnp.float32)))
    moved = 0
    for t in range(32):
        chosen = np.argsort(-(scores[t] + bias))[:2]
        moved += set(chosen) != set(np.argsort(-scores[t])[:2])
        want = np.zeros(8)
        want[chosen] = scores[t][chosen] / (scores[t][chosen].sum() + 1e-6)
        assert np.abs(got[t] - want).max() < 1e-6
    assert moved > 3, "the bias was meant to change some sets"


def test_reference_imports_nothing_of_the_programs_models():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME,
                           "reference.py")) as f:
        src = f.read()
    assert "import" in src
    for line in src.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert "dynamo_tpu" not in line, line


# ------------------------------------ the readers, on a hand-made trace

PROJ = "jit(decode_window)/while/body/conv/conv.proj/dot_general:"
MIX = "jit(prefill_step)/while/body/conv/conv.mix/mul:"
SNAP = "jit(prefill_step)/state.snapshot/scatter:"
PICK = "jit(decode_window)/state.snapshot/select_n:"
ATTN = "jit(decode_window)/attn/dot_general:"
OPS = {1: "%fusion.1 = f32[64,6144]{1,0} fusion(bf16[64] %p)",
       2: "%fusion.2 = f32[8,514,2048]{2,1,0} fusion(f32[8] %p)",
       3: "%scatter.3 = bf16[4096,24576]{1,0} scatter(bf16[4096] %p)",
       4: "%fusion.4 = bf16[64,6,2,2048]{3,2,1,0} fusion(pred[64] %p)",
       5: "%fusion.5 = bf16[64,2048]{1,0} fusion(bf16[64] %p)",
       6: "%while.6 = (s32[], f32[4]) while(%t), body=%b"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the conv projections 0-300, the mix
    (inside a prefill's loop, which a while spans) 300-450, the snapshot
    scatter 450-500, the window's choice of a row's state 500-520,
    attention 520-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, PROJ))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, MIX))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, SNAP))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, PICK))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, ATTN))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, MIX))
        + _line("XLA Ops", [(1, 0, 300), (6, 300, 150), (2, 300, 150),
                            (3, 450, 50), (4, 500, 20), (5, 520, 480)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_lfm2")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


LFM2 = {"num_hidden_layers": 8, "layer_types": KINDS * 5}
STATS = {"stats1": {counters.PHASES_KEY: {"idle": 1.0}}}
RAW = {"trace": {"busy_s": 1000e-6}, "model": {"config": LFM2}, **STATS}


def _reader(name):
    return cells.load_reader(name, ROOT)


@pytest.mark.parametrize("name,want", [
    ("conv_busy_share", 45.0),                  # 300 + 150, no container
    ("state_snapshot_busy_share", 7.0)])        # 50 + 20
def test_the_scope_share_readers_by_hand(traced, monkeypatch, name, want):
    read = _reader(name)
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    assert read(RAW) == pytest.approx(want)
    # not traced; a program without the phases (an older parent)
    assert read({**RAW, "trace": None}) is None
    assert read({**RAW, "stats1": {}}) is None


def test_a_trace_without_the_scopes_reads_zero_not_a_fault(traced,
                                                           monkeypatch):
    """Another model's trace (it has ``attn``, so it is a program with
    scopes): the two readers find no op of theirs and say 0, they do not
    raise; the harness lists them for the LFM2 cell alone."""
    for name, scope in (("conv_busy_share", "conv.none"),
                        ("state_snapshot_busy_share", "state.none")):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        from benchmark.harness import scope_ops
        assert scope_ops.path_share(RAW, scope, traced) == 0.0


def test_state_restore_share_by_hand():
    read = _reader("state_restore_share")
    raw = {"stats0": {"state_restores_total": 4, "first_tokens_total": 10},
           "stats1": {"state_restores_total": 49, "first_tokens_total": 60}}
    assert read(raw) == pytest.approx(90.0)     # 45 restores, 50 requests
    # a program without the counter (the parent; a model without state),
    # and a window in which no request started
    assert read({"stats0": {"first_tokens_total": 1},
                 "stats1": {"first_tokens_total": 9}}) is None
    assert read({"stats0": raw["stats0"], "stats1": raw["stats0"]}) is None


def _rows():
    """Two requests: 3 + 2 + 2 tokens at 1.0 / 2.0 / 9.0 s (the first
    chunk holds token 0, from prefill), and 1 + 4 at 2.5 / 3.0 s."""
    return [{"prompt_len": 100, "chunk_s": [1.0, 2.0, 9.0],
             "chunk_n": [3, 2, 2]},
            {"prompt_len": 50, "chunk_s": [2.5, 3.0], "chunk_n": [1, 4]}]


def test_paged_attn_roofline_agent_loop_counts_the_attending_layers():
    """Against the accepted reader on the same raw material: the same
    share with the 2 attending layers of the first 8 in place of 8; heads
    of 64, as published."""
    plain, mine = (_reader("paged_attn_roofline"),
                   _reader("paged_attn_roofline.agent-loop"))
    raw = {"trace": {"kernel_s": 2e-3}, "trace_slice": [1.5, 3.5],
           "rows": _rows(), "device": {"kind": "TPU v5 lite"},
           "model": {"num_layers": 8, "num_heads": 32, "num_kv_heads": 8,
                     "head_dim": 64, "page_size": 64, "kv_itemsize": 2,
                     "config": LFM2}}
    assert mine(raw) == pytest.approx(plain(raw) * 2 / 8)
    assert mine(raw) > 0
    assert mine({**raw, "trace": None}) is None
    # another family's configuration: nothing to read, and no raise
    assert mine({**raw, "model": {**raw["model"], "config": {
        "num_hidden_layers": 28, "attn_layer_period": 14}}}) is None
    assert _reader("paged_attn_roofline.hybrid")(raw) is None
