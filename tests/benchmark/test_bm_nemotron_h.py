"""What PR 60 added to the benchmark for a model whose layer is ONE
sub-block (Mamba-2 in groups, attention without positions, or experts
that are not gated, in a latent, beside a full-width shared expert) and
which holds the chip's share of a layer's experts, on the CPU: a
``tiny-nemotron`` configuration ADDED to a copy of the benchmark by
files alone (its reference is the repo's
``configs/nemotron-3-super-120b-a12b/reference.py``, its traffic a small
closed loop) and run end to end through ``serve.agree``; the repo's own
configuration and cell against the catalog, against ``BENCHMARK.json``
and against the issue's traffic; ``harness/nemotron_work.py`` against a
hand count; the new readers on hand-made counters and a hand-made
trace."""

import json
import os
import shutil

import pytest

from bm_paths import BENCH, ROOT
from test_bm_e2e import _dump, _last_line, _run  # noqa: F401
from test_bm_host_trace import (_event_meta, _int, _line, _msg,  # noqa: F401
                                _stat_meta)

from benchmark.harness import cells, counters, nemotron_work, roofline, \
    ssd_work

CELL = "tiny-nemotron.tiny-closed"
LIKE = "nemotron-3-super-120b-a12b.agent-reason"
NAME = "nemotron-3-super-120b-a12b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
PATTERN = "MEMEMEM*EME"
TINY = {
    "model_type": "nemotron_h", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 11,
    "hybrid_override_pattern": PATTERN + "MEMEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "expand": 2, "chunk_size": 8,
    "moe_intermediate_size": 32, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "n_routed_experts": 6, "router_num_experts": 12,
    "first_local_expert": 0, "num_experts_per_tok": 3,
    # 0.68 = 5 x 3 / 22: a chosen pair weighs 0.23 as at 22 of 512 x 5
    # (at 3 of 12 a scale of 5 makes it 1.67, and the bf16 engine's
    # routed sums then drown a 64-wide stream: median 0.108)
    "routed_scaling_factor": 0.68, "norm_topk_prob": True,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-05, "use_conv_bias": True,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
    "max_position_embeddings": 2048}
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
def nroot(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to: one
    configuration (the cell's weight scales, an embedding of unit RMS at
    this width), one traffic mix, one cell that reports what the repo's
    own Nemotron cell reports."""
    root = str(tmp_path_factory.mktemp("bench_copy_nemotron"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    os.makedirs(os.path.join(bdir, "configs", "tiny-nemotron"))
    _dump(os.path.join(bdir, "configs", "tiny-nemotron", "config.json"),
          TINY)
    _dump(os.path.join(bdir, "configs", "tiny-nemotron", "about.json"), {
        "reference": f"benchmark/configs/{NAME}/reference.py",
        "weight_scales": dict(_about()["weight_scales"], embed=8.0)})
    b["configs"].append({
        "name": "tiny-nemotron", "source": "test", "reduced": [],
        "why": "test",
        "file": "benchmark/configs/tiny-nemotron/config.json"})
    _dump(os.path.join(bdir, "traffic", "tiny-closed.json"), TRAFFIC)
    _dump(os.path.join(bdir, "workloads", CELL + ".json"), {
        "config": "tiny-nemotron", "traffic": "tiny-closed", "chips": 1,
        "engine": ENGINE})
    b["workloads"].append({"name": CELL, "config": "tiny-nemotron",
                           "traffic": "tiny-closed", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def test_the_tiny_nemotron_cell_end_to_end(nroot):
    """``correct`` true on the CPU: the engine (bf16; prompts of up to two
    prefill chunks of 64 = sixteen scan chunks of 8, the state carried
    through the pool; windows on gathered rows; half of every token's
    expert pairs routed to experts that are not here; a mixer with no
    second half and a last layer of experts) against the repo's plain
    reference given the same share, under the harness's one rule, and a
    closed-loop window with no failed request."""
    proc = _run(nroot, CELL, 0, seconds=4)
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


def test_a_traced_run_reads_every_counter_metric_then_is_refused(nroot):
    """No /device:TPU plane on the CPU: the trace readers of the cell
    return None by their own rule, none raises, and the run is refused
    as no measurement."""
    proc = _run(nroot, CELL, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


NEW = {"moe_latent_busy_share", "moe_router_busy_share",
       # accepted quantities under names of the cell's own: their
       # accepted entries' lists are pinned to one cell each by
       # test_bm_granite.py / test_bm_kimi_linear.py / test_bm_kanana.py,
       # and the Mamba-2 readers ask for granite's keys
       "ssd_step_roofline.agent-reason", "ssd_chunk_roofline.agent-reason",
       "moe_held_pair_share.agent-reason",
       "state_carried_chunk_share.agent-reason",
       "moe_shared_busy_share.agent-reason"}
# the accepted quantities the cell is appended to
SHARED = {"moe_busy_share", "ssm_busy_share", "paged_attn_busy_share",
          "state_pool_fill_share", "output_tok_s.tpot"}


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
    # no variant of the attention kernel's roofline: one layer in 11
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
    assert "quarter" in entry["why"] and "1 layer in 11" in entry["why"]
    assert NAME in [c["name"] for c in bench["configs"]]


def test_the_cell_reports_its_readers():
    benchmark_lists_hold(cells.load_benchmark(ROOT))
    for m in cells.metrics_for(LIKE, "per_layer", ROOT):
        assert os.path.isfile(cells.reader_path(m["name"], ROOT))
    for name in NEW:        # a file of its own each, not the quantity's
        assert cells.reader_path(name, ROOT).endswith(name + ".py")


# ------------------------------------------- the repo's own cell's files


def test_the_configuration_is_the_catalogs_but_for_its_named_cuts():
    """``published`` equals the catalog row's ``config`` key by key; the
    file as run differs from it in the four keys ``reduced`` names and
    in nothing else, and states the share beside the published count;
    every width is as published."""
    cell = cells.load_cell(LIKE, ROOT)
    about = _about()
    run, published = cell["model_config"], about["published"]
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == CATALOG_NAME)
        assert published == row["config"]
        assert about["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers"]
    assert about["reduced"] == reduced
    assert set(about["reduced_why"]) == set(reduced)
    share = {"router_num_experts", "first_local_expert"}
    assert {k for k in set(run) | set(published)
            if run.get(k) != published.get(k)} == set(reduced) | share
    assert (published["num_hidden_layers"], run["num_hidden_layers"]) \
        == (88, 11)
    assert (published["n_routed_experts"], run["n_routed_experts"],
            run["router_num_experts"], run["first_local_expert"]) \
        == (512, 128, 512, 0)
    assert (published["vocab_size"], run["vocab_size"]) == (131072, 32768)
    assert (published["num_nextn_predict_layers"],
            run["num_nextn_predict_layers"]) == (1, 0)
    # the whole published pattern is kept; one whole period runs, with
    # the published 40 : 40 : 8 of its three kinds
    pattern = run["hybrid_override_pattern"]
    assert pattern == published["hybrid_override_pattern"]
    assert len(pattern) == 88
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    ran = pattern[:run["num_hidden_layers"]]
    assert ran == PATTERN and [ran.count(k) for k in "ME*"] == [5, 5, 1]
    # the guide's floors: a period and four layers, 8 experts, an eighth
    # of the vocabulary
    assert run["num_hidden_layers"] >= 4 and run["n_routed_experts"] >= 8
    assert run["vocab_size"] * 8 >= published["vocab_size"]
    assert (run["hidden_size"], run["mamba_num_heads"],
            run["mamba_head_dim"], run["ssm_state_size"], run["n_groups"],
            run["conv_kernel"], run["chunk_size"],
            run["num_attention_heads"], run["num_key_value_heads"],
            run["head_dim"], run["moe_latent_size"],
            run["moe_intermediate_size"],
            run["moe_shared_expert_intermediate_size"],
            run["num_experts_per_tok"], run["routed_scaling_factor"]) == (
                4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 1024, 2688,
                5376, 22, 5)
    for key in ("assumed", "stands_for", "caveat", "memory", "reference",
                "weight_scales", "weight_scales_why"):
        assert about[key], key
    assert "4 chips share each layer" in about["stands_for"]
    assert "32 v5e chips" in about["stands_for"]
    assert about["memory"]["fits"] and about["memory"]["peak_gb"] < 15.75
    # a quarter of one chip's memory, by what is resident alone
    assert about["memory"]["resident_gb"] > 0.25 * 15.75
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
        NAME, "agent-reason", 1) == (cell["config"], cell["traffic"],
                                     cell["chips"])
    t, e = cell["traffic_params"], cell["engine"]
    assert (t["loop"], t["clients"], t["pool"]) == ("closed", 128, 1024)
    assert "shared_prefix" not in t
    # doc-reason's lengths on purpose, under a base_seed of its own
    with open(os.path.join(BENCH, "traffic", "doc-reason.json")) as f:
        doc = json.load(f)
    assert t["prompt_len"] == doc["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256,
        "max": 7168}
    assert t["output_len"] == doc["output_len"] == {
        "dist": "uniform", "min": 512, "max": 1536}
    assert t["base_seed"] != doc["base_seed"]
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert e["max_batch"] == t["clients"] == e["batch_buckets"][-1] == 128
    assert e["prefill_chunk"] == 512
    assert e["page_buckets"][-1] * e["page_size"] >= longest
    assert e["max_prefill_batch"] in e["batch_buckets"]


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


# ---------------------------------------------------------- the readers


def _reader(name):
    return cells.load_reader(name, ROOT)


NEMOTRON = {"model_type": "nemotron_h", "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "num_hidden_layers": 11, "moe_latent_size": 1024,
            "n_shared_experts": 1,
            "hybrid_override_pattern": PATTERN * 8}
SHAPES = {"heads": 128, "head_dim": 64, "d_state": 128, "layers": 5}


def test_nemotron_work_by_hand():
    """Shapes from the configuration as it is run: 5 of the first 11
    layers are Mamba-2 layers; the accepted work functions then count
    one row-step at 2 x 4 MiB of state a layer, and the issue's floor of
    the state's traffic: 128 rows x 5 layers x 8 MiB at 819 GB/s. B and C
    of seven of the eight groups are NOT in the floor (3.5 KB of 8 MiB a
    row-step a layer): it errs low."""
    assert nemotron_work.shapes(NEMOTRON) == SHAPES
    assert nemotron_work.shapes({"mamba_n_heads": 128}) is None
    assert nemotron_work.shapes({"mamba_num_heads": 128}) is None
    keyed = nemotron_work._granite_keys(NEMOTRON)
    assert ssd_work.mamba2_shapes(keyed) == SHAPES
    assert keyed["layer_types"] == [
        {"M": "mamba", "E": "moe", "*": "attention"}[k] for k in PATTERN]
    ops, bytes_ = ssd_work.ssd_decode(128, **SHAPES)
    least = roofline.least_seconds(ops, bytes_, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(5.37e9 / 819e9, rel=0.01)
    left_out = 2 * 7 * 128 * 2
    assert left_out / ssd_work.ssd_decode(1, **dict(SHAPES, layers=1))[1] \
        < 5e-4


def test_the_counter_readers_by_hand():
    held = _reader("moe_held_pair_share.agent-reason")
    carried = _reader("state_carried_chunk_share.agent-reason")
    raw = {"model": {"config": NEMOTRON},
           "stats0": {"moe_pairs_routed_total": 1000,
                      "moe_pairs_held_total": 260,
                      "prefill_row_chunks_total": 10,
                      "prefill_row_chunks_carried_total": 4},
           "stats1": {"moe_pairs_routed_total": 9000,
                      "moe_pairs_held_total": 2260,
                      "prefill_row_chunks_total": 110,
                      "prefill_row_chunks_carried_total": 64}}
    assert held(raw) == pytest.approx(25.0)
    assert carried(raw) == pytest.approx(60.0)
    # the parent's program (no counters), and another family's run
    assert held({**raw, "stats0": {}, "stats1": {}}) is None
    assert carried({**raw, "stats0": {}, "stats1": {}}) is None
    other = {**raw, "model": {"config": {"mamba_n_heads": 128}}}
    assert held(other) is None and carried(other) is None


W = "jit(decode_window)/while/body/"
STEP = W + "ssm/ssm.scan/jit(ssd_step)/pallas_call:"
CHUNK = "jit(prefill_step)/while/body/ssm/ssm.scan/while/body/dot_general:"
ROUTER = W + "moe/moe.router/top_k:"
LATENT = W + "moe/moe.latent/dot_general:"
LATENT_P = "jit(prefill_step)/moe/moe.latent/dot_general:"
EXPERTS = W + "moe/moe.experts/dot_general:"
SHARED_E = W + "moe/moe.shared/dot_general:"
OPS = {1: "%ssd_step.1 = (f32[128,1,8192], f32[129,5,128,8192]) custom-call()",
       2: "%fusion.2 = f32[8,512,128,64]{3,2,1,0} fusion(f32[8] %p)",
       3: "%fusion.3 = f32[128,22]{1,0} fusion(f32[128,512] %p)",
       4: "%fusion.4 = f32[128,1024]{1,0} fusion(bf16[128] %p)",
       5: "%fusion.5 = f32[4096,1024]{1,0} fusion(bf16[4096] %p)",
       6: "%fusion.6 = f32[128,1,128,2688]{3,2,1,0} fusion(bf16[128] %p)",
       7: "%fusion.7 = bf16[128,5376]{1,0} fusion(bf16[128] %p)"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One chip, 1,000 us busy: the step kernel 0-250 (decode_window),
    the chunked scan 250-350 (prefill_step), the router 350-400, the
    latent pair 400-440 in the window and 440-460 in prefill, the
    experts 460-900, the shared expert 900-1000."""
    device = (
        _msg(2, "/device:TPU:0") + _stat_meta(1, "tf_op")
        + _event_meta(1, OPS[1], _int(1, 1) + _msg(5, STEP))
        + _event_meta(2, OPS[2], _int(1, 1) + _msg(5, CHUNK))
        + _event_meta(3, OPS[3], _int(1, 1) + _msg(5, ROUTER))
        + _event_meta(4, OPS[4], _int(1, 1) + _msg(5, LATENT))
        + _event_meta(5, OPS[5], _int(1, 1) + _msg(5, LATENT_P))
        + _event_meta(6, OPS[6], _int(1, 1) + _msg(5, EXPERTS))
        + _event_meta(7, OPS[7], _int(1, 1) + _msg(5, SHARED_E))
        + _line("XLA Ops", [(1, 0, 250), (2, 250, 100), (3, 350, 50),
                            (4, 400, 40), (5, 440, 20), (6, 460, 440),
                            (7, 900, 100)])
        + _line("XLA Modules", []))
    root = tmp_path_factory.mktemp("traced_root_nemotron")
    d = root / ".bench_trace" / "cell" / "plugins" / "profile" / "t1"
    d.mkdir(parents=True)
    (d / "hand.xplane.pb").write_bytes(_msg(1, device))
    return str(root / "benchmark" / "metrics" / "reader.py")


def _raw():
    rows = [{"prompt_len": 700, "chunk_s": [11.0, 12.0, 29.0],
             "chunk_n": [2, 2, 4]}]
    return {"trace": {"busy_s": 1000e-6}, "trace_slice": [10.0, 15.0],
            "window_s": 50.0, "rows": rows,
            "device": {"kind": "TPU v5 lite"},
            "stats0": {"prefill_tokens_total": 0},
            "stats1": {"prefill_tokens_total": 2560,
                       counters.PHASES_KEY: {"idle": 1.0}},
            "model": {"kv_itemsize": 2, "config": NEMOTRON}}


def _at(read, traced, monkeypatch):
    """The reader, and the accepted reader it goes through, looking for
    the trace beside the hand-made root."""
    monkeypatch.setitem(read.__globals__, "__file__", traced)
    return read


def test_the_scope_readers_by_hand(traced, monkeypatch):
    """``moe_latent_busy_share`` 6% (both programs), ``moe_router_busy_
    share`` 5%, the shared expert's 10% through the accepted reader; the
    accepted ``moe`` and ``ssm`` readers find the module's scopes. Silent
    for another family, an untraced run, a program without the
    phases."""
    raw = _raw()
    for name, want in (("moe_latent_busy_share", 6.0),
                       ("moe_router_busy_share", 5.0),
                       ("moe_busy_share", 65.0), ("ssm_busy_share", 35.0)):
        read = _at(_reader(name), traced, monkeypatch)
        assert read(raw) == pytest.approx(want), name
    for name in ("moe_latent_busy_share", "moe_router_busy_share"):
        read = _reader(name)
        monkeypatch.setitem(read.__globals__, "__file__", traced)
        assert read({**raw, "model": {**raw["model"], "config": {
            "mamba_n_heads": 128}}}) is None
        assert read({**raw, "stats1": {}}) is None
    shared = _at(_reader("moe_shared_busy_share"), traced, monkeypatch)
    mine = _reader("moe_shared_busy_share.agent-reason")
    monkeypatch.setattr(cells, "load_reader", lambda name, root=ROOT: {
        "moe_shared_busy_share": shared}[name])
    assert mine(raw) == pytest.approx(10.0)


def test_the_two_roofline_readers_by_hand(traced, monkeypatch):
    """``ssd_step_roofline.agent-reason``: 3 row-steps x 5 layers of
    state read and written over the 250 us under ``ssm.scan`` in
    ``decode_window``; ``ssd_chunk_roofline.agent-reason``: 256 prompt
    tokens' vectors over the 100 us in ``prefill_step``: the accepted
    readers, handed this family's shapes. Both under 100%, and silent
    for another configuration and an untraced run; the accepted entries'
    own readers read NOTHING of this configuration (they ask for
    granite's keys)."""
    raw = _raw()
    accepted = {n: _at(_reader(n), traced, monkeypatch)
                for n in ("ssd_step_roofline", "ssd_chunk_roofline")}
    assert all(read(raw) is None for read in accepted.values())
    step = _reader("ssd_step_roofline.agent-reason")
    chunk = _reader("ssd_chunk_roofline.agent-reason")
    monkeypatch.setattr(cells, "load_reader",
                        lambda name, root=ROOT: accepted[name])
    least = roofline.least_seconds(*ssd_work.ssd_decode(3, **SHAPES),
                                   "TPU v5 lite")
    assert step(raw) == pytest.approx(100.0 * least["seconds"] / 250e-6)
    least = roofline.least_seconds(*ssd_work.ssd_prefill(256, **SHAPES),
                                   "TPU v5 lite")
    assert chunk(raw) == pytest.approx(100.0 * least["seconds"] / 100e-6)
    assert 0 < step(raw) <= 100 and 0 < chunk(raw) <= 100
    other = {**raw, "model": {**raw["model"],
                              "config": {"mamba_d_state": 16}}}
    for read in (step, chunk):
        assert read(other) is None
        assert read({**raw, "trace": None}) is None
