"""The whole command end to end at tiny size on the CPU, from a temp
copy of the benchmark to which two configurations (``tiny-moe`` of the
family the harness was written around, ``tiny-mla`` of another: latent
cache, a leading dense layer, routed + shared experts, sigmoid
``noaux_tc`` routing, with its own reference file and weight scales),
two traffic mixes, three cells and two per-layer metrics are ADDED as
files and BENCHMARK.json entries — no file that exists is edited. The
device check is stubbed (``require_platform=None``) only here; without
the stub the same command exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bm_paths import BENCH, ROOT

TINY_CONFIG = {
    "model_type": "mixtral", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 2048}
# routed_scaling_factor: at Moonlight's 2.446 one swapped expert of 8 at
# width 64 moves a position by 1.0-2.1 (FLIP_ATOL is 2.5) on most seeds,
# whatever w_router's scale (1 to 8 tried): a matter of the tiny size,
# and this test is of the plumbing. At 1.0 and _run's seed the 27
# positions read 0.10 at most.
TINY_MLA_CONFIG = {
    "model_type": "deepseek_v3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 1.0,
    "rope_theta": 50000, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "max_position_embeddings": 2048}
# what a configuration's about.json has to say to the harness
ABOUT = {
    "tiny-moe": {"reference": "benchmark/reference.py",
                 "weight_scales": {"w_router": 2.0}},
    "tiny-mla": {"reference": "benchmark/configs/tiny-mla/reference.py",
                 "weight_scales": {"w_router": 2.0,
                                   "router_bias": "zeros"}},
}
MLA_REFERENCE = '''"""Added by the test, for the plumbing only: it wraps the PROGRAM's own
mla.reference_forward (float32 copies of the tiny tree), so it is not
independent of the code under test. The plain MLA reference is the PR's
that adds an MLA configuration to the benchmark."""


def reference_logits(params, cfg, tokens):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import mla

    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return mla.reference_forward(
        params, cfg, jnp.asarray(tokens, jnp.int32)[None])[0]
'''
TINY_ENGINE = {
    "page_size": 16, "num_pages": 64, "max_batch": 4, "batch_buckets": [4],
    "prefill_chunk": 128, "prefill_buckets": [128], "page_buckets": [8],
    "max_prefill_batch": 4, "warmup_logprobs": False}
TRAFFIC = {
    "tiny-open": {
        "loop": "open", "rate_rps": 4.0, "base_seed": 1,
        "shared_prefix": {"count": 2, "chars": 32, "zipf": 1.0},
        "prompt_len": {"dist": "uniform", "min": 41, "max": 60},
        "output_len": {"dist": "uniform", "min": 4, "max": 12},
        "slo": {"ttft_ms": 60000, "gap_ms": 60000}},
    "tiny-closed": {
        "loop": "closed", "clients": 3, "pool": 64, "base_seed": 1,
        "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
        "output_len": {"dist": "uniform", "min": 6, "max": 10}},
}
CELLS = ["tiny-moe.tiny-open", "tiny-moe.tiny-closed",
         "tiny-mla.tiny-closed"]
NEW_METRIC = '''"""Added by the test: requests the clients sent."""


def read(raw):
    return len(raw["rows"])
'''
POOL_METRIC = '''"""Added by the test: bytes of cache the tokens decoded in the window
read at the least (a token attends to its context once in every layer),
over the device seconds of the fusions in the traced slice. The bytes of
a cached token are counted from the engine's pools as they are, whatever
their layout, and checked against the configuration as run."""

from benchmark.harness import host_trace


def read(raw):
    m = raw["model"]
    per_token = 0
    for pool in m["kv_pools"]:
        layers, _pages, heads, page_size, width = pool["shape"]
        if layers != m["num_layers"] or page_size != m["page_size"]:
            raise ValueError(f"pool {pool} is not [L, pages, h, ps, d]")
        per_token += layers * heads * width * pool["itemsize"]
    c = m["config"]
    if "kv_lora_rank" in c:
        latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
        if per_token != m["num_layers"] * latent * m["kv_itemsize"]:
            raise ValueError(f"{per_token} bytes a token is no latent pool")
    seconds = host_trace.op_seconds(raw, "fusion", __file__)
    if not seconds:
        return None
    read_tokens = sum(r["prompt_len"] + j for r in raw["rows"]
                      for j in range(1, sum(r["chunk_n"])))
    return per_token * read_tokens / seconds / 1e9
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """BENCHMARK.json + benchmark/ copied, then only added to."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    bdir = os.path.join(root, "benchmark")
    for name, config in (("tiny-moe", TINY_CONFIG),
                         ("tiny-mla", TINY_MLA_CONFIG)):
        os.makedirs(os.path.join(bdir, "configs", name))
        _dump(os.path.join(bdir, "configs", name, "config.json"), config)
        _dump(os.path.join(bdir, "configs", name, "about.json"), ABOUT[name])
        b["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{name}/config.json"})
    with open(os.path.join(bdir, "configs", "tiny-mla", "reference.py"),
              "w") as f:
        f.write(MLA_REFERENCE)
    for mix, params in TRAFFIC.items():
        _dump(os.path.join(bdir, "traffic", mix + ".json"), params)
    for cell in CELLS:
        config, mix = cell.split(".")
        _dump(os.path.join(bdir, "workloads", cell + ".json"), {
            "config": config, "traffic": mix, "chips": 1,
            "engine": TINY_ENGINE})
        b["workloads"].append({"name": cell, "config": config,
                               "traffic": mix, "chips": 1, "why": "test"})
    added = {"requests_sent": (NEW_METRIC, "tiny-moe.tiny-open"),
             "cache_read_gb_s": (POOL_METRIC, "tiny-mla.tiny-closed")}
    for name, (source, cell) in added.items():
        with open(os.path.join(bdir, "metrics", name + ".py"), "w") as f:
            f.write(source)
        b["per_layer"].append({
            "name": name, "unit": "x", "better": "higher",
            "source": "host_clock", "layer": "HTTP frontend and client",
            "moves": "tpot_p50_ms", "workloads": [cell]})
    # each new cell reports what the cell of today with its loop reports
    like = {"tiny-moe.tiny-open": "mixtral-8x7b.chat-steady",
            "tiny-moe.tiny-closed": "qwen3-30b-a3b.decode-heavy",
            "tiny-mla.tiny-closed": "qwen3-30b-a3b.decode-heavy"}
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and m["name"] not in added:
            m["workloads"] += [c for c in CELLS if like[c] in m["workloads"]]
    _dump(os.path.join(root, "BENCHMARK.json"), b)
    return root


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _run(root, cell, trace, seconds=4):
    """python -c 'run.main(..., require_platform=None, root=<copy>)':
    the command's own code path with the device check stubbed."""
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark import run; "
            "sys.exit(run.main(%r, require_platform=None, root=%r))" % (
                ROOT, ["--workload", cell, "--seed", str(2 ** 31 + 321),
                       "--seconds", str(seconds), "--trace", str(trace)],
                root))
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=300)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_open_loop_cell_end_to_end(root):
    line = _last_line(_run(root, "tiny-moe.tiny-open", 0))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 16
    assert set(line["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"     # never a device number


@pytest.mark.parametrize("cell", ["tiny-moe.tiny-closed",
                                  "tiny-mla.tiny-closed"])
def test_closed_loop_cell_end_to_end(root, cell):
    proc = _run(root, cell, 0, seconds=3)
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] >= 3
    assert set(line["metrics"]) == {"tpot_p50_ms", "output_tok_s",
                                    "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    client = next(n for n in notes if n["note"] == "client")
    assert client["cut_by_window_end"] <= 3 and client["failed"] == 0


@pytest.mark.parametrize("cell", ["tiny-moe.tiny-open",
                                  "tiny-mla.tiny-closed"])
def test_traced_run_without_a_device_plane_is_refused(root, cell):
    """On the CPU the profiler's trace has no /device:TPU plane: the
    per-layer line must not appear (a traced run in which no operation
    ran on a device is no measurement). The refusal comes after every
    per-layer reader of the cell has read the run's raw material, the
    added ones among them: one that raised would end the run before it."""
    proc = _run(root, cell, 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr, proc.stderr[-3000:]
    assert not any(ln.startswith('{"correct"')
                   for ln in proc.stdout.splitlines())
    # the counters and client-side readers did their work before that
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    assert any(n["note"] == "client" and n["failed"] == 0 for n in notes)


def test_without_a_chip_the_command_exits_nonzero_and_prints_no_result():
    """The command exactly as the driver runs it, on a machine whose JAX
    sees only the CPU."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable] + b["command"][1:] + [
            "--workload", b["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform" in proc.stderr and proc.stdout.strip() == ""


def test_alone_in_a_directory_the_command_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under paths: nothing to
    measure."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "x", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
