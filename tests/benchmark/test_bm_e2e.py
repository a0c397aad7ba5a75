"""The whole command end to end at tiny size on the CPU, from a temp
copy of the benchmark to which one configuration, one traffic mix, one
cell and one per-layer metric are ADDED as files and BENCHMARK.json
entries — no file that exists is edited. The device check is stubbed
(``require_platform=None``) only here; without the stub the same
command exits non-zero and prints no result line."""

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
NEW_METRIC = '''"""Added by the test: requests the clients sent."""


def read(raw):
    return len(raw["rows"])
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
    os.makedirs(os.path.join(bdir, "configs", "tiny-moe"))
    _dump(os.path.join(bdir, "configs", "tiny-moe", "config.json"),
          TINY_CONFIG)
    b["configs"].append({
        "name": "tiny-moe", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/tiny-moe/config.json"})
    for mix, params in TRAFFIC.items():
        cell = f"tiny-moe.{mix}"
        _dump(os.path.join(bdir, "traffic", mix + ".json"), params)
        _dump(os.path.join(bdir, "workloads", cell + ".json"), {
            "config": "tiny-moe", "traffic": mix, "chips": 1,
            "engine": TINY_ENGINE})
        b["workloads"].append({"name": cell, "config": "tiny-moe",
                               "traffic": mix, "chips": 1, "why": "test"})
    with open(os.path.join(bdir, "metrics", "requests_sent.py"), "w") as f:
        f.write(NEW_METRIC)
    b["per_layer"].append({
        "name": "requests_sent", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "HTTP frontend and client",
        "moves": "tpot_p50_ms", "workloads": ["tiny-moe.tiny-open"]})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and m["name"] != "requests_sent":
            m["workloads"].append(
                "tiny-moe.tiny-closed" if m["name"] == "output_tok_s"
                else "tiny-moe.tiny-open")
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


def test_closed_loop_cell_end_to_end(root):
    proc = _run(root, "tiny-moe.tiny-closed", 0, seconds=3)
    line = _last_line(proc)
    assert line["correct"] is True and line["attempted"] >= 3
    assert set(line["metrics"]) == {"tpot_p50_ms", "output_tok_s",
                                    "setup_s"}
    notes = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"note"')]
    client = next(n for n in notes if n["note"] == "client")
    assert client["cut_by_window_end"] <= 3 and client["failed"] == 0


def test_traced_run_without_a_device_plane_is_refused(root):
    """On the CPU the profiler's trace has no /device:TPU plane: the
    per-layer line must not appear (a traced run in which no operation
    ran on a device is no measurement)."""
    proc = _run(root, "tiny-moe.tiny-open", 1, seconds=6)
    assert proc.returncode != 0
    assert "no operation on a device" in proc.stderr
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
