"""bench.py is driver-facing and load-bearing. These tests pin its
failure contract — no chip or a scenario that raised ends in a NON-ZERO
exit and a record without a number, never a CPU number in a device
metric's place; every record names platform / device_kind / count; no
child process touches the backend — and measure()'s aggregation, all
with fakes; no TPU."""

import asyncio
import io
import json
import os
import subprocess
import sys
import types
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def make_args(**over):
    base = dict(sweep=None, scenario="sharegpt", isl=512, osl=128,
                requests=64, concurrency=32, model="1b", dtype="bf16",
                users=16, turns=4, host_pages=0, disagg_threshold=256)
    base.update(over)
    return types.SimpleNamespace(**base)


# ------------------------------------------------------------ emit contract


def record_of(fn, *a):
    buf = io.StringIO()
    with redirect_stdout(buf):
        fn(*a)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert len(lines) == 1, f"must print exactly ONE record: {lines}"
    return json.loads(lines[-1])


@pytest.mark.parametrize("over,unit", [
    ({}, "tok/s"),
    ({"scenario": "multiturn"}, "ms"),
    ({"scenario": "disagg"}, "ratio"),
    ({"sweep": "32:64:4"}, "tok/s"),
    ({"sweep": "32:64:4", "scenario": "multiturn"}, "tok/s"),  # sweep wins
    ({"model": "8b", "dtype": "int8"}, "tok/s"),
    ({"scenario": "sharded", "dp_replicas": 2, "mesh": "model=2"},
     "tok/s"),
    ({"scenario": "failover"}, "tok/s"),
    ({"scenario": "hotpath", "decode_steps": 16}, "ms"),
])
def test_emit_error_matches_metric_name(over, unit):
    """An error record must carry the SAME metric label (and a
    consistent unit) as the success record for the same invocation, or
    the driver cannot pair them — and never a number."""
    args = make_args(**over)
    rec = record_of(bench.emit_error, args, "test reason")
    assert rec["metric"] == bench.metric_name(args)
    assert rec["unit"] == unit
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert rec["error"] == "test reason"


def test_int8_model_tag_in_label():
    assert "8b-int8 llama" in bench.metric_name(
        make_args(model="8b", dtype="int8"))
    assert "1b llama" in bench.metric_name(make_args())


# ------------------------------------------------------- the device record


def fake_devices(monkeypatch, platform, kind, n=1):
    import jax

    devs = [types.SimpleNamespace(platform=platform, device_kind=kind)
            for _ in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)


def test_device_record_names_the_tpu(monkeypatch):
    fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=4)
    assert bench.device_record(make_args(cpu=False)) == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4}


def test_device_record_refuses_cpu_without_flag(monkeypatch):
    fake_devices(monkeypatch, "cpu", "cpu")
    with pytest.raises(bench.NoChip, match="no chip"):
        bench.device_record(make_args(cpu=False))


def test_device_record_refuses_any_other_accelerator(monkeypatch):
    """'not cpu' is not enough: the record must say tpu."""
    fake_devices(monkeypatch, "gpu", "H100")
    with pytest.raises(bench.NoChip):
        bench.device_record(make_args(cpu=False))


def test_device_record_cpu_flag_allows_cpu(monkeypatch):
    fake_devices(monkeypatch, "cpu", "cpu", n=8)
    rec = bench.device_record(make_args(cpu=True))
    assert rec["platform"] == "cpu" and rec["device_count"] == 8


def test_backend_failure_is_not_swallowed(monkeypatch):
    """A backend that fails to initialise raises out of device_record
    (main turns it into the error record + exit 1) — nothing retries on
    the CPU."""
    import jax

    def boom(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="initialize backend"):
        bench.device_record(make_args(cpu=False))


# ------------------------------------------------- main() failure envelopes


def run_main(monkeypatch, argv, **patches):
    """(exit code, the ONE stdout record). The compile-cache helper is
    stubbed: it would re-point this test process's jax cache."""
    from dynamo_tpu.runtime import compile_cache

    monkeypatch.setattr(sys, "argv", ["bench.py"] + argv)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "stubbed")
    for name, val in patches.items():
        monkeypatch.setattr(bench, name, val)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench.main()
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert len(lines) == 1, f"driver expects ONE stdout line: {lines}"
    return rc, json.loads(lines[-1])


TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def test_main_no_chip_exits_nonzero_without_a_number(monkeypatch):
    fake_devices(monkeypatch, "cpu", "cpu")
    ran = []
    rc, rec = run_main(monkeypatch, [],
                       _run_scenario=lambda a: ran.append(a) or {})
    assert rc != 0 and not ran, "no scenario may run without a chip"
    assert rec["value"] is None and "no chip" in rec["error"]
    assert rec["metric"] == bench.metric_name(make_args())


def test_main_spec_without_chip_fails(monkeypatch):
    """--spec used to re-run itself on the CPU at tiny size and exit 0
    with a 'degraded' number; now it fails like everything else."""
    fake_devices(monkeypatch, "cpu", "cpu")
    ran = []
    rc, rec = run_main(monkeypatch, ["--spec"],
                       _run_spec_ab=lambda a: ran.append(a) or {})
    assert rc != 0 and not ran
    assert rec["value"] is None and "degraded" not in rec
    assert "1 chip" in rec["metric"] and "cpu smoke" not in rec["metric"]


def test_main_scenario_exception_exits_nonzero(monkeypatch):
    def boom(args):
        raise RuntimeError("engine died mid-run")

    rc, rec = run_main(monkeypatch, [], device_record=lambda a: dict(TPU),
                       _run_scenario=boom)
    assert rc != 0
    assert rec["value"] is None
    assert "RuntimeError: engine died mid-run" in rec["error"]


def test_main_success_record_names_the_device(monkeypatch):
    good = {"metric": "m", "value": 123.0, "unit": "tok/s",
            "vs_baseline": 1.0}
    rc, rec = run_main(monkeypatch, [], device_record=lambda a: dict(TPU),
                       _run_scenario=lambda a: dict(good))
    assert rc == 0
    assert rec == {**good, **TPU}


def test_main_starts_no_child_process(monkeypatch):
    """One process per chip: main() itself never spawns anything (the
    probe child that initialised the backend before the parent did is
    gone)."""
    def no_children(*a, **k):
        raise AssertionError("bench.main() started a child process")

    monkeypatch.setattr(subprocess, "Popen", no_children)
    monkeypatch.setattr(os, "fork", no_children)
    rc, rec = run_main(monkeypatch, [], device_record=lambda a: dict(TPU),
                       _run_scenario=lambda a: {"value": 1.0})
    assert rc == 0 and rec["platform"] == "tpu"


def test_main_does_not_swallow_interrupts(monkeypatch):
    """Time limits are the caller's: a KeyboardInterrupt/SystemExit out
    of a scenario propagates instead of becoming an exit-0 record."""
    def stop(args):
        raise KeyboardInterrupt

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "device_record", lambda a: dict(TPU))
    monkeypatch.setattr(bench, "_run_scenario", stop)
    from dynamo_tpu.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "x")
    with pytest.raises(KeyboardInterrupt):
        bench.main()


def test_bench_process_without_chip_exits_nonzero(tmp_path):
    """True e2e in a subprocess: `python bench.py` where JAX finds only
    the CPU exits non-zero and its one stdout line has no number."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                           "--requests", "2"], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-500:])
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] is None and "no chip" in rec["error"]
    assert not os.listdir(tmp_path), "nothing compiled, nothing cached"


# ------------------------------------------------------ measure() contract


class FakeEngine:
    """Yields `chunks` per request: list of (token_ids, finish_reason,
    delay_s) — enough to script TTFT/ITL/error shapes."""

    def __init__(self, chunks):
        self.chunks = chunks

    async def generate(self, req, ctx):
        for token_ids, fin, delay in self.chunks:
            await asyncio.sleep(delay)
            yield types.SimpleNamespace(token_ids=token_ids,
                                        finish_reason=fin)


def test_measure_aggregates_and_raw_itl():
    eng = FakeEngine([
        ([1], None, 0.02),          # first token: TTFT ~20ms
        ([2, 3], None, 0.04),       # chunk gap 40ms
        ([4, 5], "stop", 0.04),     # chunk gap 40ms
    ])
    rep = asyncio.run(bench.measure(eng, [([7] * 4, 5)] * 3, 2))
    assert rep["requests"] == 3 and rep["errors"] == 0
    assert rep["ttft_p50_ms"] and rep["ttft_p50_ms"] >= 15
    # window-amortized: (last-first)/(n-1) = 80ms/4 = ~20ms
    assert 10 <= rep["itl_p50_ms"] <= 40
    # raw chunk gaps: ~40ms each — the un-amortized truth
    assert 30 <= rep["itl_raw_chunk_p50_ms"] <= 80
    assert rep["itl_raw_chunk_p99_ms"] >= rep["itl_raw_chunk_p50_ms"]


def test_measure_error_rows_excluded():
    eng = FakeEngine([([1], "error", 0.0)])
    rep = asyncio.run(bench.measure(eng, [([7], 3)] * 2, 2))
    assert rep["errors"] == 2 and rep["requests"] == 0
    assert rep["output_tok_per_s"] == 0.0


def test_measure_request_timeout_is_error_row(monkeypatch):
    monkeypatch.setenv("DYN_BENCH_REQ_TIMEOUT", "0.3")

    class HangingEngine:
        async def generate(self, req, ctx):
            yield types.SimpleNamespace(token_ids=[1], finish_reason=None)
            await asyncio.sleep(60)

    rep = asyncio.run(bench.measure(HangingEngine(), [([7], 3)], 1))
    assert rep["errors"] == 1 and rep["requests"] == 0


def test_disagg_label_reflects_transfer_int8(monkeypatch):
    args = make_args(scenario="disagg")
    base = bench.metric_name(args)
    monkeypatch.setenv("DYN_KV_TRANSFER_INT8", "1")
    assert "kv-int8" in bench.metric_name(args)
    monkeypatch.delenv("DYN_KV_TRANSFER_INT8")
    assert bench.metric_name(args) == base
    assert "kv-chunks 0,4" in bench.metric_name(
        make_args(scenario="disagg", kv_chunk_pages="0,4"))


def test_disagg_streaming_smoke_cpu():
    """Tier-1 CPU smoke for the streaming transfer plane through the REAL
    disagg bench path: a bulk leg (chunk_pages=0) and a chunked leg on the
    same engines, each reporting the per-stage extract/compress/wire/
    inject breakdown. Pins the sweep plumbing, the per-leg stat deltas,
    and that multi-chunk streams actually went over the wire."""
    args = make_args(scenario="disagg", model="tiny", requests=4,
                     concurrency=2, isl=96, osl=4, seed=0,
                     decode_steps=2, disagg_threshold=16,
                     kv_chunk_pages="0,2", prefill_token_budget=None,
                     host_pages=0, host_tier_int8=False, max_batch=None,
                     spec=False, dtype="bf16")
    report = asyncio.run(bench.run_disagg(args))
    legs = report["disagg_legs"]
    assert [leg["kv_chunk_pages"] for leg in legs] == [0, 2]
    bulk, chunked = legs
    for leg in legs:
        assert leg["errors"] == 0
        assert leg["remote_prefills"] > 0
        assert leg["remote_fallbacks"] == 0
        stages = leg["transfer_stages"]
        assert stages["extract_s"] > 0 and stages["inject_s"] > 0
        assert stages["send_wall_s"] > 0
    # bulk mode sends exactly one frame per request → no chunk frames
    assert bulk["transfer_stages"]["chunks_sent"] == 0
    # 96-token prompts = 6 pages of 16 → ≥3 chunk frames per request
    assert (chunked["transfer_stages"]["chunks_sent"]
            >= 3 * chunked["remote_prefills"])
    assert chunked["transfer_pages"] > 0
    # both legs moved the same pages per request (same workload shape)
    assert report["disagg_over_agg_req_per_s"] > 0
