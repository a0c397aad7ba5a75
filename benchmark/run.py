#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's engine on the chip (weights made on the device from
--seed, the persistent compile cache on, DYN_JIT_FENCE=raise), warms the
cell's own grid, checks agreement with the configuration's reference,
starts the OpenAI frontend in-process, starts the load generator as a
child process that never imports jax, measures for --seconds, and prints
as its LAST line one JSON object: correct, attempted, failed, metrics,
device (and breakdown with --trace 1). --trace 0 reports the cell's
end-to-end metrics, --trace 1 its per-layer metrics (a profiler trace of
a 5 s slice in the middle of the window is taken then). Earlier lines
are notes (sample counts, the agreement check, offered and completed
rates) and are not part of the contract.

Exits non-zero and prints no result line when JAX reports another
platform than tpu or fewer chips than the cell asks for, and when the
program (dynamo_tpu/) is not beside this directory.

--sweep r1,r2,... (builder's tool, not used by the driver) runs one
window per rate on one engine and prints a line per rate: how the knee
in a traffic file was found.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

# none of these touches jax or the program at import
from benchmark.harness import (cells, loadgen, serve, stats,  # noqa: E402
                               trace)

TRACE_SLICE_S = 5.0


def note(kind: str, **fields) -> None:
    print(json.dumps({"note": kind, **fields}), flush=True)


async def _window(cell, engine, base, mdc_name, a, trace_dir=None,
                  rate=None) -> dict:
    """One measured window: the child offers the load, this process
    serves it. Returns the raw material the metric readers take."""
    cmd = [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
           "--url", base, "--model", mdc_name, "--traffic",
           cell["traffic_file"], "--seed", str(a.seed), "--seconds",
           str(a.seconds)]
    if rate is not None:
        cmd += ["--rate", str(rate)]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    stats0 = engine.stats()
    proc = await asyncio.create_subprocess_exec(
        *cmd, stdout=asyncio.subprocess.PIPE, env=env)
    pool_samples: list = []
    sampler = asyncio.create_task(_sample_pool(engine, pool_samples))
    try:
        first = await proc.stdout.readline()
        serve.check(first, "the load generator ended before its window")
        t_open = json.loads(first)["open"]      # CLOCK_MONOTONIC, shared
        tracer = slice_task = None
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = serve.Tracer(trace_dir)
            lead = (t_open + (a.seconds - TRACE_SLICE_S) / 2
                    - time.monotonic())
            slice_task = asyncio.create_task(
                tracer.slice(lead, min(TRACE_SLICE_S, a.seconds)))
        out, _ = await proc.communicate()
        if slice_task:
            await slice_task
    finally:
        sampler.cancel()
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    serve.check(proc.returncode == 0,
                f"the load generator exited {proc.returncode}")
    raw = {"rows": [json.loads(ln) for ln in out.decode().splitlines()
                    if ln],
           "window_s": float(a.seconds), "t_open": t_open,
           "stats0": stats0, "stats1": engine.stats(),
           "pool_samples": [p for p in pool_samples
                            if t_open <= p["t"] <= t_open + a.seconds],
           "traffic": dict(cell["traffic_params"]),
           "engine": {"decode_steps": engine.ecfg.decode_steps},
           "model": {"num_layers": engine.cfg.num_layers,
                     "num_heads": engine.cfg.num_heads,
                     "num_kv_heads": engine.cfg.num_kv_heads,
                     "head_dim": engine.cfg.head_dim_,
                     "page_size": engine.ecfg.page_size,
                     "kv_itemsize": engine.kv_k.dtype.itemsize,
                     # what the six keys above cannot say of another
                     # family: the configuration as run, and the
                     # engine's pools as they are
                     "config": dict(cell["model_config"]),
                     "kv_pools": [{"shape": list(p.shape),
                                   "itemsize": p.dtype.itemsize}
                                  for p in (engine.kv_k, engine.kv_v)]},
           "trace": None, "trace_slice": None}
    if rate is not None:
        raw["traffic"]["rate_rps"] = rate
    if tracer:
        path = trace.find_xplane(trace_dir)
        if path:
            planes = await asyncio.to_thread(trace.load, path)
            raw["trace"] = trace.reduce(planes, tracer.window_s)
            raw["trace_slice"] = [tracer.t0 - t_open,
                                  tracer.t0 - t_open + tracer.window_s]
    return raw


async def _sample_pool(engine, out: list) -> None:
    """Once a second: pages held by running sequences, pages kept for
    reuse by the prefix cache, pages of the pool."""
    while True:
        s = engine.stats()
        out.append({"t": time.monotonic(), "active": s["kv_active_blocks"],
                    "cached": s["kv_cached_blocks"],
                    "total": s["kv_total_blocks"]})
        await asyncio.sleep(1.0)


def _client_notes(raw: dict) -> dict:
    """Sample counts and the rates that are not end-to-end metrics."""
    rows = raw["rows"]
    done = [r for r in rows if stats.ok(r)]
    ttft = [stats.ttft_s(r) for r in rows if not r["cut"]]
    gaps = [g for r in rows for g in stats.gaps_s(r)]
    tpot = [t for t in map(stats.tpot_s, rows) if t is not None]
    return {
        "requests": len(rows), "completed": len(done),
        "cut_by_window_end": sum(r["cut"] for r in rows),
        "failed": sum(stats.failed(r) for r in rows),
        "ttft_samples": len(ttft), "gap_samples": len(gaps),
        "tpot_samples": len(tpot),
        "ttft_p50_ms": stats.finite_ms(stats.pctile(ttft, 0.5)),
        "ttft_mean_ms": stats.finite_ms(sum(ttft) / max(len(ttft), 1)),
        "ttft_p95_ms": stats.finite_ms(stats.pctile(ttft, 0.95)),
        "chunk_gap_p99_ms": stats.finite_ms(stats.pctile(gaps, 0.99)),
        "tpot_p50_ms": stats.finite_ms(stats.pctile(tpot, 0.5)),
        "output_tok_s_in_window":
            stats.tokens_in_window(rows, raw["window_s"]) / raw["window_s"],
        "prompt_tokens_sent": sum(r["prompt_len"] for r in rows),
        "last_end_s": max((r["end_s"] or 0.0) for r in rows) if rows else 0,
        "waiting_at_end": raw["stats1"]["num_requests_waiting"],
        "pool_pages_active_peak": max(
            (p["active"] for p in raw["pool_samples"]), default=None),
        "pool_pages_cached_mean": (
            sum(p["cached"] for p in raw["pool_samples"])
            / max(len(raw["pool_samples"]), 1)),
    }


async def amain(a, cell, dev, root) -> dict:
    reference = cells.load_reference(cell)
    port = serve.free_port()
    args, built = await asyncio.to_thread(serve.build, cell, a.seed, port)
    engine, mdc, _ = built
    try:
        # the agreement check asks for top-20 logprobs, which no request
        # of the window does: its programs compile here, before warmup()
        # arms the compile fence, and the window's grid needs no
        # logprobs variant
        res = await serve.agree(engine, a.seed, reference.reference_logits)
        note("agree", **res)
        t0 = time.monotonic()
        compiles = await asyncio.to_thread(engine.warmup)
        note("warmup", programs=compiles, seconds=time.monotonic() - t0,
             grid=engine.ecfg.warmed_grid())
        trace_dir = (os.path.join(root, ".bench_trace", cell["name"])
                     if a.trace else None)
        async with serving_warm(args, built) as base:
            if a.sweep:
                for rate in a.sweep:
                    raw = await _window(cell, engine, base, mdc.name, a,
                                        rate=rate)
                    note("sweep", rate_rps=rate, **_client_notes(raw),
                         quarters_ttft_p50_ms=_quarters(raw))
                return {}
            raw = await _window(cell, engine, base, mdc.name, a,
                                trace_dir)
        raw["setup_s"] = raw["t_open"] - T_START
        raw["device"] = dev
        note("client", **_client_notes(raw))
    finally:
        await engine.stop()

    kind = "per_layer" if a.trace else "end_to_end"
    metrics = {}
    for m in cells.metrics_for(cell["name"], kind, root):
        value = cells.load_reader(m["name"], root)(raw)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    rows = raw["rows"]
    n_failed = sum(stats.failed(r) for r in rows)
    compiles_after = raw["stats1"]["post_warmup_compiles_total"]
    note("correct", agree=res["ok"], failed=n_failed,
         post_warmup_compiles=compiles_after)
    device = dict(dev, memory_peak_bytes=serve.memory_peak_bytes(
        cell["chips"]))
    line = {"correct": bool(res["ok"] and n_failed == 0
                            and compiles_after == 0),
            "attempted": len(rows), "failed": n_failed,
            "metrics": metrics, "device": device}
    if a.trace:
        serve.check(raw["trace"] is not None,
                    "the traced run saw no operation on a device")
        device["busy_s"] = raw["trace"]["busy_s"]
        device["window_s"] = raw["trace"]["window_s"]
        line["breakdown"] = {"device_ops": raw["trace"]["device_ops"],
                             "idle_gaps": raw["trace"]["idle_gaps"]}
    return line


def _quarters(raw: dict) -> list:
    """Median TTFT of the requests due in each quarter of the window: a
    backlog that grows shows as a rising row."""
    q = raw["window_s"] / 4
    out = []
    for k in range(4):
        xs = [stats.ttft_s(r) for r in raw["rows"]
              if k * q <= (r["due_s"] or 0.0) < (k + 1) * q]
        out.append(stats.finite_ms(stats.pctile(xs, 0.5)))
    return out


@contextlib.asynccontextmanager
async def serving_warm(args, built):
    """serve.serving plus one small request through the whole HTTP path
    before the window: the frontend's lazy set-up (tokenizer, template,
    first connection) is set-up, not the first request's TTFT."""
    import aiohttp

    async with serve.serving(args, built) as base:
        req = {"i": 0, "due_s": 0.0, "prompt_len": 48, "output_len": 8}
        row = loadgen.new_row(req)
        async with aiohttp.ClientSession() as http:
            await loadgen.one_request(
                http, base + "/v1/chat/completions", built[1].name,
                [{"role": "user", "content": "warm " * 9 + "up"}], req,
                time.monotonic(), row)
        serve.check(stats.ok(row), f"the warm-up request failed: {row}")
        yield base


def main(argv=None, require_platform="tpu", root=ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--sweep", default=None,
                    type=lambda s: [float(x) for x in s.split(",")])
    a = ap.parse_args(argv)
    os.environ.setdefault("DYN_JIT_FENCE", "raise")
    try:
        import dynamo_tpu  # noqa: F401 — the system under test
    except ImportError:
        print("benchmark/run.py: the program (dynamo_tpu/) is not beside "
              "this directory; nothing to measure", file=sys.stderr)
        return 1
    cell = cells.load_cell(a.workload, root)
    try:
        from dynamo_tpu.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        dev = serve.device_info(cell["chips"], require_platform)
        line = asyncio.run(amain(a, cell, dev, root))
    except serve.BenchFailed as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    if a.sweep:
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
