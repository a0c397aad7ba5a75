"""Serving benchmark — the framework's north-star measurement harness.

Reproduces the reference's batch-mode benchmarking (launch/dynamo-run
input/batch.rs:42-105: per-request tokens_in/tokens_out/elapsed + aggregate
throughput) against this framework's serving chain: OpenAIPreprocessor →
Backend → JaxEngine (continuous batching, paged KV, prefix cache).

Workload: ShareGPT-like synthetic conversations (lognormal ISL centered
~512, OSL ~128) issued concurrently. Reports output-token throughput as the
headline metric plus req/s and p50/p99 TTFT & ITL, and prints the ONE JSON
line the driver records.

Run on the attached TPU (default; any other platform is a failure, exit
code 1 and no number) or, with --cpu, as a CPU smoke of the control flow:
    python bench.py [--requests N] [--concurrency N] [--cpu] [--model 1b|tiny]

Every record names the device it ran on (platform, device_kind,
device_count). One process touches JAX: time limits are the caller's
(the chip tool's), not this script's.
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import json
import os
import signal
import statistics
import sys
import time

# kill -USR1 <pid> dumps every thread's stack to stderr — the first tool
# to reach for when a scenario wedges
faulthandler.register(signal.SIGUSR1)


def _model_tag(args) -> str:
    dt = getattr(args, "dtype", "bf16")
    return args.model if dt == "bf16" else f"{args.model}-{dt}"


def metric_name(args) -> str:
    """The driver-facing metric label — built in ONE place so success and
    chip-unavailable records for the same invocation always match."""
    if getattr(args, "spec", False):
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "1 chip"
        return ("output tokens/s with speculative decoding, spec on/off "
                f"A/B on a repetitive workload (K={args.spec_tokens}, "
                f"ISL~{args.isl}/OSL {args.osl}, {args.requests} reqs, "
                f"{_model_tag(args)} llama, {smoke})")
    if getattr(args, "sweep", None):
        return ("output tokens/s, best of batch-geometry sweep "
                f"(ISL~{args.isl}/OSL {args.osl}, {_model_tag(args)} "
                "llama, 1 chip)")
    if args.scenario == "multiturn":
        tier = str(args.host_pages) + (
            "-int8" if getattr(args, "host_tier_int8", False) else "")
        return (f"TTFT p50 (later turns), multiturn {args.users}u x "
                f"{args.turns}t, host_pages={tier}")
    if args.scenario == "disagg":
        from dynamo_tpu.runtime.config import env_bool
        x8 = ", kv-int8" if env_bool("DYN_KV_TRANSFER_INT8") else ""
        ch = (f", kv-chunks {args.kv_chunk_pages}"
              if getattr(args, "kv_chunk_pages", None) else "")
        sp = (", shared-prefix A/B"
              if getattr(args, "shared_prefix", False) else "")
        return (f"disagg/agg req/s ratio (1-chip time-shared, threshold "
                f"{args.disagg_threshold}{x8}{ch}{sp})")
    if args.scenario == "sharded":
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "chip"
        return (f"output tokens/s, {args.dp_replicas}x mesh-sharded "
                f"replicas ({getattr(args, 'mesh', None) or 'model=2'}) "
                f"behind the KV router vs one unsharded engine, identical "
                f"workload (ISL~{args.isl}/OSL {args.osl}, "
                f"{args.requests} reqs, {_model_tag(args)} llama, {smoke})")
    if args.scenario == "shared" and getattr(args, "cache_ab", False):
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "1 chip"
        tier = str(args.host_pages) + (
            "-fp16" if getattr(args, "host_tier_fp16", False) else "-int8")
        return (f"realized hit rate + TTFT p95, dynaheat cache A/B "
                f"(arms: lru/serial control, cost-evict, overlap-restore, "
                f"cost+overlap; shared "
                f"{getattr(args, 'shared_shape', 'multi_tenant')}, "
                f"host_pages={tier}, {args.users}u x {args.turns}w, "
                f"{_model_tag(args)} llama, {smoke})")
    if args.scenario == "shared":
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "1 chip"
        return (f"prefix-cache hit rate, shared-prefix workloads "
                f"({getattr(args, 'shared_shape', 'multi_tenant')}) through "
                f"the real HTTP->KV-router->engine stack "
                f"({args.users}u x {args.turns}w, {_model_tag(args)} "
                f"llama, {smoke})")
    if args.scenario == "failover":
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "1 chip"
        return (f"goodput tok/s under mid-burst worker kill with "
                f"mid-stream failover (2 workers, ISL~{args.isl}/OSL "
                f"{args.osl}, {args.requests} reqs) + shed rate under 2x "
                f"overload ({_model_tag(args)} llama, {smoke})")
    if args.scenario == "hotpath":
        smoke = "cpu smoke" if getattr(args, "cpu", False) else "1 chip"
        return (f"ITL raw-chunk p99 ms, decode-heavy hot path ("
                f"ISL~{args.isl}/OSL {args.osl}, {args.requests} reqs, "
                f"conc {args.concurrency}, K={args.decode_steps}, "
                f"{_model_tag(args)} llama, {smoke})")
    return ("output tokens/s, synthetic ShareGPT "
            f"(ISL~{args.isl}/OSL {args.osl}, {args.requests} reqs, "
            f"conc {args.concurrency}, {_model_tag(args)} llama, 1 chip)")


def metric_unit(args) -> str:
    """Companion to metric_name(): the record's unit, with the same
    sweep-outranks-scenario precedence — ONE encoding of which record
    shape an invocation emits (success, sweep, and chip-unavailable
    paths all call this)."""
    if getattr(args, "spec", False) or getattr(args, "sweep", None):
        return "tok/s"
    return {"multiturn": "ms", "disagg": "ratio", "shared": "rate",
            "sharded": "tok/s", "failover": "tok/s",
            "hotpath": "ms"}.get(args.scenario, "tok/s")


class NoChip(RuntimeError):
    """bench.py without --cpu found a platform other than tpu."""


def emit_error(args, reason: str) -> None:
    """The structured record of a run that failed: same metric label as
    the success record, and never a number. main() exits non-zero after
    printing it."""
    print(json.dumps({
        "metric": metric_name(args),
        "value": None, "unit": metric_unit(args), "vs_baseline": None,
        "error": reason,
    }))


def device_record(args) -> dict:
    """The device this process measures on, as JAX reports it — stamped
    on every record. Without --cpu anything but a TPU is a failure: a
    CPU timing is never written under a device metric's name."""
    import jax

    devs = jax.devices()
    rec = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind,
           "device_count": len(devs)}
    if not args.cpu and rec["platform"] != "tpu":
        raise NoChip(f"no chip: jax reports {rec} (pass --cpu for a CPU "
                     f"smoke of the control flow)")
    print(f"devices: {rec}", file=sys.stderr)
    return rec


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--isl", type=int, default=512, help="mean input len")
    ap.add_argument("--osl", type=int, default=128, help="output len")
    ap.add_argument("--cpu", action="store_true", help="CPU smoke mode")
    ap.add_argument("--model", default="1b", choices=["1b", "8b", "tiny"])
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "int8"],
                    help="int8 = weight-only quantization (models/quant.py);"
                         " required for --model 8b on a 16 GB chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-steps", type=int, default=16,
                    help="fused decode window (amortizes dispatch latency)")
    ap.add_argument("--scenario", default="sharegpt",
                    choices=["sharegpt", "multiturn", "disagg", "shared",
                             "sharded", "failover", "hotpath"],
                    help="multiturn = conversations with growing shared "
                         "prefixes (the KV-offload TTFT scenario, "
                         "reference docs/architecture.md:91-96); "
                         "disagg = A/B of disaggregated prefill/decode vs "
                         "aggregated on the same workload (the BASELINE.md "
                         "north-star, reference docs/architecture.md:57-61); "
                         "shared = dynacache shared-prefix workloads "
                         "driven through the REAL HTTP->KV-router->engine "
                         "stack, share vs no-share A/B per shape with the "
                         "router/engine/host-tier attribution breakdown; "
                         "sharded = dynashard A/B: an unsharded single "
                         "engine vs --dp-replicas mesh-sharded replicas "
                         "behind the real HTTP frontend + KV router at "
                         "identical workload (tok/s, mesh_shape, "
                         "compile counts); "
                         "failover = dynarevive robustness bench: a "
                         "2-worker pool behind the KV router with one "
                         "worker killed mid-burst (goodput under churn + "
                         "resume-stall p99 via mid-stream failover) and a "
                         "2x-overload wave against SLO-aware admission "
                         "control (shed rate + admitted TTFT p99); "
                         "hotpath = dynaturbo decode hot-path record: "
                         "decode-heavy/small-batch/long-generation mix "
                         "reporting itl_raw_chunk_p99_ms + loop-lag p99 "
                         "+ the compile fence in ONE record")
    ap.add_argument("--mesh", default=None,
                    help="sharded scenario: per-replica mesh as 'axis=N' "
                         "pairs (e.g. 'model=2'; default DYN_MESH_SHAPE "
                         "or model=2)")
    ap.add_argument("--dp-replicas", type=int, default=2,
                    help="sharded scenario: data-parallel replicas behind "
                         "the KV router")
    ap.add_argument("--shared-shape", default="multi_tenant",
                    choices=["multi_tenant", "rag", "agent", "all"],
                    help="shared scenario workload shape: multi_tenant = "
                         "per-tenant shared system prompts; rag = one long "
                         "common context + distinct questions; agent = "
                         "per-agent growing histories re-sent every turn; "
                         "all = run each in sequence")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="disagg scenario: add a shared-prefix leg (same "
                         "lengths, common 2/3-ISL prompt prefix) so the "
                         "transfer-vs-reuse interaction is measurable — "
                         "decode-side reservations prefix-hit and skip "
                         "transferring the shared pages")
    ap.add_argument("--disagg-threshold", type=int, default=256,
                    help="max local prefill length for the disagg router")
    ap.add_argument("--kv-chunk-pages", default=None,
                    help="disagg scenario: pages per streamed KV chunk "
                         "frame; 0 = legacy single bulk frame. Sweepable "
                         "as a comma list (e.g. '0,4,16') — each value is "
                         "measured as its own disagg leg against the same "
                         "engines, with a transfer-plane stage breakdown "
                         "(extract/compress/wire/inject) per leg")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="chunked-prefill mixing: cap prefill tokens per "
                         "iteration, interleave decode windows")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-DRAM offload tier size (multiturn scenario)")
    ap.add_argument("--host-tier-int8", action="store_true",
                    help="int8-compress the host tier: half the D2H/H2D "
                         "bytes per page move (lossy; "
                         "engine/kv_compress.py). Now the DEFAULT when "
                         "the tier is on — kept for invocation compat")
    ap.add_argument("--host-tier-fp16", action="store_true",
                    help="keep the host tier at pool precision (the "
                         "lossless fallback arm for the int8-default "
                         "A/B)")
    ap.add_argument("--evict-policy", default=None,
                    choices=["lru", "cost"],
                    help="KV eviction policy override for both cache "
                         "tiers (default: engine default = cost; lru is "
                         "the A/B control)")
    ap.add_argument("--restore-overlap", default=None,
                    choices=["on", "off"],
                    help="override the pipelined host-tier restore "
                         "drain (default: engine default = on; off is "
                         "the serial A/B control)")
    ap.add_argument("--cache-ab", action="store_true",
                    help="shared scenario: run the dynaheat four-arm "
                         "cache A/B — lru/serial control, cost-evict, "
                         "overlap-restore, cost+overlap — same workload "
                         "per arm, fresh engine each, HBM pool sized "
                         "below the working set so eviction policy "
                         "actually decides; quotes per-arm TTFT "
                         "p50/p95, realized hit rate, restore wait and "
                         "evict fate split")
    ap.add_argument("--users", type=int, default=16)
    ap.add_argument("--turns", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="override engine max_batch (and batch buckets)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding A/B: run the headline "
                         "workload (made repetitive — the regime prompt-"
                         "lookup drafting targets) with spec_decode off "
                         "then on, report both tok/s plus acceptance "
                         "stats; degrades to a CPU smoke A/B when no "
                         "chip is available")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="max draft tokens verified per step (K)")
    ap.add_argument("--trace", action="store_true",
                    help="dyntrace: record a trace per benched request "
                         "(sampling forced to 1.0) and dump a per-request "
                         "stage breakdown (route/prefill/kv_transfer/"
                         "decode span durations) plus a stage rollup "
                         "after the run")
    ap.add_argument("--trip-incident", action="store_true",
                    help="dynablack: after the workload finishes, trip a "
                         "manual flight-recorder capture in-process and "
                         "write the incident bundle next to --report-out "
                         "(<stem>.incident.json), recording id/workers "
                         "in the report's blackbox block — the chip "
                         "run that proves the armed recorder produces "
                         "a renderable bundle mid-bench")
    ap.add_argument("--report-out", default=None, metavar="PATH",
                    help="also write the full machine-readable record "
                         "(the BENCH_r*.json shape: metric/value/unit/"
                         "vs_baseline + the complete detail report, now "
                         "incl. the dynaslo goodput/per-role-quantile "
                         "block) to PATH, so every round lands in the "
                         "perf trajectory instead of living in stderr")
    ap.add_argument("--sweep", default=None,
                    help="batch-geometry sweep (VERDICT r3 task 3): comma-"
                         "separated conc:max_batch:decode_steps triples, "
                         "e.g. '32:64:4,64:64:8,128:128:16' — runs the "
                         "headline workload at each point, prints one "
                         "result line per point to stderr and a summary "
                         "table, then the best point's record as THE line")
    return ap.parse_args()


def engine_setup(args):
    """The bench engine-config assembly, shared by the single-engine
    build and the dynashard replica set: (model_cfg, engine_cfg, params,
    quant)."""
    from dynamo_tpu.engine.jax_engine import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    if args.model == "tiny":
        cfg = ModelConfig.tiny()
        ecfg = EngineConfig(page_size=16, num_pages=256, max_batch=16,
                            prefill_chunk=128, prefill_buckets=(128,),
                            batch_buckets=(4, 16), page_buckets=(16,),
                            decode_steps=args.decode_steps,
                            host_pages=args.host_pages)
    elif args.model == "8b":
        # Llama-3-8B-shaped — the size BASELINE.md's north-star metric is
        # defined at. bf16 weights (16 GB) exceed a v5e's HBM, so this
        # config requires --dtype int8 (~8 GB weights + scales).
        if args.dtype != "int8":
            raise SystemExit("--model 8b needs --dtype int8 on a 16 GB "
                             "chip (bf16 weights alone are 16 GB)")
        cfg = ModelConfig(vocab_size=128256, hidden_size=4096,
                          intermediate_size=14336, num_layers=32,
                          num_heads=32, num_kv_heads=8, head_dim=128,
                          rope_theta=500000.0, dtype="bfloat16")
        # KV: 2*32L*8KV*128hd*2B = 128 KB/token → 512 pages x 64 tok
        # = 32K cached tokens ≈ 4 GB; ~8 GB weights + ~4 GB KV leaves
        # headroom for decode-window transients on 16 GB
        ecfg = EngineConfig(page_size=64, num_pages=512, max_batch=16,
                            prefill_chunk=1024, prefill_buckets=(512, 1024),
                            batch_buckets=(8, 16), page_buckets=(16, 32),
                            decode_steps=args.decode_steps,
                            host_pages=args.host_pages)
    else:
        # Llama-3.2-1B-shaped: ~2.5 GB bf16 params + KV pool on one v5e chip
        cfg = ModelConfig(vocab_size=128256, hidden_size=2048,
                          intermediate_size=8192, num_layers=16,
                          num_heads=32, num_kv_heads=8, head_dim=64,
                          dtype="bfloat16")
        # KV pool: 1536 pages x 64 tok = 96K cached tokens (~3.2 GB);
        # headroom for the decode window's pool gather transients.
        # Two prefill T buckets + two page buckets: a 512-token prompt
        # pays 512x1024 attention instead of 1024x2048 (bucket-
        # homogeneous prefill batching keeps batches on their bucket)
        ecfg = EngineConfig(page_size=64, num_pages=1536, max_batch=32,
                            prefill_chunk=1024, prefill_buckets=(512, 1024),
                            batch_buckets=(8, 32), page_buckets=(16, 32),
                            decode_steps=args.decode_steps,
                            host_pages=args.host_pages)
    if args.max_batch:
        ecfg.max_batch = args.max_batch
        ecfg.batch_buckets = (8, args.max_batch)
    if getattr(args, "_spec_on", False):
        ecfg.spec_decode = True
        ecfg.spec_tokens = args.spec_tokens
    if args.prefill_token_budget is not None:
        ecfg.prefill_token_budget = args.prefill_token_budget
    if args.scenario == "multiturn":
        # size the HBM pool BELOW the conversation working set so turns
        # evict each other; the host tier is what keeps TTFT low
        # (~10 pages/user HBM vs histories growing past 17 pages)
        ecfg.num_pages = min(ecfg.num_pages, 10 * args.users)
        ecfg.host_pages = args.host_pages
    if args.scenario == "shared" and args.host_pages:
        # dynaheat cache A/B: same pool-pressure setup — an HBM pool
        # below the working set makes the eviction policy (and the
        # host-tier restore pipeline) the thing being measured
        ecfg.num_pages = min(ecfg.num_pages, 10 * args.users)
        ecfg.host_pages = args.host_pages
    if args.host_tier_int8:
        ecfg.host_tier_int8 = True
    if getattr(args, "host_tier_fp16", False):
        ecfg.host_tier_int8 = False
    if getattr(args, "evict_policy", None):
        ecfg.evict_policy = args.evict_policy
    if getattr(args, "restore_overlap", None) is not None:
        ecfg.restore_overlap = args.restore_overlap == "on"
    params = None
    if args.model == "8b":
        # 8B Gaussian host-init costs minutes of single-core host time
        # a chip run can't spare; throughput never reads the values —
        # synthesize the int8 tree instantly (models/quant.py)
        from dynamo_tpu.models import llama
        from dynamo_tpu.models.quant import synthetic_int8_params

        params = synthetic_int8_params(llama, cfg)
    quant = ("int8" if args.dtype == "int8" and params is None else None)
    return cfg, ecfg, params, quant


def build_engine(args):
    from dynamo_tpu.engine.jax_engine import JaxEngine

    cfg, ecfg, params, quant = engine_setup(args)
    engine = JaxEngine(cfg, ecfg, seed=args.seed, params=params,
                       quant=quant)
    return engine, cfg


def synth_requests(args, vocab: int, cap_tokens: int = 1 << 30):
    """ShareGPT-like synthetic prompts: lognormal input lengths, clipped
    to the engine's grid capacity (a deployment router rejects over-
    capacity prompts up front; letting them error-finish here would
    inflate req/s with zero-work requests)."""
    import numpy as np

    rng = np.random.RandomState(args.seed)
    hi = max(32, min(3072, cap_tokens - args.osl - 8))
    repetitive = getattr(args, "spec", False)
    reqs = []
    for i in range(args.requests):
        isl = int(np.clip(rng.lognormal(mean=np.log(args.isl), sigma=0.6),
                          32, hi))
        if repetitive:
            # --spec A/B: per-request repeated motif — the structured-
            # text regime prompt-lookup drafting targets (code, RAG
            # quotes, JSON); pure random tokens would measure only the
            # verify overhead
            motif = rng.randint(1, min(vocab - 10, 255), size=24).tolist()
            token_ids = (motif * (isl // len(motif) + 1))[:isl]
        else:
            token_ids = rng.randint(1, min(vocab - 10, 255),
                                    size=isl).tolist()
        reqs.append((token_ids, args.osl))
    return reqs


async def run_multiturn(args):
    """Multi-turn conversations with shared growing prefixes: each user
    alternates ~turns requests whose prompt = full history + new chunk.
    Measures per-turn TTFT; with --host-pages the evicted histories
    restore from the host tier instead of recomputing (reference KV
    offload '+40% TTFT', docs/architecture.md:91-96)."""
    import numpy as np

    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    engine, cfg = build_engine(args)
    print("warming up (compiling bucket grid)...", file=sys.stderr)
    engine.warmup()
    rng = np.random.RandomState(args.seed)
    histories = [rng.randint(1, 255, 512).tolist()
                 for _ in range(args.users)]
    ttfts = []

    errors = [0]

    async def one_turn(u):
        # histories grow ~256 tokens/turn; keep them inside the engine's
        # warmed-grid capacity (over-capacity prompts error-finish at
        # admission and would silently drop out of the TTFT sample)
        histories[u] = histories[u][-max(engine.cap_tokens - args.osl - 8,
                                         64):]
        req = PreprocessedRequest(
            token_ids=list(histories[u]), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=args.osl, ignore_eos=True),
            eos_token_ids=[])
        t0 = time.monotonic()
        first = None
        out_toks = []
        async for out in engine.generate(req, Context()):
            if out.token_ids and first is None:
                first = time.monotonic() - t0
            out_toks.extend(out.token_ids)
            if out.finish_reason:
                if out.finish_reason == "error":
                    errors[0] += 1
                break
        ttfts.append(first)
        histories[u] = histories[u] + out_toks + \
            rng.randint(1, 255, 128).tolist()

    bench_t0 = time.monotonic()
    for turn in range(args.turns):
        await asyncio.gather(*(one_turn(u) for u in range(args.users)))
        print(f"turn {turn + 1}/{args.turns} done", file=sys.stderr)
    wall = time.monotonic() - bench_t0
    await engine.stop()

    later = sorted(t for t in ttfts[args.users:] if t is not None)
    stats = engine.stats()
    report = {
        "scenario": "multiturn", "users": args.users, "turns": args.turns,
        "errors": errors[0],
        "host_pages": args.host_pages, "wall_s": round(wall, 2),
        "ttft_later_turns_p50_ms":
            round(later[len(later) // 2] * 1000, 1) if later else None,
        "prefix_hit_rate": round(stats["gpu_prefix_cache_hit_rate"], 4),
        "host_restores": stats["host_restore_pages_total"],
        "host_offloads": stats["host_offload_pages_total"],
        "post_warmup_compiles": stats["post_warmup_compiles_total"],
        "loop_lag_p99_ms": round(
            stats["loop_lag_p99_seconds"] * 1000, 2),
    }
    print(json.dumps(report), file=sys.stderr)
    return report


# ------------------------------------------------ dynacache shared-prefix


def _word_text(rng, nchars: int) -> str:
    """Deterministic filler text of ~nchars (byte tokenizer: 1 char =
    1 token) — the fleet/traffic.py word-soup idiom."""
    words = ("alpha bravo charlie delta echo foxtrot golf hotel india "
             "juliet kilo lima mike november oscar papa quebec romeo "
             "sierra tango uniform victor whiskey xray yankee zulu").split()
    out = []
    n = 0
    while n < nchars:
        w = words[rng.randint(0, len(words) - 1)]
        out.append(w)
        n += len(w) + 1
    return " ".join(out)[:nchars]


async def _shared_settle(publisher, kvr) -> None:
    """Between waves: flush the engine's stored-block events onto the bus,
    let the router's subscription drain them, refresh worker stats."""
    await publisher.flush()
    await asyncio.sleep(0.05)
    await kvr.scrape_once()


async def _shared_wave(http, port, reqs, osl: int, rows: list) -> dict:
    """Issue one wave of completions concurrently over the REAL HTTP
    frontend; returns {rid: completion_text} (agent histories grow by
    it). Each request pins its X-Request-Id so /v1/traces/{rid} can be
    joined afterwards."""
    import json as _json

    texts = {}

    async def one(rid, prompt):
        t0 = time.monotonic()
        first = None
        text = []
        async with http.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "bench", "prompt": prompt,
                      "stream": True, "max_tokens": osl},
                headers={"X-Request-Id": rid}) as resp:
            if resp.status != 200:
                rows.append({"rid": rid, "ttft": None, "error": True})
                return
            async for raw in resp.content:
                line = raw.strip()
                if line == b"data: [DONE]":
                    break
                if not line.startswith(b"data: "):
                    continue
                chunk = _json.loads(line[len(b"data: "):])
                for c in chunk.get("choices", []):
                    piece = c.get("text") or ""
                    if piece:
                        if first is None:
                            first = time.monotonic() - t0
                        text.append(piece)
        texts[rid] = "".join(text)
        rows.append({"rid": rid, "ttft": first, "error": False,
                     "e2e": time.monotonic() - t0})

    await asyncio.gather(*(one(rid, p) for rid, p in reqs))
    return texts


async def _run_shared_leg(args, shape: str, share: bool, http, port,
                          publisher, kvr, cap_tokens: int,
                          leg_tag: str) -> list:
    """One leg of a shape: waves of requests whose prompts share (or —
    the A/B control — do not share) prefixes. Returns the per-request
    rows; wave boundaries settle the event/stats planes so followers can
    actually route onto and hit the blocks the leaders committed."""
    import numpy as np

    rng = np.random.RandomState(args.seed ^ (0xCA if share else 0x5E))
    budget = max(cap_tokens - args.osl - 16, 96)
    prefix_chars = min(max(int(args.isl * 2 // 3), 48), int(budget * 0.6))
    suffix_chars = max(min(args.isl - prefix_chars, budget - prefix_chars
                           - 16), 8)
    rows: list = []
    n_req = 0

    def rid_for():
        nonlocal n_req
        n_req += 1
        return f"{leg_tag}-{n_req:04d}"

    if shape == "multi_tenant":
        # per-tenant shared system prompt; wave 0 seeds each tenant's
        # chain, later waves re-use it with unique question suffixes
        prefixes = {t: _word_text(rng, prefix_chars)
                    for t in range(args.users)}
        for wave in range(max(args.turns, 2)):
            reqs = []
            for t in range(args.users):
                prefix = (prefixes[t] if share
                          else _word_text(rng, prefix_chars))
                suffix = f" q{wave}: " + _word_text(rng, suffix_chars)
                reqs.append((rid_for(), prefix + suffix))
            await _shared_wave(http, port, reqs, args.osl, rows)
            await _shared_settle(publisher, kvr)
    elif shape == "rag":
        # one long common context; wave 0 = a single seeding question,
        # then concurrent distinct questions over the same context
        context = _word_text(rng, prefix_chars)
        seed_req = [(rid_for(),
                     (context if share else _word_text(rng, prefix_chars))
                     + " q0: " + _word_text(rng, suffix_chars))]
        await _shared_wave(http, port, seed_req, args.osl, rows)
        await _shared_settle(publisher, kvr)
        for wave in range(1, max(args.turns, 2)):
            reqs = []
            for u in range(args.users):
                ctx = context if share else _word_text(rng, prefix_chars)
                reqs.append((rid_for(), ctx + f" q{wave}.{u}: "
                             + _word_text(rng, suffix_chars)))
            await _shared_wave(http, port, reqs, args.osl, rows)
            await _shared_settle(publisher, kvr)
    elif shape == "agent":
        # agent loop: each turn re-sends the full growing history (prior
        # prompt + the model's own answer + a new instruction)
        histories = {a: _word_text(rng, prefix_chars)
                     for a in range(args.users)}
        for turn in range(max(args.turns, 2)):
            reqs = []
            rid_by_agent = {}
            for a in range(args.users):
                if not share:
                    # control: same lengths, no reuse across turns
                    histories[a] = _word_text(rng, len(histories[a]))
                if len(histories[a]) + args.osl + 24 > budget:
                    continue  # history hit the warmed-grid capacity
                prompt = histories[a] + f" step{turn}: " \
                    + _word_text(rng, 16)
                rid = rid_for()
                rid_by_agent[a] = (rid, prompt)
                reqs.append((rid, prompt))
            if not reqs:
                break
            texts = await _shared_wave(http, port, reqs, args.osl, rows)
            for a, (rid, prompt) in rid_by_agent.items():
                histories[a] = prompt + texts.get(rid, "")
            await _shared_settle(publisher, kvr)
    else:
        raise ValueError(f"unknown shared shape {shape!r}")
    return rows


async def _shared_cost_split(http, port, rows) -> dict:
    """Join the per-request cost blocks from /v1/traces/{rid}: the
    router-predicted vs engine-realized vs host-tier attribution
    breakdown summed over the leg."""
    split = {"requests_with_cost": 0, "prompt_blocks": 0,
             "router_overlap_blocks": 0, "device_hit_blocks": 0,
             "host_restored_blocks": 0, "fresh_blocks": 0,
             "restore_wait_ms": 0.0}
    for row in rows:
        if row.get("error"):
            continue
        async with http.get(
                f"http://127.0.0.1:{port}/v1/traces/{row['rid']}") as resp:
            if resp.status != 200:
                continue
            cost = (await resp.json()).get("cost")
        if not cost or "device_hit_blocks" not in cost:
            continue
        split["requests_with_cost"] += 1
        pb = int(cost.get("prompt_blocks", 0))
        dh = int(cost.get("device_hit_blocks", 0))
        hr = int(cost.get("host_restored_blocks", 0))
        split["prompt_blocks"] += pb
        split["router_overlap_blocks"] += int(
            cost.get("router_overlap_blocks", 0))
        split["device_hit_blocks"] += dh
        split["host_restored_blocks"] += hr
        split["fresh_blocks"] += pb - dh - hr
        split["restore_wait_ms"] += float(cost.get("restore_wait_ms", 0.0))
    split["restore_wait_ms"] = round(split["restore_wait_ms"], 3)
    return split


async def run_shared(args):
    """dynacache tentpole workloads: shared-prefix traffic driven through
    the REAL stack (aiohttp -> HttpService -> Processor -> KvRouter ->
    token worker -> JaxEngine), each shape A/B'd against a no-sharing
    control of identical lengths. The report quotes, per shape:
    the engine prefix hit rate (windowed counters delta), the TTFT delta
    vs no-sharing, and the router-predicted vs engine-realized vs
    host-restored attribution breakdown from the per-request cost
    blocks."""
    import aiohttp

    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.processor import Processor
    from dynamo_tpu.llm.worker import serve_token_model
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    engine, cfg = build_engine(args)
    print("warming up (compiling bucket grid)...", file=sys.stderr)
    engine.warmup()

    drt = await DistributedRuntime.detached()
    service = None
    kvr = None
    token_client = None
    publisher = None
    try:
        mdc = ModelDeploymentCard(name="bench", tokenizer_kind="byte",
                                  kv_block_size=engine.ecfg.page_size,
                                  model_type="completions")
        _handle, publisher = await serve_token_model(
            drt, mdc, engine, namespace="bench", component="w")
        kvr = KvRouter(drt, "bench", "w",
                       block_size=engine.ecfg.page_size, seed=args.seed)
        await kvr.start(run_loop=False)
        await kvr.scrape_once()
        token_client = await drt.namespace("bench").component("w") \
            .endpoint("generate_tokens").client()
        processor = Processor(mdc, token_client, kvr)
        service = HttpService()
        service.manager.add_completions_model("bench",
                                              processor.completion)
        await service.start(host="127.0.0.1", port=0)

        shapes = (["multi_tenant", "rag", "agent"]
                  if args.shared_shape == "all" else [args.shared_shape])
        report = {"scenario": "shared_prefix", "users": args.users,
                  "waves": args.turns, "shapes": {}}
        agg_hits = agg_prompts = 0
        ttft_ratios = []
        share_ttfts: list = []  # share-leg TTFTs (the cache-sensitive arm)
        all_rows: list = []    # every leg's request rows (dynaslo goodput)
        async with aiohttp.ClientSession() as http:
            for shape in shapes:
                legs = {}
                # no-share control FIRST: its unique junk cannot be hit
                # by the shared leg, the shared leg's blocks can
                for share in (False, True):
                    tag = f"{shape}-{'sh' if share else 'no'}"
                    st0 = engine.stats()
                    r0 = kvr.stats()
                    rows = await _run_shared_leg(
                        args, shape, share, http, service.port,
                        publisher, kvr, engine.cap_tokens, tag)
                    st1 = engine.stats()
                    r1 = kvr.stats()
                    all_rows.extend(rows)
                    hits = (st1["prefix_hit_tokens_total"]
                            - st0["prefix_hit_tokens_total"])
                    prompts = (st1["prompt_tokens_total"]
                               - st0["prompt_tokens_total"])
                    ttfts = sorted(r["ttft"] for r in rows
                                   if r.get("ttft") is not None)
                    leg = {
                        "requests": len(rows),
                        "errors": sum(1 for r in rows if r.get("error")),
                        "ttft_p50_ms": (round(
                            ttfts[len(ttfts) // 2] * 1000, 1)
                            if ttfts else None),
                        "ttft_p95_ms": (round(
                            ttfts[min(int(len(ttfts) * 0.95),
                                      len(ttfts) - 1)] * 1000, 1)
                            if ttfts else None),
                        "prefix_hit_rate": round(hits / max(prompts, 1),
                                                 4),
                        "prefix_hit_tokens": hits,
                        "prompt_tokens": prompts,
                        "device_hit_blocks": (
                            st1["cache_device_hit_blocks_total"]
                            - st0["cache_device_hit_blocks_total"]),
                        "host_restored_blocks": (
                            st1["cache_host_restored_blocks_total"]
                            - st0["cache_host_restored_blocks_total"]),
                        "fresh_blocks": (
                            st1["cache_fresh_blocks_total"]
                            - st0["cache_fresh_blocks_total"]),
                        "restore_wait_s": round(
                            st1["cache_restore_wait_seconds_total"]
                            - st0["cache_restore_wait_seconds_total"], 4),
                        "router_predicted_blocks": (
                            r1["calibration"]["predicted_blocks_total"]
                            - r0["calibration"]["predicted_blocks_total"]),
                        "router_realized_blocks": (
                            r1["calibration"]["realized_blocks_total"]
                            - r0["calibration"]["realized_blocks_total"]),
                        "cost_split": await _shared_cost_split(
                            http, service.port, rows),
                    }
                    legs["share" if share else "noshare"] = leg
                    if share:
                        agg_hits += hits
                        agg_prompts += prompts
                        share_ttfts.extend(r["ttft"] for r in rows
                                           if r.get("ttft") is not None)
                entry = dict(legs)
                if (legs["share"]["ttft_p50_ms"]
                        and legs["noshare"]["ttft_p50_ms"]):
                    entry["ttft_delta_ms"] = round(
                        legs["noshare"]["ttft_p50_ms"]
                        - legs["share"]["ttft_p50_ms"], 1)
                    ttft_ratios.append(legs["noshare"]["ttft_p50_ms"]
                                       / max(legs["share"]["ttft_p50_ms"],
                                             1e-9))
                report["shapes"][shape] = entry
                print(json.dumps({shape: entry}), file=sys.stderr)
        st = engine.stats()
        report["prefix_hit_rate"] = round(agg_hits / max(agg_prompts, 1),
                                          4)
        report["hit_rate_windowed"] = round(
            st["gpu_prefix_cache_hit_rate"], 4)
        report["calibration"] = kvr.stats()["calibration"]
        report["post_warmup_compiles"] = st["post_warmup_compiles_total"]
        report["host_restores"] = st["host_restore_pages_total"]
        report["host_offloads"] = st["host_offload_pages_total"]
        report["ttft_noshare_over_share"] = (
            round(sum(ttft_ratios) / len(ttft_ratios), 3)
            if ttft_ratios else None)
        # dynaslo: goodput + per-role quantiles from the engine's merged
        # latency histograms (every wave's request rows judged)
        report["slo"] = _slo_block([st], all_rows)
        # dynaheat flat cache keys: the per-toggle A/B driver reads
        # these top-level (share-leg TTFT, the lifecycle counters, and
        # the arm's toggle settings)
        sorted_tt = sorted(share_ttfts)
        report["ttft_p50_ms"] = (round(
            sorted_tt[len(sorted_tt) // 2] * 1000, 1) if sorted_tt else None)
        report["ttft_p95_ms"] = (round(
            sorted_tt[min(int(len(sorted_tt) * 0.95),
                          len(sorted_tt) - 1)] * 1000, 1)
            if sorted_tt else None)
        report["restore_wait_ms"] = round(
            st["cache_restore_wait_seconds_total"] * 1000, 2)
        report["device_hit_blocks"] = st["cache_device_hit_blocks_total"]
        report["host_restored_blocks"] = st["cache_host_restored_blocks_total"]
        report["fresh_blocks"] = st["cache_fresh_blocks_total"]
        report["evict_offloaded_total"] = st["cache_evict_offloaded_total"]
        report["evict_dropped_total"] = st["cache_evict_dropped_total"]
        report["host_evictions_total"] = st["cache_host_evictions_total"]
        report["restore_batch_pages_mean"] = round(
            st["cache_restore_batch_pages_total"]
            / max(st["cache_restore_batches_total"], 1), 2)
        report["evict_policy"] = engine.ecfg.evict_policy
        report["restore_overlap"] = bool(engine.ecfg.restore_overlap)
        report["host_tier_int8"] = bool(engine.ecfg.host_tier_int8)
        report["router_load_balance_weight"] = \
            kvr.stats()["load_balance_weight"]
        print(json.dumps(report), file=sys.stderr)
        return report
    finally:
        if service is not None:
            await service.stop()
        if kvr is not None:
            await kvr.stop()
        if token_client is not None:
            await token_client.close()
        if publisher is not None:
            await publisher.stop()
        await engine.stop()
        await drt.shutdown()


# dynaheat per-toggle A/B: the SAME shared-prefix workload (same seed,
# same shapes, same pool pressure) re-run once per arm with a fresh
# engine, so every cache change is quoted against the lru/serial
# control it replaced rather than against a different traffic mix.
_CACHE_AB_ARMS = (
    # name            evict_policy  restore_overlap
    ("control",        "lru",       "off"),   # pre-dynaheat behavior
    ("cost_evict",     "cost",      "off"),
    ("overlap_restore", "lru",      "on"),
    ("cost_overlap",   "cost",      "on"),    # dynaheat defaults
)

_CACHE_AB_ARM_KEYS = (
    "prefix_hit_rate", "hit_rate_windowed", "ttft_p50_ms", "ttft_p95_ms",
    "restore_wait_ms", "restore_batch_pages_mean",
    "device_hit_blocks", "host_restored_blocks", "fresh_blocks",
    "evict_offloaded_total", "evict_dropped_total", "host_evictions_total",
    "post_warmup_compiles", "evict_policy", "restore_overlap",
    "host_tier_int8", "router_load_balance_weight",
)


def run_shared_cache_ab(args) -> dict:
    """Four-arm cache A/B (--cache-ab): lru/serial control, cost-aware
    eviction alone, overlapped restores alone, and both together. Value
    is the combined arm's realized prefix hit rate; vs_baseline is the
    control-over-combined TTFT-p95 ratio (>1 = dynaheat is faster)."""
    import copy

    if not args.host_pages:
        # the A/B is ABOUT the two-tier cache — without a host tier the
        # eviction policy only picks drop victims and restores never run
        args.host_pages = 16 * args.users
    arms = {}
    for name, policy, overlap in _CACHE_AB_ARMS:
        a = copy.copy(args)
        a.evict_policy = policy
        a.restore_overlap = overlap
        print(f"=== cache A/B arm {name}: evict={policy}, "
              f"restore_overlap={overlap} ===", file=sys.stderr)
        rep = asyncio.run(run_shared(a))
        arms[name] = {k: rep.get(k) for k in _CACHE_AB_ARM_KEYS}
    ctrl, best = arms["control"], arms["cost_overlap"]
    detail = {"scenario": "shared_cache_ab", "users": args.users,
              "waves": args.turns, "host_pages": args.host_pages,
              "host_tier_int8": best["host_tier_int8"],
              "arms": arms}
    for name, rep in arms.items():
        if name == "control":
            continue
        d = {}
        if ctrl["ttft_p95_ms"] and rep["ttft_p95_ms"]:
            d["ttft_p95_control_over_arm"] = round(
                ctrl["ttft_p95_ms"] / rep["ttft_p95_ms"], 3)
        d["hit_rate_delta"] = round(
            rep["prefix_hit_rate"] - ctrl["prefix_hit_rate"], 4)
        d["restore_wait_ms_delta"] = round(
            rep["restore_wait_ms"] - ctrl["restore_wait_ms"], 2)
        detail[f"{name}_vs_control"] = d
    vs = (round(ctrl["ttft_p95_ms"] / best["ttft_p95_ms"], 3)
          if ctrl["ttft_p95_ms"] and best["ttft_p95_ms"] else 1.0)
    return {"metric": metric_name(args),
            "value": best["prefix_hit_rate"],
            "unit": metric_unit(args), "vs_baseline": vs,
            "detail": detail}


# --------------------------------------------------- dynashard sharded A/B


async def _sharded_leg(args, tag, prompts, *, token_counts, http, port):
    """Drive the identical workload through one leg's HTTP frontend;
    returns {wall_s, output_tok_per_s, ttft_p50_ms, requests, errors}.
    Output tokens are counted ENGINE-side (decode_tokens_total delta +
    one first token per request) so both legs use the same ruler."""
    import json as _json

    before = [f() for f in token_counts]
    rows: list = []
    sem = asyncio.Semaphore(args.concurrency)

    async def one(i, prompt):
        async with sem:
            t0 = time.monotonic()
            first = None
            async with http.post(
                    f"http://127.0.0.1:{port}/v1/completions",
                    json={"model": "bench", "prompt": prompt,
                          "stream": True, "max_tokens": args.osl},
                    headers={"X-Request-Id": f"{tag}-{i:04d}"}) as resp:
                if resp.status != 200:
                    rows.append({"ttft": None, "error": True})
                    return
                async for raw in resp.content:
                    line = raw.strip()
                    if line == b"data: [DONE]":
                        break
                    if not line.startswith(b"data: "):
                        continue
                    chunk = _json.loads(line[len(b"data: "):])
                    if first is None and any(
                            (c.get("text") or "")
                            for c in chunk.get("choices", [])):
                        first = time.monotonic() - t0
            rows.append({"ttft": first, "error": False})

    t0 = time.monotonic()
    await asyncio.gather(*(one(i, p) for i, p in enumerate(prompts)))
    wall = time.monotonic() - t0
    after = [f() for f in token_counts]
    ok = [r for r in rows if not r["error"]]
    out_toks = sum(a - b for a, b in zip(after, before)) + len(ok)
    ttfts = sorted(r["ttft"] for r in ok if r["ttft"] is not None)
    return {
        "requests": len(rows),
        "errors": sum(1 for r in rows if r["error"]),
        "wall_s": round(wall, 3),
        "output_tok_per_s": round(out_toks / wall, 1) if wall else 0.0,
        "ttft_p50_ms": (round(ttfts[len(ttfts) // 2] * 1000, 1)
                        if ttfts else None),
    }


async def run_sharded(args):
    """dynashard tentpole A/B: the SAME workload served by (a) one
    unsharded engine and (b) --dp-replicas mesh-sharded engine replicas
    on partitioned submeshes — both behind the real aiohttp → HttpService
    → Processor → KvRouter → generate_tokens stack. Reports tok/s per
    leg, the mesh shape and compile counts (the compile fence must hold
    under sharding: 0 per replica)."""
    import aiohttp
    import jax
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.processor import Processor
    from dynamo_tpu.llm.worker import serve_token_model
    from dynamo_tpu.parallel.serving import (devices_per_replica,
                                             parse_mesh_shape,
                                             ShardedReplicaSet)
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    axes = parse_mesh_shape(args.mesh or env_str_cfg("DYN_MESH_SHAPE")
                            or "model=2")
    replicas = max(args.dp_replicas, 1)
    need = devices_per_replica(axes) * replicas
    if len(jax.devices()) < need:
        raise RuntimeError(
            f"sharded A/B needs {need} devices "
            f"({replicas} x {axes}), have {len(jax.devices())} — on CPU "
            f"set DYN_FORCE_HOST_DEVICES (bench --cpu defaults it to 8)")
    cfg, ecfg, params, quant = engine_setup(args)

    rng = np.random.RandomState(args.seed)
    cap = min(ecfg.page_buckets[-1] * ecfg.page_size, 1 << 30)
    budget = max(cap - args.osl - 16, 64)
    prompts = [_word_text(rng, min(max(args.isl + int(v), 32), budget))
               for v in rng.randint(-args.isl // 4, args.isl // 4 + 1,
                                    size=args.requests)]
    mdc = ModelDeploymentCard(name="bench", tokenizer_kind="byte",
                              kv_block_size=ecfg.page_size,
                              model_type="completions")

    async def leg(tag, start_leg):
        drt = await DistributedRuntime.detached()
        service = kvr = token_client = None
        try:
            token_counts, compiles, extra, stop_leg = await start_leg(drt)
            kvr = KvRouter(drt, "bench", tag,
                           block_size=ecfg.page_size, seed=args.seed)
            await kvr.start(run_loop=False)
            await kvr.scrape_once()
            token_client = await drt.namespace("bench").component(tag) \
                .endpoint("generate_tokens").client()
            processor = Processor(mdc, token_client, kvr)
            service = HttpService()
            service.manager.add_completions_model("bench",
                                                  processor.completion)
            await service.start(host="127.0.0.1", port=0)
            async with aiohttp.ClientSession() as http:
                rep = await _sharded_leg(args, tag, prompts,
                                         token_counts=token_counts,
                                         http=http, port=service.port)
            rep["post_warmup_compiles"] = compiles()
            rep.update(extra())
            print(json.dumps({tag: rep}), file=sys.stderr)
            return rep
        finally:
            if service is not None:
                await service.stop()
            if kvr is not None:
                await kvr.stop()
            if token_client is not None:
                await token_client.close()
            try:
                await stop_leg()
            except UnboundLocalError:
                pass
            await drt.shutdown()

    async def start_unsharded(drt):
        engine = JaxEngine(cfg, ecfg, seed=args.seed, params=params,
                           quant=quant)
        print("warming up unsharded engine...", file=sys.stderr)
        await asyncio.to_thread(engine.warmup)
        _handle, publisher = await serve_token_model(
            drt, mdc, engine, namespace="bench", component="agg")

        async def stop():
            await publisher.stop()
            await engine.stop()

        return ([lambda: engine.decode_tokens_total],
                lambda: engine.fence.post_warmup_compiles,
                lambda: {"mesh_shape": "single"},
                stop)

    async def start_sharded(drt):
        rs = ShardedReplicaSet(
            cfg, ecfg, mesh_axes=axes, replicas=replicas,
            namespace="bench", component="sharded", mdc=mdc,
            dcp_address=drt.dcp.address, params=params, seed=args.seed,
            quant=quant)
        print(f"warming up {replicas} sharded replicas "
              f"(mesh {rs.mesh_shape})...", file=sys.stderr)
        await rs.start()

        def extra():
            return {
                "mesh_shape": rs.mesh_shape,
                "sharding": rs.describe(),
                "per_replica_compiles": rs.post_warmup_compiles(),
                "per_replica_decode_tokens": {
                    r.name: r.engine.decode_tokens_total
                    for r in rs.replicas},
            }

        return ([lambda r=r: r.engine.decode_tokens_total
                 for r in rs.replicas],
                lambda: sum(rs.post_warmup_compiles().values()),
                extra, rs.stop)

    unsharded = await leg("agg", start_unsharded)
    sharded = await leg("sharded", start_sharded)
    report = {
        "scenario": "sharded_vs_unsharded",
        "mesh_shape": sharded.get("mesh_shape"),
        "dp_replicas": replicas,
        "unsharded": unsharded,
        "sharded": sharded,
        "sharded_over_unsharded_tok_per_s": round(
            sharded["output_tok_per_s"]
            / max(unsharded["output_tok_per_s"], 1e-9), 3),
        "post_warmup_compiles": (unsharded["post_warmup_compiles"]
                                 + sharded["post_warmup_compiles"]),
    }
    print(json.dumps(report), file=sys.stderr)
    return report


def _pctile(vals, q):
    """Deterministic nearest-rank percentile; None on empty (the one
    shared implementation in runtime/slo.py — dynaslo)."""
    from dynamo_tpu.runtime.slo import nearest_rank

    return nearest_rank(list(vals), q)


# default CPU-smoke objectives for the bench goodput block when no
# DYN_SLO_OBJECTIVES is set: generous enough that a healthy smoke run
# scores goodput 1.0 and any wedge/regression scores below it (chip runs
# set real targets via the env registry)
_BENCH_DEFAULT_SLO = "ttft<=30@0.95/600;e2e<=120@0.95/600"


def _slo_block(stats_list, request_rows=None):
    """dynaslo bench block: per-role latency quantiles from the workers'
    MERGED histograms (the same mergeable-histogram plane the metrics
    aggregator renders) + per-request goodput against the registered
    (or default CPU-smoke) objectives."""
    from dynamo_tpu.runtime import slo as _slo

    merged = _slo.merge_latency_wire(
        [s.get("latency_hist") or {} for s in stats_list])
    per_role = {
        role: {metric: {"p50_ms": round(h.quantile(0.5) * 1000, 3),
                        "p95_ms": round(h.quantile(0.95) * 1000, 3),
                        "p99_ms": round(h.quantile(0.99) * 1000, 3),
                        "count": h.count}
               for metric, h in sorted(per.items()) if h.count}
        for role, per in sorted(merged.items())}
    reg = _slo.SloRegistry.from_env()
    if not reg.objectives:
        reg = _slo.SloRegistry.parse(_BENCH_DEFAULT_SLO)
    gp = _slo.GoodputTracker(reg)
    for r in request_rows or []:
        if r.get("error") or r.get("shed"):
            gp.observe_failed()
            continue
        metrics = {k: r[k] for k in ("ttft", "itl", "e2e")
                   if r.get(k) is not None}
        gp.observe_request(metrics)
    return {
        "objectives": [o.to_dict() for o in reg.objectives],
        "goodput": gp.snapshot(),
        "per_role_quantiles": per_role,
    }


async def run_failover(args):
    """dynarevive robustness bench: two workers behind the real
    aiohttp → HttpService → Processor → KvRouter → generate_tokens
    stack. Phase 1 (churn): one worker is killed mid-burst — mid-stream
    failover must resume its streams on the sibling with zero client
    errors; reports goodput under churn and resume-stall p99 (the
    client-visible gap the failover inserts). Phase 2 (overload): 2x the
    surviving capacity is thrown at the frontend with SLO-aware
    admission control on; reports shed rate and admitted-TTFT p99 (the
    point of shedding: the requests we DO admit stay fast)."""
    import aiohttp
    import json as _json
    import random as _random

    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.processor import Processor
    from dynamo_tpu.llm.worker import serve_token_model
    from dynamo_tpu.runtime import profiling, revive
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    cfg, ecfg, params, quant = engine_setup(args)
    rng = np.random.RandomState(args.seed)
    cap = ecfg.page_buckets[-1] * ecfg.page_size
    # the resume prompt is prompt + emitted: keep isl + osl inside the
    # warmed grid so failover never trips the compile fence
    isl = max(min(args.isl, cap - 2 * args.osl - 16), 32)
    prompts = [_word_text(rng, isl) for _ in range(args.requests)]
    mdc = ModelDeploymentCard(name="bench", tokenizer_kind="byte",
                              kv_block_size=ecfg.page_size,
                              model_type="completions")

    drt = await DistributedRuntime.detached()
    drt2 = await DistributedRuntime.attach(drt.dcp.address)
    engines, handles, pubs = [], [], []
    service = kvr = token_client = admission = None
    try:
        for i, d in enumerate((drt, drt2)):
            # same seed → identical weights on both workers (the greedy
            # resume token-identity contract needs sibling equivalence)
            eng = JaxEngine(cfg, ecfg, seed=args.seed, params=params,
                            quant=quant, worker_label=f"w{i}")
            print(f"warming up worker {i}...", file=sys.stderr)
            # the compile fence is process-global: mask the already-armed
            # siblings while this worker warms up (the dynashard join
            # idiom) so per-worker compile counts stay meaningful
            live_fences = [e.fence for e in engines]
            for f in live_fences:
                f.disarm()
            try:
                await asyncio.to_thread(eng.warmup)
            finally:
                for f in live_fences:
                    f.arm()
            handle, pub = await serve_token_model(
                d, mdc, eng, namespace="bench", component="fo")
            engines.append(eng)
            handles.append(handle)
            pubs.append(pub)
        # production shape: the scrape loop runs, so the dead worker
        # drops out of the scheduler and optimistic slot accounting
        # resets as real occupancy comes back
        kvr = KvRouter(drt, "bench", "fo", block_size=ecfg.page_size,
                       scrape_interval=0.25, seed=args.seed)
        await kvr.start(run_loop=True)
        await kvr.scrape_once()
        token_client = await drt.namespace("bench").component("fo") \
            .endpoint("generate_tokens").client()
        processor = Processor(mdc, token_client, kvr)

        def signals():
            live = [e.stats() for e in engines if not e.draining]
            if not live:
                return revive.LoadSignals()
            return revive.LoadSignals(
                queue_depth=sum(s["num_requests_waiting"] for s in live),
                workers=len(live),
                loop_lag_p99_ms=max(s["loop_lag_p99_seconds"]
                                    for s in live) * 1000.0,
                kv_free_blocks=min(s["kv_free_blocks"] for s in live))

        admission = revive.AdmissionController(
            signals,
            cfg=revive.ShedConfig(
                queue_depth=max(ecfg.max_batch // 4, 2)),
            rng=_random.Random(args.seed))
        service = HttpService()  # churn phase: no shedding
        service.manager.add_completions_model("bench",
                                              processor.completion)
        await service.start(host="127.0.0.1", port=0)

        async def one(http, i, prompt, rows, tag, osl):
            rid = f"{tag}-{i:04d}"
            t0 = time.monotonic()
            first = last = None
            max_gap = 0.0
            chars = 0
            errored = False
            async with http.post(
                    f"http://127.0.0.1:{service.port}/v1/completions",
                    json={"model": "bench", "prompt": prompt,
                          "stream": True, "max_tokens": osl},
                    headers={"X-Request-Id": rid}) as resp:
                if resp.status == 503:
                    rows.append({"rid": rid, "shed": True, "error": False,
                                 "ttft": None, "max_gap": 0.0, "chars": 0})
                    return
                if resp.status != 200:
                    rows.append({"rid": rid, "shed": False, "error": True,
                                 "ttft": None, "max_gap": 0.0, "chars": 0})
                    return
                async for raw in resp.content:
                    line = raw.strip()
                    if line == b"data: [DONE]":
                        break
                    if line.startswith(b"event: error"):
                        errored = True
                        continue
                    if not line.startswith(b"data: "):
                        continue
                    chunk = _json.loads(line[len(b"data: "):])
                    piece = "".join(c.get("text") or ""
                                    for c in chunk.get("choices", []))
                    if piece:
                        now = time.monotonic()
                        if first is None:
                            first = now - t0
                        elif last is not None:
                            max_gap = max(max_gap, now - last)
                        last = now
                        chars += len(piece)  # byte tokenizer: chars==tokens
            rows.append({"rid": rid, "shed": False, "error": errored,
                         "ttft": first, "max_gap": max_gap,
                         "chars": chars,
                         "e2e": time.monotonic() - t0})

        # ---------------------------------------- phase 1: churn (kill)
        resumed_before = revive.journal().resumed_total
        rows1: list = []
        killed = []

        async def killer():
            # wait for the victim to be loaded and mid-decode, then die
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if handles[0].inflight > 0 and \
                        engines[0].decode_tokens_total >= args.osl:
                    await handles[0].die()
                    engines[0].draining = True  # capacity is gone for real
                    killed.append(time.monotonic())
                    return
                await asyncio.sleep(0.005)

        async with aiohttp.ClientSession() as http:
            t0 = time.monotonic()
            ktask = asyncio.ensure_future(killer())
            await asyncio.gather(*(one(http, i, p, rows1, "churn",
                                       args.osl)
                                   for i, p in enumerate(prompts)))
            wall1 = time.monotonic() - t0
            await ktask

            resumed_rows = []
            for r in rows1:
                cost = profiling.request_attribution(r["rid"]) or {}
                if cost.get("resumed_attempts"):
                    resumed_rows.append(r)
            ok1 = [r for r in rows1 if not r["error"] and not r["shed"]]
            churn = {
                "requests": len(rows1),
                "completed": len(ok1),
                "errors": sum(1 for r in rows1 if r["error"]),
                "worker_killed": bool(killed),
                "resumed": len(resumed_rows),
                "goodput_tok_per_s": round(
                    sum(r["chars"] for r in ok1) / wall1, 1)
                if wall1 else 0.0,
                "resume_stall_p99_ms": (round(_pctile(
                    [r["max_gap"] for r in resumed_rows], 99) * 1000, 1)
                    if resumed_rows else None),
                "ttft_p99_ms": (round(_pctile(
                    [r["ttft"] for r in ok1 if r["ttft"] is not None],
                    99) * 1000, 1) if ok1 else None),
            }
            print(_json.dumps({"churn": churn}), file=sys.stderr)

            # ------------------------------- phase 2: 2x overload, shed
            # sustained 2x the survivor's slot capacity in flight (not
            # one instantaneous burst): later arrivals see the queues the
            # earlier ones built, which is what the shed signals read
            service.set_admission(admission)
            admission.start(0.02)  # peak-hold sampler between arrivals
            n2 = 4 * ecfg.max_batch
            sem2 = asyncio.Semaphore(2 * ecfg.max_batch)
            prompts2 = [_word_text(rng, isl) for _ in range(n2)]
            rows2: list = []
            osl2 = max(args.osl // 2, 8)

            async def over(i, p):
                async with sem2:
                    await one(http, i, p, rows2, "over", osl2)

            t0 = time.monotonic()
            await asyncio.gather(*(over(i, p)
                                   for i, p in enumerate(prompts2)))
            wall2 = time.monotonic() - t0
            shed = [r for r in rows2 if r["shed"]]
            admitted = [r for r in rows2
                        if not r["shed"] and not r["error"]]
            overload = {
                "requests": n2,
                "overload_factor": 2.0,
                "shed": len(shed),
                "shed_rate": round(len(shed) / max(n2, 1), 3),
                "admitted": len(admitted),
                "errors": sum(1 for r in rows2 if r["error"]),
                "admitted_ttft_p99_ms": (round(_pctile(
                    [r["ttft"] for r in admitted
                     if r["ttft"] is not None], 99) * 1000, 1)
                    if admitted else None),
                "goodput_tok_per_s": round(
                    sum(r["chars"] for r in admitted) / wall2, 1)
                if wall2 else 0.0,
                "shed_by_signal": dict(sorted(
                    admission.shed_by_signal.items())),
            }
            print(_json.dumps({"overload": overload}), file=sys.stderr)

        report = {
            "scenario": "failover",
            "workers": 2,
            "isl": isl, "osl": args.osl,
            "churn": churn,
            "overload": overload,
            "revive_resumes": revive.journal().resumed_total
            - resumed_before,
            # the surviving replica must never compile mid-failover: the
            # resume prompt stays on the warmed grid
            "post_warmup_compiles": {
                f"w{i}": e.fence.post_warmup_compiles
                for i, e in enumerate(engines)},
            # dynaslo: goodput + per-role quantiles from the two
            # workers' MERGED latency histograms (both phases' requests
            # judged; shed counts against goodput, it was not served)
            "slo": _slo_block([e.stats() for e in engines],
                              rows1 + rows2),
        }
        print(_json.dumps(report), file=sys.stderr)
        return report
    finally:
        if admission is not None:
            await admission.stop()
        if service is not None:
            await service.stop()
        if kvr is not None:
            await kvr.stop()
        if token_client is not None:
            await token_client.close()
        for pub in pubs:
            await pub.stop()
        for handle in handles:
            await handle.stop()
        for eng in engines:
            await eng.stop()
        await drt2.shutdown()
        await drt.shutdown()


def env_str_cfg(name):
    from dynamo_tpu.runtime.config import env_str

    return env_str(name)


async def measure(engine, reqs, concurrency, trace=False):
    """Drive `reqs` through any AsyncEngine-shaped object at the given
    concurrency; returns the aggregate report (the reference batch-mode
    metrics, launch/dynamo-run input/batch.rs:42-105). ``trace=True``
    wraps every request in a dyntrace root span and appends a per-stage
    breakdown to the report."""
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime import tracing
    from dynamo_tpu.runtime.engine import Context

    from dynamo_tpu.runtime import profiling

    # dynaprof: lag-monitor the bench loop for the run's duration so
    # every report carries loop_lag_p99_ms (released before returning)
    profiling.acquire_loop_profiler()
    sem = asyncio.Semaphore(concurrency)
    results = []
    trace_rids = []
    # hard per-request watchdog: a wedged generator must surface as an
    # error row, never hang the whole bench (the driver runs this
    # unattended at end of round)
    from dynamo_tpu.runtime.config import env_float
    req_timeout = env_float("DYN_BENCH_REQ_TIMEOUT")

    async def one(req_idx, token_ids, osl):
        async with sem:
            ctx = Context()
            try:
                await asyncio.wait_for(_one_inner(ctx, token_ids, osl),
                                       req_timeout)
            except asyncio.TimeoutError:
                # cancel the engine-side sequence too: an abandoned
                # request would keep its batch slot + KV pages and decode
                # to max_tokens, starving the remaining waves
                ctx.stop_generating()
                print(f"request {req_idx} timed out after {req_timeout}s",
                      file=sys.stderr)
                results.append({
                    "tokens_in": len(token_ids), "tokens_out": 0,
                    "ttft": None, "elapsed": req_timeout, "itl": None,
                    "gaps": [], "error": True,
                })

    async def _one_inner(ctx, token_ids, osl):
        pre = PreprocessedRequest(
            token_ids=token_ids,
            sampling=SamplingOptions(),  # greedy
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
            eos_token_ids=[])
        if trace:
            trace_rids.append(ctx.id)
            with tracing.get_tracer().start_span(
                    "bench.request", parent=None, request_id=ctx.id,
                    attributes={"isl": len(token_ids), "osl": osl}):
                await _drive(pre, ctx, osl, len(token_ids))
        else:
            await _drive(pre, ctx, osl, len(token_ids))

    async def _drive(pre, ctx, osl, isl):
        t_start = time.monotonic()
        t_first = None
        chunk_stamps = []
        n_out = 0
        finish = None
        async for out in engine.generate(pre, ctx):
            now = time.monotonic()
            if out.token_ids:
                if t_first is None:
                    t_first = now
                chunk_stamps.append(now)
                n_out += len(out.token_ids)
            if out.finish_reason:
                finish = out.finish_reason
                break
        t_end = time.monotonic()
        # window-amortized ITL: the fused decode window emits K tokens
        # per host sync, so raw inter-arrival gaps are 0 within a
        # window and ~window-time at boundaries (the r1/r2 itl_p50=0
        # artifact). The honest per-request number is the mean
        # inter-token interval over the whole stream.
        itl = ((chunk_stamps[-1] - chunk_stamps[0]) / (n_out - 1)
               if n_out > 1 else None)
        results.append({
            "tokens_in": isl, "tokens_out": n_out,
            "ttft": (t_first - t_start) if t_first else None,
            "elapsed": t_end - t_start, "itl": itl,
            # raw inter-CHUNK arrival gaps: what a streaming client
            # actually experiences between deliveries (with decode_steps
            # K>1 these are ~K-token strides — report them alongside the
            # amortized figure, not instead of it; VERDICT r4 weak #6)
            "gaps": [b - a for a, b in zip(chunk_stamps, chunk_stamps[1:])],
            "error": finish == "error",
        })

    bench_t0 = time.monotonic()
    await asyncio.gather(*(one(i, t, o) for i, (t, o) in enumerate(reqs)))
    wall = time.monotonic() - bench_t0
    lag = profiling.loop_lag_snapshot()
    await profiling.release_loop_profiler()

    errors = sum(1 for r in results if r["error"])
    results = [r for r in results if not r["error"]]
    total_out = sum(r["tokens_out"] for r in results)
    total_in = sum(r["tokens_in"] for r in results)
    ttfts = sorted(r["ttft"] for r in results if r["ttft"] is not None)
    itls = sorted(r["itl"] for r in results if r["itl"] is not None)
    # pooled raw inter-chunk gaps across all requests (client-observed
    # stream cadence — the un-amortized truth the window-ITL smooths)
    gaps = sorted(g for r in results for g in r["gaps"])

    def pct(v, p):
        return v[min(int(len(v) * p / 100), len(v) - 1)] if v else None

    report = {
        "requests": len(results), "errors": errors,
        "wall_s": round(wall, 3),
        "req_per_s": round(len(results) / wall, 3),
        "output_tok_per_s": round(total_out / wall, 1),
        "total_tok_per_s": round((total_in + total_out) / wall, 1),
        "ttft_p50_ms": round(pct(ttfts, 50) * 1000, 1) if ttfts else None,
        "ttft_p99_ms": round(pct(ttfts, 99) * 1000, 1) if ttfts else None,
        "itl_p50_ms": round(pct(itls, 50) * 1000, 2) if itls else None,
        "itl_p99_ms": round(pct(itls, 99) * 1000, 2) if itls else None,
        "itl_raw_chunk_p50_ms": (round(pct(gaps, 50) * 1000, 2)
                                 if gaps else None),
        "itl_raw_chunk_p99_ms": (round(pct(gaps, 99) * 1000, 2)
                                 if gaps else None),
        # dynaprof: event-loop callback-overrun p99 during the run —
        # the scheduler-overhead companion to the latency percentiles
        "loop_lag_p99_ms": round(lag["p99_s"] * 1000, 2),
    }
    if trace:
        report["trace_stages"] = _trace_breakdown(trace_rids)
    return report


def _trace_breakdown(request_ids):
    """Per-request stage dump (stderr) + a mean/max rollup per stage name
    over the whole run, read straight from the dyntrace ring."""
    from dynamo_tpu.runtime import tracing

    tracer = tracing.get_tracer()
    per_stage = {}
    for rid in request_ids:
        tr = tracer.get_request_trace(rid)
        if tr is None:
            continue
        print(f"trace {rid}: " + " ".join(
            f"{name}={ms:.1f}ms" for name, ms in sorted(tr["stages"].items())),
            file=sys.stderr)
        for name, ms in tr["stages"].items():
            per_stage.setdefault(name, []).append(ms)
    return {name: {"n": len(v),
                   "mean_ms": round(sum(v) / len(v), 2),
                   "max_ms": round(max(v), 2)}
            for name, v in sorted(per_stage.items())}


async def run_bench(args):
    engine, cfg = build_engine(args)
    print("warming up (compiling bucket grid)...", file=sys.stderr)
    t0 = time.monotonic()
    engine.warmup()
    print(f"warmup done in {time.monotonic()-t0:.1f}s", file=sys.stderr)

    reqs = synth_requests(args, cfg.vocab_size, engine.cap_tokens)
    report = await measure(engine, reqs, args.concurrency,
                           trace=getattr(args, "trace", False))
    st = engine.stats()
    report["prefix_hit_rate"] = round(st["gpu_prefix_cache_hit_rate"], 4)
    # compile-regression gate for hot-path work (ROADMAP item 3): any
    # nonzero value means a serve-time XLA compile stalled the run
    report["post_warmup_compiles"] = st["post_warmup_compiles_total"]
    # dynaslo: per-role latency quantiles from the engine's mergeable
    # histograms (no per-request rows here — measure() owns the client
    # view; goodput rides the shared/failover scenarios)
    report["slo"] = _slo_block([st])
    if getattr(args, "trace", False):
        print(f"trace compile fence: {st['post_warmup_compiles_total']} "
              f"post-warmup XLA compile(s)", file=sys.stderr)
    if engine.ecfg.spec_decode:
        report["spec_steps"] = st["spec_decode_steps"]
        report["spec_acceptance_rate"] = round(
            st["spec_decode_acceptance_rate"], 4)
        report["spec_mean_accepted_len"] = round(
            st["spec_decode_mean_accepted_len"], 4)
    await engine.stop()
    print(json.dumps(report), file=sys.stderr)
    return report


async def run_hotpath(args):
    """A decode-heavy, small-batch, long-generation mix (ITL is decided
    by per-token host work, not FLOPs, in this regime): ONE record
    carries the client metric (``itl_raw_chunk_p99_ms``), loop-lag p99
    and the compile fence."""
    # decode-heavy defaults wherever the caller left the global ones:
    # short prompts, long generations, small concurrency
    if args.isl == 512:
        args.isl = 96
    if args.osl == 128:
        args.osl = 192
    if args.requests == 64:
        args.requests = 16
    if args.concurrency == 32:
        args.concurrency = 4
    return await run_bench(args)


async def run_disagg(args):
    """Disagg vs agg A/B on the same workload — the BASELINE.md north-star
    (reference docs/architecture.md:57-61 claims +30%/GPU at 1 node).

    Here both engines time-share ONE chip and every KV page is staged
    through the host, so the interesting output is the full metric set +
    the transfer-overhead breakdown, not a win: disagg's gain
    comes from putting prefill on separate hardware, which a single-chip
    A/B cannot express by construction.
    """
    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.llm.disagg import DisaggRouter, PrefillWorker
    from dynamo_tpu.llm.disagg.decode import build_disagg_decode
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    engine, cfg = build_engine(args)  # aggregated baseline: full pool
    params = engine.params  # one HBM copy shared by all three engines
    print("warming up agg engine...", file=sys.stderr)
    engine.warmup()
    reqs = synth_requests(args, cfg.vocab_size, engine.cap_tokens)
    agg = await measure(engine, reqs, args.concurrency,
                        trace=getattr(args, "trace", False))
    agg_st = engine.stats()
    agg["post_warmup_compiles"] = agg_st["post_warmup_compiles_total"]
    await engine.stop()
    base_ecfg = engine.ecfg
    del engine

    # disaggregated: decode engine (2/3 pool) + prefill engine (1/3 pool)
    import dataclasses

    decode_ecfg = dataclasses.replace(base_ecfg,
                                      num_pages=base_ecfg.num_pages * 2 // 3)
    prefill_ecfg = dataclasses.replace(base_ecfg,
                                       num_pages=base_ecfg.num_pages // 3)
    decode_eng = JaxEngine(cfg, decode_ecfg, params=params)
    prefill_eng = JaxEngine(cfg, prefill_ecfg, params=params)
    print("warming up disagg engines...", file=sys.stderr)
    decode_eng.warmup()
    prefill_eng.warmup(decode=False)

    drt = await DistributedRuntime.detached()
    router = DisaggRouter(max_local_prefill_length=args.disagg_threshold)
    disagg = await build_disagg_decode(drt, decode_eng, namespace="bench",
                                       router=router, watch_config=False)
    pw = PrefillWorker(drt, prefill_eng, namespace="bench")
    pw.start()

    # one disagg leg per chunk size (0 = legacy bulk frame): same engines,
    # fresh prompts per leg (a repeated workload would prefix-hit the
    # decode pool and skip the transfer under test — --shared-prefix adds
    # a deliberate A/B leg that does exactly that, measuring the
    # transfer-vs-reuse interaction instead of dodging it)
    if args.kv_chunk_pages is not None:
        chunk_values = [int(x) for x in
                        str(args.kv_chunk_pages).split(",") if x != ""]
    else:
        chunk_values = [pw.chunk_pages]
    legs = []
    for li, cp in enumerate(chunk_values):
        pw.chunk_pages = cp
        import copy as _copy

        a = _copy.copy(args)
        a.seed = args.seed + 101 * li
        leg_reqs = (reqs if li == 0
                    else synth_requests(a, cfg.vocab_size,
                                        decode_eng.cap_tokens))
        before_st = disagg.stats()
        before_send = dict(pw.xfer.__dict__)
        print(f"--- disagg leg kv_chunk_pages={cp} ---", file=sys.stderr)
        dis = await measure(disagg, leg_reqs, args.concurrency,
                            trace=getattr(args, "trace", False))
        st = disagg.stats()
        send = {k: v - before_send[k] for k, v in pw.xfer.__dict__.items()}
        dis["kv_chunk_pages"] = cp
        dis["post_warmup_compiles"] = (
            decode_eng.fence.post_warmup_compiles
            + prefill_eng.fence.post_warmup_compiles)
        dis["remote_prefills"] = (st["remote_prefills"]
                                  - before_st["remote_prefills"])
        dis["local_prefills"] = (st["local_prefills"]
                                 - before_st["local_prefills"])
        dis["remote_fallbacks"] = (st["remote_fallbacks"]
                                   - before_st["remote_fallbacks"])
        # per-request means over COMPLETED remote prefills (the wait/ingest
        # accumulators only count successes; timeouts → remote_fallbacks)
        ok_remote = max(dis["remote_prefills"] - dis["remote_fallbacks"], 1)
        wait_s = st["remote_wait_total_s"] - before_st["remote_wait_total_s"]
        inject_s = (st["kv_transfer_inject_seconds_total"]
                    - before_st["kv_transfer_inject_seconds_total"])
        dis["remote_wait_mean_ms"] = round(1000 * wait_s / ok_remote, 1)
        dis["transfer_mb"] = round(
            (st["kv_transfer_bytes_total"]
             - before_st["kv_transfer_bytes_total"]) / 1e6, 1)
        dis["transfer_pages"] = (st["kv_transfer_pages_total"]
                                 - before_st["kv_transfer_pages_total"])
        dis["transfer_ingest_ms_per_req"] = round(
            1000 * inject_s / ok_remote, 1)
        # per-stage pipeline breakdown: overlapped stages legitimately sum
        # past the sender's wall time — that inequality IS the evidence the
        # extract/compress/wire/inject pipeline overlaps (tentpole metric)
        stage_sum = (send["extract_seconds"] + send["compress_seconds"]
                     + send["wire_seconds"] + inject_s)
        dis["transfer_stages"] = {
            "extract_s": round(send["extract_seconds"], 4),
            "compress_s": round(send["compress_seconds"], 4),
            "wire_s": round(send["wire_seconds"], 4),
            "inject_s": round(inject_s, 4),
            "stage_sum_s": round(stage_sum, 4),
            "send_wall_s": round(send["wall_seconds"], 4),
            "chunks_sent": send["chunks_sent"],
            "overlap": bool(stage_sum > send["wall_seconds"]),
        }
        print(json.dumps(dis), file=sys.stderr)
        legs.append(dis)

    shared_ab = None
    if getattr(args, "shared_prefix", False):
        # transfer-vs-reuse A/B (dynacache): same length distribution,
        # but every prompt shares one page-aligned 2/3-ISL prefix. After
        # the first transfers commit the shared blocks, decode-side
        # reservations prefix-hit and the prefill worker skips shipping
        # those pages — measured as transfer pages per remote prefill
        # next to the decode engine's realized hit split.
        import copy as _copy

        import numpy as np

        pw.chunk_pages = chunk_values[0]
        ps = decode_eng.ecfg.page_size
        pl = max((int(args.isl * 2 // 3) // ps) * ps, ps)
        a = _copy.copy(args)
        a.seed = args.seed + 7777
        base = synth_requests(a, cfg.vocab_size, decode_eng.cap_tokens)
        motif_rng = np.random.RandomState(args.seed ^ 0xD1CE)
        motif = motif_rng.randint(1, min(cfg.vocab_size - 10, 255),
                                  size=pl).tolist()
        shared_reqs = []
        for toks, osl in base:
            if len(toks) <= pl + 8:
                toks = toks + motif[:pl + 8 - len(toks) + 1]
            shared_reqs.append((motif + list(toks[pl:]), osl))
        cs0 = decode_eng.pm.cache_stats()
        before_st = disagg.stats()
        print("--- disagg shared-prefix leg ---", file=sys.stderr)
        shared_leg = await measure(disagg, shared_reqs, args.concurrency)
        st = disagg.stats()
        cs1 = decode_eng.pm.cache_stats()
        hit_blocks = (cs1["device_hit_blocks_total"]
                      - cs0["device_hit_blocks_total"]
                      + cs1["host_restored_blocks_total"]
                      - cs0["host_restored_blocks_total"])
        alloc_blocks = hit_blocks + (cs1["fresh_blocks_total"]
                                     - cs0["fresh_blocks_total"])
        shared_leg["transfer_pages"] = (
            st["kv_transfer_pages_total"]
            - before_st["kv_transfer_pages_total"])
        shared_leg["remote_prefills"] = (st["remote_prefills"]
                                         - before_st["remote_prefills"])
        shared_leg["decode_hit_blocks"] = hit_blocks
        shared_leg["decode_hit_block_rate"] = round(
            hit_blocks / max(alloc_blocks, 1), 4)
        fresh_leg = legs[0]
        shared_ab = {
            "fresh": {k: fresh_leg[k] for k in
                      ("req_per_s", "ttft_p50_ms", "transfer_pages",
                       "remote_prefills")},
            "shared": {k: shared_leg[k] for k in
                       ("req_per_s", "ttft_p50_ms", "transfer_pages",
                        "remote_prefills", "decode_hit_blocks",
                        "decode_hit_block_rate")},
            "transfer_pages_per_remote_fresh": round(
                fresh_leg["transfer_pages"]
                / max(fresh_leg["remote_prefills"], 1), 2),
            "transfer_pages_per_remote_shared": round(
                shared_leg["transfer_pages"]
                / max(shared_leg["remote_prefills"], 1), 2),
        }
        print(json.dumps({"shared_prefix_ab": shared_ab}),
              file=sys.stderr)

    await pw.stop()
    await disagg.transfer.stop()
    await prefill_eng.stop()
    await decode_eng.stop()
    await drt.shutdown()

    best = max(legs, key=lambda d: d["req_per_s"])
    report = {"scenario": "disagg_vs_agg", "agg": agg, "disagg": best,
              "disagg_over_agg_req_per_s":
                  round(best["req_per_s"] / agg["req_per_s"], 3)}
    if shared_ab is not None:
        report["shared_prefix_ab"] = shared_ab
    if len(legs) > 1:
        report["disagg_legs"] = legs
    print(json.dumps(report), file=sys.stderr)
    return report


def _run_spec_ab(args) -> dict:
    """Speculative-decoding A/B: the same repetitive workload measured
    with spec_decode off then on (separately built + warmed engines).
    The headline value is the spec-ON tok/s; vs_baseline is the on/off
    ratio; the detail block carries both full reports plus the
    acceptance stats, all in the ONE driver-parsed JSON line."""
    import copy

    reports = {}
    for on in (False, True):
        a = copy.copy(args)
        a._spec_on = on
        print(f"--- spec A/B: speculation {'ON' if on else 'OFF'} ---",
              file=sys.stderr)
        reports["spec_on" if on else "spec_off"] = asyncio.run(run_bench(a))
    off_tps = reports["spec_off"]["output_tok_per_s"]
    value = reports["spec_on"]["output_tok_per_s"]
    out = {"metric": metric_name(args), "value": value,
           "unit": metric_unit(args),
           "vs_baseline": round(value / off_tps, 3) if off_tps else None,
           "detail": reports}
    return out


def _run_sweep(args) -> dict:
    """Batch-geometry sweep over (concurrency, max_batch, decode_steps):
    one engine per distinct (max_batch, decode_steps) — separately warmed
    and torn down so pools don't stack in HBM — measuring the headline
    workload at each point. Proves (or spends) the 'remaining headroom is
    batch geometry' claim from the round-3 notes with data instead of a
    roofline argument."""
    import copy

    points = []
    for spec in args.sweep.split(","):
        conc, mb, ds = (int(x) for x in spec.strip().split(":"))
        points.append((conc, mb, ds))
    rows = []
    for conc, mb, ds in points:
        a = copy.copy(args)
        a.concurrency, a.max_batch, a.decode_steps = conc, mb, ds
        # more requests than 2 concurrency waves so steady-state dominates
        a.requests = max(args.requests, 2 * conc)
        print(f"--- sweep point conc={conc} max_batch={mb} "
              f"decode_steps={ds} ---", file=sys.stderr)
        try:
            rep = asyncio.run(run_bench(a))
        except Exception as e:  # one bad point must not kill the sweep
            print(f"sweep point failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        rows.append({"concurrency": conc, "max_batch": mb,
                     "decode_steps": ds, **rep})
        print(json.dumps(rows[-1]), file=sys.stderr)
    if not rows:
        raise RuntimeError("every sweep point failed")
    hdr = (f"{'conc':>5} {'max_b':>5} {'K':>3} {'out tok/s':>10} "
           f"{'ttft_p50':>9} {'itl_p50':>8} {'err':>4}")
    print(hdr, file=sys.stderr)

    def cell(v, w):  # all-error points report their percentiles as None
        return f"{'-' if v is None else v:>{w}}"

    for r in rows:
        print(f"{r['concurrency']:>5} {r['max_batch']:>5} "
              f"{r['decode_steps']:>3} {cell(r['output_tok_per_s'], 10)} "
              f"{cell(r['ttft_p50_ms'], 9)} {cell(r['itl_p50_ms'], 8)} "
              f"{r['errors']:>4}", file=sys.stderr)
    best = max(rows, key=lambda r: r["output_tok_per_s"])
    return {"metric": metric_name(args),
            "value": best["output_tok_per_s"], "unit": metric_unit(args),
            "vs_baseline": 1.0,
            "detail": {"best": best, "sweep": rows}}


def main() -> int:
    args = parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
        if args.scenario == "sharded":
            # the forced-device-count flag must land in XLA_FLAGS before
            # the jax backend initializes (silently ignored afterwards)
            from dynamo_tpu.parallel.serving import \
                apply_forced_host_devices
            from dynamo_tpu.runtime.config import env_set_default

            env_set_default("DYN_FORCE_HOST_DEVICES", "8")
            apply_forced_host_devices()
    try:
        from dynamo_tpu.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        device = device_record(args)
        record = _run_scenario(args)
    except Exception as e:
        # no chip, or a scenario that raised: the structured record (no
        # number) and a non-zero exit — never a CPU number in its place
        import traceback
        traceback.print_exc()
        emit_error(args, f"{type(e).__name__}: {e}"[:300])
        return 1
    record.update(device)
    if getattr(args, "trip_incident", False):
        record["blackbox"] = _trip_incident(args)
    if getattr(args, "report_out", None):
        # full machine-readable record for the perf trajectory; must
        # round-trip through json.load (tier-1 gated)
        with open(args.report_out, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"report written to {args.report_out}", file=sys.stderr)
    # the ONE line the driver records
    print(json.dumps(record))
    return 0


def _trip_incident(args) -> dict:
    """dynablack --trip-incident: manual capture after the workload, so
    a chip run proves an armed recorder yields a renderable bundle
    without perturbing the benched path (the trip happens post-run)."""
    from dynamo_tpu.runtime import blackbox

    rec = blackbox.get_recorder()
    if not rec.enabled:
        return {"armed": False, "window_s": rec.window_s}
    bundle = rec.trip("manual", {"via": "bench"})
    if bundle is None:
        return {"armed": True, "captured": False,
                "cooldown_remaining_s": round(rec.cooldown_remaining_s(), 3)}
    block = {"armed": True, "captured": True,
             "incident_id": bundle["id"],
             "workers": sorted(bundle["workers"])}
    if getattr(args, "report_out", None):
        stem = args.report_out
        if stem.endswith(".json"):
            stem = stem[:-len(".json")]
        path = stem + ".incident.json"
        with open(path, "w") as f:
            f.write(blackbox.render_bundle_json(bundle))
            f.write("\n")
        print(f"incident bundle written to {path}", file=sys.stderr)
        block["bundle_path"] = path
    return block


def _run_scenario(args) -> dict:
    if getattr(args, "trace", False):
        # force-sample every benched request and size the ring to hold
        # the whole run's spans (~a dozen per request on the disagg path)
        from dynamo_tpu.runtime import tracing

        tracing.configure(sample=1.0,
                          ring=max(4096, args.requests * 64))
    if args.spec:
        return _run_spec_ab(args)
    if args.sweep:
        return _run_sweep(args)
    if args.scenario == "multiturn":
        report = asyncio.run(run_multiturn(args))
        return {"metric": metric_name(args),
                "value": report["ttft_later_turns_p50_ms"],
                "unit": metric_unit(args), "vs_baseline": 1.0,
                "detail": report}
    if args.scenario == "disagg":
        report = asyncio.run(run_disagg(args))
        return {"metric": metric_name(args),
                "value": report["disagg_over_agg_req_per_s"],
                "unit": metric_unit(args), "vs_baseline": 1.0,
                "detail": report}
    if args.scenario == "shared" and getattr(args, "cache_ab", False):
        return run_shared_cache_ab(args)
    if args.scenario == "shared":
        report = asyncio.run(run_shared(args))
        return {"metric": metric_name(args),
                "value": report["prefix_hit_rate"],
                "unit": metric_unit(args),
                "vs_baseline": report["ttft_noshare_over_share"] or 1.0,
                "detail": report}
    if args.scenario == "sharded":
        report = asyncio.run(run_sharded(args))
        return {"metric": metric_name(args),
                "value": report["sharded"]["output_tok_per_s"],
                "unit": metric_unit(args),
                "vs_baseline":
                    report["sharded_over_unsharded_tok_per_s"],
                "detail": report}
    if args.scenario == "failover":
        report = asyncio.run(run_failover(args))
        return {"metric": metric_name(args),
                "value": report["churn"]["goodput_tok_per_s"],
                "unit": metric_unit(args), "vs_baseline": 1.0,
                "detail": report}
    if args.scenario == "hotpath":
        report = asyncio.run(run_hotpath(args))
        return {"metric": metric_name(args),
                "value": report["itl_raw_chunk_p99_ms"],
                "unit": metric_unit(args), "vs_baseline": 1.0,
                "detail": report}
    report = asyncio.run(run_bench(args))
    # vs_baseline: reference publishes no absolute numbers —
    # BASELINE.json.published == {} — so round-over-round ratio
    # starts at 1.0
    prev = None
    if os.path.exists("BENCH_prev.json"):
        try:
            with open("BENCH_prev.json") as f:
                prev = json.load(f).get("value")
        except Exception:
            prev = None
    value = report["output_tok_per_s"]
    return {"metric": metric_name(args), "value": value,
            "unit": metric_unit(args),
            "vs_baseline": round(value / prev, 3) if prev else 1.0,
            "detail": report}


if __name__ == "__main__":
    sys.exit(main())
