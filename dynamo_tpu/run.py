"""`dynamo-run` — the single-command launcher: ``in=… out=…``.

Reference launch/dynamo-run (SURVEY §2.5): one binary wiring an input
frontend to an output engine:

    python -m dynamo_tpu.run in=http out=jax --model-path /models/llama
    python -m dynamo_tpu.run in=text out=echo_core
    python -m dynamo_tpu.run in=batch:prompts.jsonl out=jax --model tiny
    python -m dynamo_tpu.run in=dyn://ns.comp.generate out=jax ...  # worker
    python -m dynamo_tpu.run in=http out=dyn                        # frontend

Inputs (reference dynamo-run lib.rs Input):
  http           OpenAI HTTP frontend (chat + completions + models + metrics)
  text           interactive chat REPL
  batch:<jsonl>  benchmark mode: per-request tokens_in/tokens_out/elapsed_ms
                 + aggregate throughput (reference input/batch.rs:42-105)
  dyn://path     worker mode: serve the engine behind the LLM pipeline on
                 the distributed runtime + register the model for discovery
                 (reference input/endpoint.rs:35-117)
  none           construct the engine, idle until SIGINT (warmup/debug)

Outputs (reference dynamo-run Output):
  jax            the JAX paged-KV engine (this framework's vLLM analog)
  echo_core      token-level echo fake engine (CI, no TPU)
  echo_full      OpenAI-level echo fake engine
  dyn[://path]   remote engines discovered from the control plane
                 (in=http becomes the standalone frontend, components/http)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import signal
import sys
import time

from .runtime.config import env_int, env_str
from .runtime.profiling import setup_ledger, setup_span
from typing import Optional, Tuple

log = logging.getLogger("dynamo_tpu.run")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="dynamo-run", usage="%(prog)s in=<input> out=<engine> [flags]")
    ap.add_argument("io", nargs="*", help="in=… and out=… positionals")
    ap.add_argument("--model-path", help="local HF-style model directory")
    ap.add_argument("--model-id", default=None,
                    help="HuggingFace model id (or local path) — resolved "
                         "cache-first via the HF hub (reference "
                         "launch/dynamo-run/src/hub.rs)")
    ap.add_argument("--model-name", help="served model name")
    ap.add_argument("--model", default=None,
                    help="preset when no --model-path: tiny|1b|8b")
    ap.add_argument("--http-port", type=int, default=8080)
    ap.add_argument("--http-host", default="0.0.0.0")
    ap.add_argument("--dcp", default=None, help="control-plane address "
                    "(default: DYN_DCP_ADDRESS or embedded)")
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--endpoint", default=None,
                    help="override dyn:// endpoint path")
    ap.add_argument("--context-length", type=int, default=None)
    ap.add_argument("--kv-cache-block-size", type=int, default=None,
                    help="tokens per KV page (reference flag name)")
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--max-batch-size", type=int, default=None)
    ap.add_argument("--tensor-parallel-size", type=int, default=1)
    ap.add_argument("--sequence-parallel-size", type=int, default=1,
                    help="seq-axis mesh size for ring-attention long "
                         "prefill (long-context serving)")
    ap.add_argument("--mesh-shape", default=env_str("DYN_MESH_SHAPE"),
                    help="dynashard: per-replica mesh as 'axis=N' pairs "
                         "(e.g. 'model=2', 'data=2,model=4'); overrides "
                         "--tensor-parallel-size/--sequence-parallel-size")
    ap.add_argument("--dp-replicas", type=int,
                    default=env_int("DYN_DP_REPLICAS") or 1,
                    help="dynashard: data-parallel engine replicas, each "
                         "on its own submesh with its own worker identity "
                         "behind the KV router (worker mode, out=jax)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative decoding: prompt-lookup drafts "
                         "verified in one [B, K+1] forward; greedy rows "
                         "only (token-identical), others bypass "
                         "(docs/serve.md 'Speculative decoding')")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="max draft tokens verified per step (K)")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="cap prompt tokens prefilled per engine "
                         "iteration and interleave decode windows "
                         "(chunked-prefill mixing; bounds ITL p99 under "
                         "prompt bursts at some TTFT cost)")
    ap.add_argument("--long-prefill-threshold", type=int, default=None,
                    help="prompts longer than this take the sequence-"
                         "parallel ring prefill (needs "
                         "--sequence-parallel-size > 1)")
    # multi-host SPMD bootstrap (replaces the reference's Ray head/follower
    # for vLLM multi-node TP, lib/llm/src/engines/vllm/ray.rs, and
    # SGLang's leader-addr handshake, engines/sglang/main.rs:48-76):
    # every process runs THIS same command with its own --process-id; JAX
    # forms the global device mesh across them
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 for jax.distributed "
                         "(multi-host TP; all processes pass the same value)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--max-tokens", type=int, default=128,
                    help="text/batch mode generation cap")
    ap.add_argument("--profile-dir", default=env_str(
        "DYN_PROFILE_DIR"), help="capture a JAX/XLA profiler trace of the "
        "serving session into this directory (view with xprof/tensorboard)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "int8"],
                    help="int8 = weight-only quantized serving "
                         "(models/quant.py): checkpoints quantize on the "
                         "host at load; ~half the HBM and decode "
                         "bytes/token of bf16")
    args = ap.parse_args(argv)

    if args.model_id and not args.model_path:
        from .models.hub import resolve_model
        args.model_path = resolve_model(args.model_id)
        if not args.model_name:
            args.model_name = args.model_id

    args.input, args.output = "http", "jax"
    for tok in args.io:
        if tok.startswith("in="):
            args.input = tok[3:]
        elif tok.startswith("out="):
            args.output = tok[4:]
        else:
            ap.error(f"positional args must be in=…/out=…, got {tok!r}")
    return args


# ------------------------------------------------------------ engine build


def build_model_config(args):
    from .models.config import ModelConfig

    if args.model_path:
        return ModelConfig.from_local_path(args.model_path)
    preset = args.model or "tiny"
    if preset == "tiny":
        return ModelConfig.tiny()
    if preset == "1b":
        return ModelConfig(vocab_size=128256, hidden_size=2048,
                           intermediate_size=8192, num_layers=16,
                           num_heads=32, num_kv_heads=8, head_dim=64,
                           dtype="bfloat16")
    if preset == "8b":
        return ModelConfig.llama3_8b()
    raise SystemExit(f"unknown --model preset {preset!r}")


def build_mdc(args):
    from .llm.model_card import ModelDeploymentCard

    if args.model_path:
        mdc = ModelDeploymentCard.from_local_path(
            args.model_path, name=args.model_name)
    else:
        mdc = ModelDeploymentCard(name=args.model_name or
                                  (args.model or "echo"))
    if args.context_length:
        mdc.context_length = args.context_length
    if args.kv_cache_block_size:
        mdc.kv_block_size = args.kv_cache_block_size
    return mdc


def build_engine(args) -> Tuple[object, object, bool]:
    """Returns (core_or_full_engine, mdc, is_full_level)."""
    from .engine.echo import EchoEngineCore, EchoEngineFull

    mdc = build_mdc(args)
    if args.output == "echo_core":
        return EchoEngineCore(), mdc, False
    if args.output == "echo_full":
        return EchoEngineFull(), mdc, True
    if args.output.startswith(("pystr:", "pytok:")):
        # user Python engines (reference engines/python.rs: pystr = full
        # OpenAI level, pytok = token-level core behind the Backend)
        kind, path = args.output.split(":", 1)
        return _load_python_engine(path, kind), mdc, kind == "pystr"
    if args.output == "jax":
        from .engine.jax_engine import JaxEngine

        cfg, ecfg, params, quant, mesh = _jax_engine_setup(args)
        mdc.kv_block_size = ecfg.page_size
        engine = JaxEngine(cfg, ecfg, params=params, seed=args.seed,
                           mesh=mesh, quant=quant)
        if not args.no_warmup:
            engine.warmup(progress=True)
        return engine, mdc, False
    raise SystemExit(f"unknown out={args.output!r}")


def mesh_axes_for(args) -> dict:
    """The per-replica mesh axes: --mesh-shape (or DYN_MESH_SHAPE) wins;
    the per-axis convenience flags otherwise."""
    from .parallel.serving import parse_mesh_shape

    if getattr(args, "mesh_shape", None):
        return parse_mesh_shape(args.mesh_shape)
    axes = {}
    if args.tensor_parallel_size > 1:
        axes["model"] = args.tensor_parallel_size
    if args.sequence_parallel_size > 1:
        axes["seq"] = args.sequence_parallel_size
    return axes


@setup_span("engine_setup")
def _jax_engine_setup(args):
    """The out=jax configuration assembly, shared by the single-engine
    build and the dynashard replica set: returns
    (model_cfg, engine_cfg, params, quant, mesh). ``mesh`` is the
    whole-local-device mesh of the single-engine path; the replica set
    ignores it and partitions submeshes itself (mesh_axes_for)."""
    import dataclasses

    from .engine.jax_engine import EngineConfig
    from .models.loader import load_params

    cfg = build_model_config(args)
    ecfg = EngineConfig()
    if args.model in (None, "tiny") and not args.model_path:
        ecfg = EngineConfig(page_size=16, num_pages=256, max_batch=16,
                            prefill_chunk=128, prefill_buckets=(128,),
                            batch_buckets=(4, 16), page_buckets=(16,))
    overrides = {}
    if args.kv_cache_block_size:
        overrides["page_size"] = args.kv_cache_block_size
        # keep the chunk a page multiple (the page-granular KV commit
        # invariant __post_init__ enforces)
        overrides["prefill_chunk"] = max(
            ecfg.prefill_chunk // args.kv_cache_block_size, 1
        ) * args.kv_cache_block_size
    if args.num_pages:
        overrides["num_pages"] = args.num_pages
    if args.max_batch_size:
        overrides["max_batch"] = args.max_batch_size
    if args.prefill_token_budget is not None:
        overrides["prefill_token_budget"] = args.prefill_token_budget
    if args.spec_decode:
        overrides["spec_decode"] = True
        overrides["spec_tokens"] = args.spec_tokens
    if overrides:
        # replace() re-runs __post_init__ — CLI overrides get the same
        # validation as direct construction
        ecfg = dataclasses.replace(ecfg, **overrides)
    params = None
    mesh = None
    if args.coordinator:
        from .parallel.mesh import initialize_multihost
        initialize_multihost(args.coordinator, args.num_processes,
                             args.process_id)
        log.info("joined multi-host group %s as process %d/%d "
                 "(%d global devices)", args.coordinator,
                 args.process_id, args.num_processes,
                 len(__import__("jax").devices()))
    axes = mesh_axes_for(args)
    if axes and getattr(args, "dp_replicas", 1) <= 1:
        from .parallel.mesh import MeshSpec
        mesh = MeshSpec(**axes).build()
    if args.long_prefill_threshold is not None:
        if axes.get("seq", 1) <= 1:
            raise SystemExit(
                "--long-prefill-threshold needs a seq mesh axis > 1 "
                "(--sequence-parallel-size or --mesh-shape seq=N: the "
                "ring prefill runs over the mesh's seq axis)")
        ecfg = dataclasses.replace(
            ecfg, long_prefill_threshold=args.long_prefill_threshold)
    quant = "int8" if args.dtype == "int8" else None
    if args.model_path:
        try:
            params = load_params(args.model_path, cfg, quant=quant)
            quant = None  # already applied on the host at load
        except FileNotFoundError:
            log.warning("no weights at %s; random init", args.model_path)
    return cfg, ecfg, params, quant, mesh


def _load_python_engine(path: str, kind: str):
    """Load a user engine file (reference engines/python.rs:16-90 —
    ``pystr:<file.py>`` / ``pytok:<file.py>``): the module must define
    ``async def generate(request, context)`` (async generator). pystr
    yields OpenAI chunk dicts; pytok yields EngineOutput-shaped dicts."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("dyn_user_engine", path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"cannot load python engine from {path!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    gen = getattr(mod, "generate", None)
    if gen is None:
        raise SystemExit(f"{path} must define `async def generate(request, "
                         f"context)`")

    if kind == "pystr":
        class _PyStrEngine:
            def __call__(self, request, context):
                payload = request.model_dump(exclude_none=True) \
                    if hasattr(request, "model_dump") else request
                return gen(payload, context)

        return _PyStrEngine()

    class _PyTokEngine:
        async def generate(self, request, context):
            from .llm.protocols.common import EngineOutput

            payload = request.to_dict() if hasattr(request, "to_dict") \
                else request
            async for out in gen(payload, context):
                yield out if isinstance(out, EngineOutput) \
                    else EngineOutput.from_dict(out)

    return _PyTokEngine()


# -------------------------------------------------------------- input modes


async def run_http(args, built=None) -> None:
    """Serve the OpenAI frontend until SIGINT/SIGTERM. ``built`` is an
    already-built ``build_engine(args)`` result (chip_smoke.py builds the
    engine itself so it can time the warm-up and inspect it afterwards);
    None builds it here."""
    if built is None and not args.output.startswith("dyn"):
        built = await asyncio.to_thread(build_engine, args)
    with setup_ledger().span("http_start"):
        svc, watcher, drt = await _start_http(args, built)
    log.info("OpenAI frontend on %s:%d: %s", args.http_host, args.http_port,
             setup_ledger().ready_line())
    await _wait_for_signal()
    await svc.stop()
    if watcher:
        await watcher.stop()
    if drt:
        await drt.shutdown()


async def _start_http(args, built):
    """run_http from its imports to the socket listening (the set-up
    ledger's ``http_start`` span): (service, watcher, runtime)."""
    from .llm.engines import LocalChatChain, LocalCompletionChain
    from .llm.http.discovery import ModelWatcher
    from .llm.http.service import HttpService, ModelManager

    manager = ModelManager()
    svc = HttpService(manager)
    watcher = None
    drt = None
    if built is None:
        # standalone frontend: discover models from the control plane
        # (reference components/http/src/main.rs + model watcher)
        drt = await _attach(args)
        watcher = ModelWatcher(drt, manager)
        await watcher.start()
    else:
        engine, mdc, full = built
        if full:
            manager.add_chat_model(mdc.name, engine)
        else:
            chat = LocalChatChain(mdc, engine)
            comp = LocalCompletionChain(mdc, engine, chat.preprocessor)
            manager.add_chat_model(mdc.name, chat)
            manager.add_completions_model(mdc.name, comp)
        from .runtime import revive

        if hasattr(engine, "stats"):
            # dynarevive admission control over the local engine's own
            # signals; sheds nothing until DYN_SHED_* thresholds are set
            svc.set_admission(revive.AdmissionController(
                lambda: revive.signals_from_stats(engine.stats())))
        fence = getattr(engine, "fence", None)
        if fence is not None:
            # no aggregator scrapes an in-process engine, so its compile
            # fence counter goes on this frontend's own /metrics under
            # the aggregator's name for it
            svc.metrics.add_source(lambda: [
                "# TYPE dyn_engine_post_warmup_compiles_total counter",
                "dyn_engine_post_warmup_compiles_total "
                f"{fence.post_warmup_compiles}"])
        if hasattr(engine, "drain"):
            # POST /drain: stop admitting, finish in-flight bounded by
            # DYN_DRAIN_TIMEOUT_MS
            svc.on_drain(lambda: engine.drain(revive.drain_timeout_s()))
    await svc.start(args.http_host, args.http_port)
    return svc, watcher, drt


async def run_text(args) -> None:
    from .llm.engines import LocalChatChain
    from .runtime.engine import Context

    engine, mdc, full = await asyncio.to_thread(build_engine, args)
    chain = engine if full else LocalChatChain(mdc, engine)
    print(f"chat with {mdc.name} — empty line or ^D to exit", flush=True)
    history = []
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except (EOFError, KeyboardInterrupt):
            break
        if not line.strip():
            break
        history.append({"role": "user", "content": line})
        req = {"model": mdc.name, "messages": history, "stream": True,
               "max_tokens": args.max_tokens}
        from .llm.protocols.openai import ChatCompletionRequest

        from .llm.http.service import _chunk_dict

        text = []
        async for chunk in chain(ChatCompletionRequest(**req), Context()):
            d = _chunk_dict(chunk)
            if not isinstance(d, dict):
                continue
            for c in d.get("choices", []):
                delta = (c.get("delta") or {}).get("content")
                if delta:
                    text.append(delta)
                    print(delta, end="", flush=True)
        print()
        history.append({"role": "assistant", "content": "".join(text)})
    if hasattr(engine, "stop"):
        await engine.stop()


async def run_batch(args, path: str) -> None:
    """Benchmark mode (reference input/batch.rs:42-105): JSONL in
    ({"text": …} or {"prompt": …}), JSONL out with per-request tokens_in/
    tokens_out/elapsed_ms; aggregate printed at the end."""
    from .llm.engines import LocalChatChain
    from .llm.protocols.openai import ChatCompletionRequest
    from .runtime.engine import Context

    engine, mdc, full = await asyncio.to_thread(build_engine, args)
    chain = engine if full else LocalChatChain(mdc, engine)

    def _read_jsonl() -> list:
        entries = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    entries.append(json.loads(line))
        return entries

    # file IO off the event loop: batch inputs can be large
    entries = await asyncio.to_thread(_read_jsonl)
    results = []
    t0 = time.monotonic()

    async def one(i, entry):
        text = entry.get("text") or entry.get("prompt") or ""
        req = ChatCompletionRequest(
            model=mdc.name, stream=True,
            messages=[{"role": "user", "content": text}],
            max_tokens=entry.get("max_tokens", args.max_tokens))
        from .llm.http.service import _chunk_dict

        start = time.monotonic()
        n_out = 0
        async for chunk in chain(req, Context()):
            d = _chunk_dict(chunk)
            if isinstance(d, dict) and d.get("choices"):
                if (d["choices"][0].get("delta") or {}).get("content"):
                    n_out += 1
        elapsed = time.monotonic() - start
        results.append({"index": i, "tokens_in": len(text.split()),
                        "tokens_out": n_out,
                        "elapsed_ms": round(elapsed * 1000, 1)})

    await asyncio.gather(*(one(i, e) for i, e in enumerate(entries)))
    wall = time.monotonic() - t0
    for r in sorted(results, key=lambda r: r["index"]):
        print(json.dumps(r))
    total_out = sum(r["tokens_out"] for r in results)
    print(json.dumps({"aggregate": {
        "requests": len(results), "wall_s": round(wall, 3),
        "output_tok_per_s": round(total_out / wall, 1) if wall else 0.0}}))
    if hasattr(engine, "stop"):
        await engine.stop()


async def run_worker(args, path: str) -> None:
    """``in=dyn://ns.comp[.ep]``: serve the engine as a discoverable model
    worker (reference input/endpoint.rs worker mode). With
    ``--dp-replicas N > 1`` (out=jax only) the process serves a dynashard
    :class:`ShardedReplicaSet` instead: N mesh-sharded engine replicas on
    partitioned submeshes, each its own worker instance behind the KV
    router."""
    from .llm.worker import serve_openai_model
    from .runtime.component import EndpointAddress

    if args.dp_replicas > 1:
        if args.output != "jax":
            raise SystemExit("--dp-replicas needs out=jax")
        await _run_sharded_worker(args, path)
        return
    engine, mdc, full = await asyncio.to_thread(build_engine, args)
    if full:
        raise SystemExit("worker mode needs a token-level engine "
                         "(out=jax or out=echo_core)")
    addr = EndpointAddress.parse(path)
    drt = await _attach(args)
    handle = await serve_openai_model(
        drt, mdc, engine, namespace=addr.namespace,
        component=addr.component, endpoint=addr.endpoint,
        stats_handler=getattr(engine, "stats", None))
    log.info("worker serving %s: %s", path, setup_ledger().ready_line())
    sig = await _wait_for_signal()
    if sig == signal.SIGTERM:
        # rolling restart: discovery record out first (no new
        # admissions), in-flight sequences finish bounded by
        # DYN_DRAIN_TIMEOUT_MS, then the lease releases (dynarevive)
        from .runtime import revive

        await revive.drain_worker(handle, engine=engine)
    else:
        await handle.stop()
    if hasattr(engine, "stop"):
        await engine.stop()
    await drt.shutdown()


async def _run_sharded_worker(args, path: str) -> None:
    """dynashard worker mode: N data-parallel sharded replicas of one
    token-level component, each with its own lease/instance id and KV
    publisher (parallel/serving.py)."""
    from .parallel.serving import ShardedReplicaSet
    from .runtime.component import EndpointAddress

    addr = EndpointAddress.parse(path)
    cfg, ecfg, params, quant, _mesh = await asyncio.to_thread(
        _jax_engine_setup, args)
    # card construction can read model files — off the event loop
    mdc = await asyncio.to_thread(build_mdc, args)
    mdc.kv_block_size = ecfg.page_size
    replica_set = ShardedReplicaSet(
        cfg, ecfg, mesh_axes=mesh_axes_for(args),
        replicas=args.dp_replicas, namespace=addr.namespace,
        component=addr.component, mdc=mdc,
        dcp_address=args.dcp or env_str("DYN_DCP_ADDRESS"),
        params=params, seed=args.seed, quant=quant,
        warmup=not args.no_warmup)
    await replica_set.start()
    log.info("sharded worker serving %s: %s; %s", path,
             replica_set.describe(), setup_ledger().ready_line())
    sig = await _wait_for_signal()
    if sig == signal.SIGTERM:
        # lifecycle drain bounded internally by DYN_DRAIN_TIMEOUT_MS
        await replica_set.drain()  # dynalint: disable=unbounded-await
    else:
        await replica_set.stop()


async def run_none(args) -> None:
    engine, mdc, _ = await asyncio.to_thread(build_engine, args)
    log.info("engine %s ready (in=none); ^C to exit", mdc.name)
    await _wait_for_signal()
    if hasattr(engine, "stop"):
        await engine.stop()


# ----------------------------------------------------------------- helpers


async def _attach(args):
    from .runtime.runtime import DistributedRuntime

    address = args.dcp or env_str("DYN_DCP_ADDRESS")
    if address:
        return await DistributedRuntime.attach(address)
    log.warning("no control plane configured; starting embedded DCP server")
    return await DistributedRuntime.detached()


async def _wait_for_signal() -> int:
    """Park until SIGINT/SIGTERM; returns the signal number so callers
    can pick fast teardown (SIGINT) vs graceful drain (SIGTERM — the
    rolling-restart signal, dynarevive docs/robustness.md)."""
    ev = asyncio.Event()
    fired: list = []
    loop = asyncio.get_running_loop()

    def _on_signal(signum: int) -> None:
        if not fired:
            fired.append(signum)
        ev.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, _on_signal, sig)
        except NotImplementedError:
            pass
    await ev.wait()
    return fired[0] if fired else signal.SIGINT


async def amain(args) -> int:
    profiling = False
    if args.profile_dir:
        # tracing/profiling plane (reference keeps tracing-crate spans;
        # on TPU the device story is the JAX profiler / XLA dumps)
        import jax

        jax.profiler.start_trace(args.profile_dir)
        profiling = True
    try:
        return await _dispatch(args)
    finally:
        if profiling:
            import jax

            jax.profiler.stop_trace()
            log.info("profiler trace written to %s", args.profile_dir)


async def _dispatch(args) -> int:
    if args.input == "http":
        await run_http(args)
    elif args.input == "text":
        await run_text(args)
    elif args.input.startswith("batch:"):
        await run_batch(args, args.input[len("batch:"):])
    elif args.input.startswith("dyn://") or args.input.startswith("dyn"):
        await run_worker(args, args.input)
    elif args.input == "none":
        await run_none(args)
    else:
        raise SystemExit(f"unknown in={args.input!r}")
    return 0


def main(argv=None) -> int:
    logging.basicConfig(level=env_str("DYN_LOG"))
    args = parse_args(argv)
    if args.output == "jax":
        from .runtime.compile_cache import enable_compile_cache
        log.info("compile cache at %s", enable_compile_cache())
    return asyncio.run(amain(args))


if __name__ == "__main__":
    sys.exit(main())
