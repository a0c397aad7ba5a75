"""`dynamo serve` — deploy a service graph as supervised processes.

Reference deploy/dynamo/sdk/cli (SURVEY §2.7): ``serve`` loads the graph
module, computes the linked-service set, and spawns one process per service
worker (the reference uses circus watchers; here a plain asyncio
supervisor). ``serve-worker`` is the per-process entrypoint (reference
cli/serve_dynamo.py). The GPU allocator (cli/allocator.py slicing
CUDA_VISIBLE_DEVICES) becomes TPU-chip gating: services that declare no
``resources={"tpu": N}`` are pinned to CPU JAX so they never grab the chip.

Usage:
    python -m dynamo_tpu.sdk.cli serve examples.llm.graphs.agg:Frontend \
        -f configs/agg.yaml [--dcp HOST:PORT]
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import logging
import os
import signal
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from ..runtime.config import env_str
from .config import ENV_KEY, ServiceConfig
from .service import DynamoService

log = logging.getLogger("dynamo_tpu.sdk.cli")


def load_target(target: str) -> DynamoService:
    """Resolve ``pkg.module:ServiceName`` to the entry DynamoService."""
    if ":" not in target:
        raise SystemExit(f"target must be module:Service, got {target!r}")
    mod_name, attr = target.split(":", 1)
    mod = importlib.import_module(mod_name)
    svc = getattr(mod, attr)
    if not isinstance(svc, DynamoService):
        raise SystemExit(f"{target} is not a @service (got {type(svc)})")
    return svc


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(svc: DynamoService, dcp: str, cfg: ServiceConfig) -> dict:
    env = dict(os.environ)
    env["DYN_DCP_ADDRESS"] = dcp
    env[ENV_KEY] = cfg.to_env_value()
    if not svc.resources.get("tpu"):
        # CPU-pin control-plane services so only TPU workers touch the chip
        env.setdefault("JAX_PLATFORMS", "cpu")
    return env


async def cmd_serve(args) -> int:
    entry = load_target(args.target)
    cfg = (await asyncio.to_thread(ServiceConfig.from_yaml, args.config)
           if args.config else ServiceConfig.from_env())
    graph = entry.graph()
    log.info("graph: %s", " -> ".join(s.name for s in graph))

    dcp_proc: Optional[subprocess.Popen] = None
    dcp = args.dcp
    if not dcp:
        port = _free_port()
        dcp = f"127.0.0.1:{port}"
        dcp_proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.runtime.dcp_server",
             "--host", "127.0.0.1", "--port", str(port)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        log.info("embedded control plane at %s (pid %d)", dcp, dcp_proc.pid)
        await asyncio.sleep(0.3)

    procs: List[Tuple[DynamoService, subprocess.Popen]] = []
    restarts: Dict[int, int] = {}

    for svc in graph:
        for _ in range(max(svc.workers, 1)):
            p = subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.sdk.cli", "serve-worker",
                 "--target", args.target, "--service", svc.name],
                env=_worker_env(svc, dcp, cfg))
            procs.append((svc, p))
            log.info("spawned %s worker pid %d", svc.name, p.pid)

    loop = asyncio.get_running_loop()
    stop_ev = asyncio.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop_ev.set)
        except NotImplementedError:
            pass

    async def supervise():
        nonlocal procs
        while not stop_ev.is_set():
            await asyncio.sleep(0.5)
            for i, (svc, p) in enumerate(list(procs)):
                rc = p.poll()
                if rc is None or stop_ev.is_set():
                    continue
                n = restarts.get(i, 0)
                if n >= args.max_restarts:
                    log.error("%s worker died rc=%s; restart budget spent",
                              svc.name, rc)
                    stop_ev.set()
                    return
                restarts[i] = n + 1
                log.warning("%s worker died rc=%s; restarting (%d/%d)",
                            svc.name, rc, n + 1, args.max_restarts)
                procs[i] = (svc, subprocess.Popen(
                    [sys.executable, "-m", "dynamo_tpu.sdk.cli",
                     "serve-worker", "--target", args.target,
                     "--service", svc.name],
                    env=_worker_env(svc, dcp, cfg)))

    try:
        await supervise()
        await stop_ev.wait()
    finally:
        for _, p in procs:
            if p.poll() is None:
                p.terminate()
        for _, p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if dcp_proc is not None:
            dcp_proc.terminate()
            try:
                dcp_proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                dcp_proc.kill()
    return 0


async def cmd_serve_worker(args) -> int:
    from ..runtime.runtime import DistributedRuntime, Runtime
    from .runner import ServiceWorker

    entry = load_target(args.target)
    svc = next((s for s in entry.graph() if s.name == args.service), None)
    if svc is None:
        raise SystemExit(f"service {args.service!r} not in graph of "
                         f"{args.target}")
    cfg = ServiceConfig.from_env()
    if svc.resources.get("tpu"):
        # before the worker's first jit (control-plane services never
        # compile, and stay off jax)
        from ..runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
    runtime = await asyncio.to_thread(Runtime)
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, runtime.shutdown)
        except NotImplementedError:
            pass
    drt = await DistributedRuntime.attach(
        env_str("DYN_DCP_ADDRESS"), runtime)
    worker = ServiceWorker(svc, drt, cfg)
    try:
        await worker.start()
        await runtime.shutdown_event.wait()
    finally:
        await worker.stop()
        await drt.shutdown()
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    logging.basicConfig(level=env_str("DYN_LOG"))
    ap = argparse.ArgumentParser(prog="dynamo")
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("serve", help="deploy a service graph")
    s.add_argument("target", help="module.path:EntryService")
    s.add_argument("-f", "--config", help="service config YAML")
    s.add_argument("--dcp", help="external control-plane address")
    s.add_argument("--max-restarts", type=int, default=3)

    w = sub.add_parser("serve-worker", help="(internal) one service worker")
    w.add_argument("--target", required=True)
    w.add_argument("--service", required=True)

    args = ap.parse_args(argv)
    if args.cmd == "serve":
        return asyncio.run(cmd_serve(args))
    if args.cmd == "serve-worker":
        return asyncio.run(cmd_serve_worker(args))
    return 2


if __name__ == "__main__":
    sys.exit(main())
