"""The system under test, started the way chip_smoke.py starts it: ONE
process touches JAX and holds the engine, the OpenAI frontend runs
in-process through ``run.run_http(args, built=...)`` and is stopped by
its own SIGTERM path. Copied from chip_smoke.py (ran on the chip in
PR 21): the device check, the server start and stop, the greedy
request, the comparison rule. Not copied: its reference. Each
configuration names its own (``cells.load_reference``); the rule of
agreement is one for all of them (``benchmark/reference.py judge``).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import os
import signal
import socket
import time
from typing import List


class BenchFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailed(msg)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def device_info(chips: int, require_platform) -> dict:
    """As JAX reports it; fails where the platform is not the required
    one or the chips are fewer than the cell asks for."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check((require_platform is None or dev["platform"] == require_platform)
          and len(devs) >= chips,
          f"need {chips} device(s) of platform {require_platform!r}; "
          f"jax reports {dev}")
    dev["count"] = chips
    return dev


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def build(cell: dict, seed: int, port: int):
    """(args, built): the cell's engine with the benchmark's weights,
    not warmed. ``cell["engine"]`` holds EngineConfig overrides."""
    from benchmark.harness import cells, weights
    from dynamo_tpu import run
    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.models.registry import get_model_module

    args = run.parse_args([
        "in=http", "out=jax", "--model-path", cell["model_path"],
        "--seed", str(seed & 0x7FFFFFFF), "--http-host", "127.0.0.1",
        "--http-port", str(port), "--no-warmup"])
    cfg, ecfg, _none, quant, mesh = run._jax_engine_setup(args)
    ecfg = dataclasses.replace(ecfg, **cells.engine_overrides(cell))
    params = weights.make_params(get_model_module(cfg), cfg, seed,
                                 cell["weight_scales"])
    engine = JaxEngine(cfg, ecfg, params=params, seed=args.seed, mesh=mesh,
                       quant=quant)
    mdc = run.build_mdc(args)
    mdc.kv_block_size = ecfg.page_size
    return args, (engine, mdc, False)


async def greedy(engine, prompt: List[int], n: int):
    """(token ids, per-token {id: logprob} top-20) from the engine's
    generate(): the path every request takes."""
    from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                                 PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        output=OutputOptions(logprobs=20))
    toks, tops = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        tops.extend(out.top_logprobs or [])
        if out.finish_reason is not None:
            break
    return toks, tops


AGREE_PROMPT = 96
AGREE_STEPS = 8


def _reference_logprobs(engine, reference_logits, prompt, toks):
    """[1 + AGREE_STEPS, V] reference logprobs at the positions the
    engine sampled from, teacher-forced on the engine's own tokens."""
    import jax
    import numpy as np

    seq = list(prompt) + toks[:-1]
    # called before warmup() arms the compile fence: the reference's
    # programs are the benchmark's, not serving compiles
    with jax.default_matmul_precision("highest"), engine._on_device():
        logits = reference_logits(engine.params, engine.cfg, seq)
        return np.asarray(jax.nn.log_softmax(logits[len(prompt) - 1:], -1))


async def agree(engine, seed: int, reference_logits) -> dict:
    """reference.PROMPTS seeded 96-token prompts + 8 greedy steps each
    against the configuration's ``reference_logits``, on the cell's own
    engine, before the window; all positions judged together
    (``reference.judge``)."""
    import random

    import numpy as np

    from benchmark.reference import PROMPTS, judge

    refs, all_toks, all_tops = [], [], []
    for k in range(PROMPTS):
        rng = random.Random(f"{seed}/agree/{k}")
        prompt = [rng.randrange(1, engine.cfg.vocab_size)
                  for _ in range(AGREE_PROMPT)]
        toks, tops = await greedy(engine, prompt, 1 + AGREE_STEPS)
        check(len(toks) == 1 + AGREE_STEPS and len(tops) == len(toks),
              f"engine returned {len(toks)} tokens / {len(tops)} "
              f"logprob rows")
        refs.append(await asyncio.to_thread(
            _reference_logprobs, engine, reference_logits, prompt, toks))
        all_toks += toks
        all_tops += tops
    return judge(np.concatenate(refs), all_toks, all_tops)


@contextlib.asynccontextmanager
async def serving(args, built):
    """The frontend up on args.http_port; stopped by the server's own
    SIGTERM path (-> _wait_for_signal -> HttpService.stop())."""
    import aiohttp

    from dynamo_tpu import run

    base = f"http://127.0.0.1:{args.http_port}"
    server = asyncio.create_task(run.run_http(args, built=built))
    try:
        async with aiohttp.ClientSession() as http:
            for _ in range(400):
                check(not server.done(), "run_http ended before serving")
                try:
                    async with http.get(f"{base}/health") as r:
                        if r.status == 200:
                            break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.05)
            else:
                raise BenchFailed("frontend never answered /health")
        yield base
    finally:
        if not server.done():
            os.kill(os.getpid(), signal.SIGTERM)
        await asyncio.wait_for(server, 60)


class Tracer:
    """A profiler trace of a slice in the middle of the window, taken in
    the serving process (only the process that holds the chip can trace
    it), off the event loop's thread."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t0 = self.window_s = 0.0

    async def slice(self, start_in_s: float, length_s: float) -> None:
        import jax

        # no Python call tracing: it slows the host it is measuring and
        # makes the file ten times larger; host TraceMe events stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        await asyncio.sleep(max(start_in_s, 0.0))
        await asyncio.to_thread(jax.profiler.start_trace, self.out_dir,
                                profiler_options=opts)
        self.t0 = time.monotonic()
        await asyncio.sleep(length_s)
        self.window_s = time.monotonic() - self.t0
        await asyncio.to_thread(jax.profiler.stop_trace)
