#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts,
compiles and answers on the attached TPU.

    python chip_smoke.py             # one chip: device, kernels, serve,
                                     # agree, cache
    python chip_smoke.py --chips 4   # only the cross-chip phase: four
                                     # one-chip replicas behind the KV
                                     # router, and one model=4 engine

ONE process touches JAX (a chip belongs to one process): the OpenAI
frontend, the engine and the HTTP client of the smoke all live in this
process, and the server is stopped by its own SIGTERM path. The model is
the ``--model 1b`` preset at full width and depth with the default
EngineConfig; weights are random, from ``--seed``.

Each phase prints one JSON line; the run stops at the first phase that
fails with a non-zero exit. The LAST line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when the platform is ``tpu`` and every phase passed.
Times and rates on the ``serve`` line are smoke observations of one
short burst, not benchmark metrics.

Stated bounds (bf16 engine against float32 references):
  KERNEL_ATOL  max |kernel - XLA gather path| per attention output
  AGREE_ATOL   max |engine logprob - float32 reference logprob| over
               the engine's top-20 ids at each compared position; a
               top-1 that differs counts as equal only when the
               reference separates the two tokens by less than this
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import os
import signal
import socket
import sys
import time
from typing import List, Optional

KERNEL_ATOL = 2e-2
AGREE_ATOL = 1e-1
AGREE_STEPS = 8           # greedy decode steps compared after the prefill
N_STREAM = 8              # concurrent streaming chat completions
OSL = 64                  # output tokens per request
N_ROUTED = 16             # --chips 4: requests through the KV router


@dataclasses.dataclass
class Settings:
    """What a run does. The CLI only sets ``chips`` and ``seed``; the
    other fields are the test-only hook (tests/test_chip_smoke.py drives
    every phase on the CPU at tiny size with the device check stubbed
    and the kernels in interpret mode)."""

    chips: int = 1
    seed: int = 0
    model: str = "1b"
    require_platform: Optional[str] = "tpu"
    interpret: bool = False          # CPU only: Pallas interpret mode
    prompt_lens: tuple = (64, 512)
    osl: int = OSL
    trim_grid_4chip: bool = True     # see phase_replicas
    tp: int = 4                      # the sharded engine's model axis


class PhaseFailed(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_args(s: Settings, *extra: str):
    from dynamo_tpu import run

    return run.parse_args(["in=http", "out=jax", "--model", s.model,
                           "--seed", str(s.seed), "--http-host",
                           "127.0.0.1", *extra])


def _word_text(rng, nchars: int) -> str:
    """Seeded filler of nchars characters (byte tokenizer: 1 per token)."""
    words = ("alpha bravo charlie delta echo foxtrot golf hotel india "
             "juliet kilo lima mike november oscar papa quebec romeo "
             "sierra tango uniform victor whiskey xray yankee zulu").split()
    out, n = [], 0
    while n < nchars:
        w = words[rng.randint(0, len(words) - 1)]
        out.append(w)
        n += len(w) + 1
    return " ".join(out)[:nchars]


# ------------------------------------------------------------------ device


def phase_device(s: Settings) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    ok = ((s.require_platform is None
           or dev["platform"] == s.require_platform)
          and len(devs) >= s.chips)
    emit("device", ok=ok, **dev)
    check(ok, f"need {s.chips} device(s) of platform "
              f"{s.require_platform!r}; jax reports {dev}")
    return dev


# ----------------------------------------------------------------- kernels


def phase_kernels(s: Settings) -> None:
    """Decode kernel (plain, layered, with stats) and the paged prefill
    kernel against the XLA gather path, at the model's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu import run
    from dynamo_tpu.models.llama import _paged_attention
    from dynamo_tpu.ops import paged_attention as pa

    cfg = run.build_model_config(_run_args(s))
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    big = s.model != "tiny"
    B, P, N, L, ps = (32, 64, 512, 16, 64) if big else (4, 4, 32, 2, 16)
    Bp, T = (8, 128) if big else (2, 16)
    dt = jnp.bfloat16
    rng = np.random.RandomState(s.seed)
    kq, kk, kv_, kp = jax.random.split(jax.random.PRNGKey(s.seed), 4)
    q = jax.random.normal(kq, (B, H, hd), dt)
    k_pools = jax.random.normal(kk, (L, N, KV, ps, hd), dt)
    v_pools = jax.random.normal(kv_, (L, N, KV, ps, hd), dt)
    table = jnp.asarray(rng.randint(1, N, (B, P)), jnp.int32)
    lengths = rng.randint(1, P * ps + 1, B).astype(np.int32)
    lengths[0], lengths[1] = P * ps, 1
    lengths = jnp.asarray(lengths)
    scale = hd ** -0.5
    layer = L - 1
    kl, vl = k_pools[layer], v_pools[layer]
    pos = (lengths - 1)[:, None]

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    want = _paged_attention(q[:, None], kl, vl, table, pos, scale)[:, 0]
    errs = {}
    errs["decode"] = err(pa.paged_attention_decode(
        q, kl, vl, table, lengths, scale=scale, interpret=s.interpret),
        want)
    errs["decode_layered"] = err(pa.paged_attention_decode_layered(
        q, k_pools, v_pools, jnp.int32(layer), table, lengths, scale=scale,
        interpret=s.interpret), want)
    out, m, l = pa.paged_attention_decode_layered(
        q, k_pools, v_pools, jnp.int32(layer), table, lengths, scale=scale,
        interpret=s.interpret, return_stats=True)
    errs["decode_stats_out"] = err(out, want)
    # the stats are the online-softmax running max and sum: check them
    # against the same quantities from dense float32 scores
    kd = kl[table].transpose(0, 1, 3, 2, 4).reshape(B, P * ps, KV, hd)
    sc = jnp.einsum("bkgh,bskh->bkgs",
                    q.reshape(B, KV, H // KV, hd).astype(jnp.float32),
                    kd.astype(jnp.float32)) * scale
    sc = jnp.where(jnp.arange(P * ps)[None, None, None, :]
                   < lengths[:, None, None, None], sc, -1e30)
    m_ref = jnp.max(sc, axis=-1).reshape(B, H)
    l_ref = jnp.sum(jnp.exp(sc - m_ref.reshape(B, KV, H // KV, 1)),
                    axis=-1).reshape(B, H)
    errs["decode_stats_m"] = err(m, m_ref)
    errs["decode_stats_l_rel"] = float(jnp.max(jnp.abs(l - l_ref) / l_ref))

    # the prefill kernel copies whole pages itself, which the chip's
    # compiler accepts for heads of 128 lanes only (llama._attention
    # sends a chunk over narrower heads, --model 1b's 64, to the XLA
    # arm): checked at 128 where the model's heads are narrower
    hdp = hd if hd % 128 == 0 or s.interpret else 128
    kq, kk, kv_ = jax.random.split(kp, 3)
    qp = jax.random.normal(kq, (Bp, T, H, hdp), dt)
    kl = jax.random.normal(kk, (N, KV, ps, hdp), dt)
    vl = jax.random.normal(kv_, (N, KV, ps, hdp), dt)
    starts = rng.randint(0, P * ps - T + 1, Bp)
    starts[0] = P * ps - T
    qpos = jnp.asarray(starts[:, None] + np.arange(T)[None, :], jnp.int32)
    want_p = _paged_attention(qp, kl, vl, table[:Bp], qpos, hdp ** -0.5)
    errs["prefill_flash"] = err(pa.paged_attention_prefill(
        qp, kl, vl, table[:Bp], qpos, scale=hdp ** -0.5,
        interpret=s.interpret), want_p)
    errs = {k: round(v, 5) for k, v in errs.items()}
    ok = all(np.isfinite(v) and v <= KERNEL_ATOL for v in errs.values())
    emit("kernels", ok=ok, atol=KERNEL_ATOL, max_abs_err=errs,
         shapes={"B": B, "H": H, "KV": KV, "hd": hd, "ps": ps, "P": P,
                 "L": L, "prefill_T": T, "prefill_hd": hdp,
                 "dtype": "bfloat16"})
    check(ok, f"a kernel left the {KERNEL_ATOL} bound: {errs}")


# ------------------------------------------------------------------- serve


async def _sse_chat(http, url: str, model: str, prompt: str, osl: int,
                    seed: int) -> dict:
    """One streaming chat completion; every token is asked to carry its
    logprob so each arrives as its own SSE chunk (random weights decode
    to almost no printable bytes, so text alone would show nothing)."""
    body = {"model": model, "stream": True, "max_tokens": osl,
            "messages": [{"role": "user", "content": prompt}],
            "temperature": 0.7, "seed": seed, "logprobs": True,
            "stream_options": {"include_usage": True},
            "ext": {"ignore_eos": True}}
    t0 = time.monotonic()
    row = {"chunks": 0, "tokens": 0, "ttft_s": None, "done": False,
           "finish": None, "usage": None, "status": None}
    async with http.post(url, json=body) as resp:
        row["status"] = resp.status
        if resp.status != 200:
            row["error"] = (await resp.text())[:300]
            return row
        async for raw in resp.content:
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            if line == b"data: [DONE]":
                row["done"] = True
                break
            chunk = json.loads(line[len(b"data: "):])
            row["chunks"] += 1
            if chunk.get("usage"):
                row["usage"] = chunk["usage"]
            for c in chunk.get("choices", []):
                n = len(((c.get("logprobs") or {}).get("content")) or [])
                if n and row["ttft_s"] is None:
                    row["ttft_s"] = time.monotonic() - t0
                row["tokens"] += n
                if c.get("finish_reason"):
                    row["finish"] = c["finish_reason"]
    row["e2e_s"] = time.monotonic() - t0
    return row


async def phase_serve(s: Settings, built, warm: dict) -> None:
    import aiohttp
    import jax
    import numpy as np

    from dynamo_tpu import run

    engine, mdc, _full = built
    port = _free_port()
    args = _run_args(s, "--http-port", str(port))
    base = f"http://127.0.0.1:{port}"
    server = asyncio.create_task(run.run_http(args, built=built))
    rng = np.random.RandomState(s.seed)
    lens = rng.randint(s.prompt_lens[0], s.prompt_lens[1] + 1, N_STREAM)
    lens[0], lens[1] = s.prompt_lens
    prompts = [_word_text(rng, int(n)) for n in lens]
    obs: dict = {"warmup_s": warm["warmup_s"], "compiles": warm["compiles"]}
    try:
        timeout = aiohttp.ClientTimeout(total=600)
        async with aiohttp.ClientSession(timeout=timeout) as http:
            for _ in range(200):
                check(not server.done(), "run_http ended before serving")
                try:
                    async with http.get(f"{base}/health") as r:
                        if r.status == 200:
                            break
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.05)
            else:
                raise PhaseFailed("frontend never answered /health")
            async with http.get(f"{base}/v1/models") as r:
                models = await r.json()
            names = [m["id"] for m in models.get("data", [])]
            check(mdc.name in names, f"/v1/models lists {names}")

            t0 = time.monotonic()
            rows = await asyncio.gather(*(
                _sse_chat(http, f"{base}/v1/chat/completions", mdc.name,
                          p, s.osl, s.seed + i)
                for i, p in enumerate(prompts)))
            wall = time.monotonic() - t0
            for i, r in enumerate(rows):
                check(r["status"] == 200, f"stream {i}: HTTP {r}")
                check(r["done"], f"stream {i}: no [DONE]: {r}")
                check(r["finish"] in ("length", "stop"),
                      f"stream {i}: finish_reason {r['finish']!r}")
                check(r["tokens"] == s.osl,
                      f"stream {i}: {r['tokens']} token chunks, not {s.osl}")
                u = r["usage"] or {}
                check(u.get("completion_tokens") == s.osl
                      and u.get("prompt_tokens", 0) >= int(lens[i]),
                      f"stream {i}: usage {u}")
            obs.update(
                streams=len(rows), prompt_chars=[int(n) for n in lens],
                ttft_s=[round(r["ttft_s"], 4) for r in rows],
                burst_wall_s=round(wall, 3),
                total_tok_per_s=round(
                    sum(r["tokens"] for r in rows) / wall, 1))

            # one plain non-streaming completion (the no-logprobs windows)
            async with http.post(f"{base}/v1/completions", json={
                    "model": mdc.name, "prompt": prompts[2],
                    "max_tokens": s.osl, "ext": {"ignore_eos": True},
                    "stream_options": {"include_usage": True}}) as r:
                check(r.status == 200, f"completion: HTTP {r.status}")
                comp = await r.json()
            ch = comp["choices"][0]
            check((comp.get("usage") or {}).get("completion_tokens")
                  == s.osl and ch["finish_reason"] == "length"
                  and isinstance(ch["text"], str),
                  f"completion malformed: {str(comp)[:300]}")

            # one unary chat with alternatives per token
            async with http.post(f"{base}/v1/chat/completions", json={
                    "model": mdc.name, "max_tokens": 8, "logprobs": True,
                    "top_logprobs": 5, "ext": {"ignore_eos": True},
                    "messages": [{"role": "user",
                                  "content": prompts[3]}]}) as r:
                check(r.status == 200, f"logprobs: HTTP {r.status}")
                lp = await r.json()
            content = lp["choices"][0]["logprobs"]["content"]
            check(len(content) == 8 and all(
                np.isfinite(e["logprob"]) and e["logprob"] <= 0.0
                and len(e["top_logprobs"]) == 5 for e in content),
                f"logprobs malformed: {str(content)[:300]}")

            async with http.get(f"{base}/metrics") as r:
                metrics = await r.text()
            line = [ln for ln in metrics.splitlines() if ln.startswith(
                "dyn_engine_post_warmup_compiles_total ")]
            check(line and float(line[0].split()[-1]) == 0,
                  f"/metrics post-warmup compiles: {line}")
        obs["requests_answered"] = len(rows) + 2
        obs["post_warmup_compiles"] = engine.fence.post_warmup_compiles

        # where things live, and what the decode window compiled to
        check(not os.environ.get("DYN_DISABLE_PALLAS"),
              "DYN_DISABLE_PALLAS is set: the kernel path is off")
        dev0 = jax.devices()[0]
        homes = {d for leaf in jax.tree.leaves(engine.params)
                 for d in leaf.devices()}
        homes |= engine.kv_k.devices() | engine.kv_v.devices()
        check(homes == {dev0}, f"params/KV pool on {homes}, not {dev0}")
        obs["params_kv_on"] = str(dev0)
        obs["window_has_tpu_custom_call"] = "tpu_custom_call" in (
            await asyncio.to_thread(_window_text, engine))
        check(s.interpret or obs["window_has_tpu_custom_call"],
              "no tpu_custom_call in the compiled decode window")
    finally:
        # the server's own shutdown path: SIGTERM → _wait_for_signal →
        # HttpService.stop()
        if not server.done():
            os.kill(os.getpid(), signal.SIGTERM)
        try:
            await asyncio.wait_for(server, 60)
            obs["server_exit"] = "clean"
        except Exception as e:  # noqa: BLE001 — reported, then judged
            obs["server_exit"] = f"{type(e).__name__}: {e}"
    with socket.socket() as probe:
        obs["port_closed"] = probe.connect_ex(("127.0.0.1", port)) != 0
    ok = obs["server_exit"] == "clean" and obs["port_closed"]
    emit("serve", ok=ok, **obs)
    check(ok, f"server did not shut down cleanly: {obs['server_exit']}")


def _window_args(engine, B: int, P: int):
    """The decode window's warm-up call form for one bucket."""
    import jax.numpy as jnp

    ecfg = engine.ecfg
    return (engine.params, jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32) - 1, jnp.zeros(B, bool),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.int32), engine.kv_k,
            engine.kv_v, jnp.zeros((B, P), jnp.int32), jnp.zeros(B),
            jnp.zeros(B, jnp.int32), jnp.ones(B),
            jnp.zeros(B, jnp.uint32),
            jnp.full((B, ecfg.max_eos_ids), -1, jnp.int32), None)


@contextlib.contextmanager
def _unfenced(engine):
    """Compiles the smoke makes for its own checks (an AOT lowering, the
    float32 reference) are not serving compiles: mask the fence."""
    was = engine.fence.armed
    engine.fence.disarm()
    try:
        with engine._on_device():
            yield
    finally:
        if was:
            engine.fence.arm()


def _window_text(engine, largest: bool = True) -> str:
    """Compiled text of the decode-window program for one warmed bucket
    (an AOT lowering of the call form warm-up uses: the serving jit
    cache is untouched)."""
    grid = engine.ecfg.warmed_grid()
    i = -1 if largest else 0
    B, P = grid["decode_batches"][i], grid["page_buckets"][i]
    with _unfenced(engine):
        return engine.decode_multi_fn.__wrapped__.lower(
            *_window_args(engine, B, P), k_steps=engine.ecfg.decode_steps,
            logprobs_topn=0).compile().as_text()


# ------------------------------------------------------------------- agree


async def _greedy(engine, prompt: List[int], n: int):
    """(token ids, per-token {id: logprob} top-20) straight from the
    engine's generate() — the path every request takes."""
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


def _reference_logprobs(engine, tokens: List[int], n_prompt: int):
    """float32, highest matmul precision, plain full attention: logprobs
    at the last prompt position and at every generated position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import reference_forward

    cfg32 = dataclasses.replace(engine.cfg, dtype="float32")

    @jax.jit
    def ref(params, toks):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        logits = reference_forward(p32, cfg32, toks)[0, n_prompt - 1:]
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    with jax.default_matmul_precision("highest"), _unfenced(engine):
        return np.asarray(ref(engine.params,
                              jnp.asarray([tokens], jnp.int32)))


def compare_with_reference(engine, prompt, toks, tops) -> dict:
    """Engine tokens/top-20 logprobs against the float32 reference run
    teacher-forced on the engine's own tokens."""
    import numpy as np

    n = len(toks)
    ref = _reference_logprobs(engine, list(prompt) + toks[:-1], len(prompt))
    max_diff, exact, near = 0.0, 0, 0
    for i in range(n):
        ids = np.fromiter(tops[i].keys(), int)
        vals = np.fromiter(tops[i].values(), float)
        max_diff = max(max_diff, float(np.max(np.abs(ref[i, ids] - vals))))
        best = int(np.argmax(ref[i]))
        if best == toks[i]:
            exact += 1
        elif ref[i, best] - ref[i, toks[i]] < AGREE_ATOL:
            near += 1
    return {"positions": n, "top1_equal": exact, "top1_near_tie": near,
            "max_abs_logprob_diff": round(max_diff, 5)}


async def phase_agree(s: Settings, engine) -> None:
    import numpy as np

    rng = np.random.RandomState(s.seed + 1)
    prompt = rng.randint(1, engine.cfg.vocab_size, 96).tolist()
    toks, tops = await _greedy(engine, prompt, 1 + AGREE_STEPS)
    check(len(toks) == 1 + AGREE_STEPS and len(tops) == len(toks),
          f"engine returned {len(toks)} tokens / {len(tops)} logprob rows")
    res = await asyncio.to_thread(compare_with_reference, engine, prompt,
                                  toks, tops)
    ok = (res["top1_equal"] + res["top1_near_tie"] == res["positions"]
          and res["max_abs_logprob_diff"] <= AGREE_ATOL)
    emit("agree", ok=ok, atol=AGREE_ATOL, prompt_tokens=len(prompt),
         reference="llama.reference_forward float32, matmul precision "
                   "highest, teacher-forced on the engine's tokens",
         **res)
    check(ok, f"engine and float32 reference disagree: {res}")


# ------------------------------------------------------------------- cache


def phase_cache(cache_dir: str) -> None:
    n = sum(len(files) for _, _, files in os.walk(cache_dir))
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    emit("cache", ok=n > 0, dir=cache_dir, entries=n,
         placed_by="JAX_COMPILATION_CACHE_DIR" if from_env else "default")
    check(n > 0, f"compile cache {cache_dir} is empty after warm-up")


# ---------------------------------------------------------- one-chip driver


def build_and_warm(s: Settings, *extra: str):
    """run.build_engine with warm-up on, timed; returns (built, obs)."""
    from dynamo_tpu import run

    args = _run_args(s, "--no-warmup", *extra)
    built = run.build_engine(args)
    t0 = time.monotonic()
    compiles = built[0].warmup()
    return built, {"warmup_s": round(time.monotonic() - t0, 2),
                   "compiles": compiles}


async def one_chip(s: Settings, cache_dir: str) -> None:
    phase_kernels(s)
    built, warm = await asyncio.to_thread(build_and_warm, s)
    engine = built[0]
    try:
        await phase_serve(s, built, warm)
        await phase_agree(s, engine)
    finally:
        await engine.stop()
    phase_cache(cache_dir)


# --------------------------------------------------------- four-chip phase


async def _routed_leg(s: Settings, tag: str, replicas: int, cfg, ecfg,
                      prompts: List[str]) -> dict:
    """``replicas`` one-chip engines behind the real HTTP frontend +
    Processor + KV router, in this process; 16 greedy completions."""
    import aiohttp
    import jax

    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.processor import Processor
    from dynamo_tpu.parallel.serving import ShardedReplicaSet
    from dynamo_tpu.runtime.runtime import DistributedRuntime

    mdc = ModelDeploymentCard(name="smoke", tokenizer_kind="byte",
                              kv_block_size=ecfg.page_size,
                              model_type="completions")
    drt = await DistributedRuntime.detached()
    rs = service = kvr = client = None
    try:
        t0 = time.monotonic()
        rs = ShardedReplicaSet(cfg, ecfg, mesh_axes={}, replicas=replicas,
                               namespace="smoke", component=tag, mdc=mdc,
                               dcp_address=drt.dcp.address, seed=s.seed)
        await rs.start()
        warm_s = time.monotonic() - t0
        kvr = KvRouter(drt, "smoke", tag, block_size=ecfg.page_size,
                       seed=s.seed)
        await kvr.start(run_loop=False)
        await kvr.scrape_once()
        client = await drt.namespace("smoke").component(tag) \
            .endpoint("generate_tokens").client()
        service = HttpService()
        service.manager.add_completions_model(
            "smoke", Processor(mdc, client, kvr).completion)
        await service.start(host="127.0.0.1", port=0)
        url = f"http://127.0.0.1:{service.port}/v1/completions"

        async def one(http, prompt):
            async with http.post(url, json={
                    "model": "smoke", "prompt": prompt, "logprobs": 0,
                    "max_tokens": s.osl, "temperature": 0.0,
                    "ext": {"ignore_eos": True}}) as r:
                check(r.status == 200, f"{tag}: HTTP {r.status}: "
                      f"{(await r.text())[:200]}")
                ch = (await r.json())["choices"][0]
            return {"text": ch["text"], "finish": ch["finish_reason"],
                    "logprobs": ch["logprobs"]["token_logprobs"]}

        before = {r.name: r.engine.prompt_tokens_total
                  for r in rs.replicas}
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=600)) as http:
            outs = await asyncio.gather(*(one(http, p) for p in prompts))
        homes = {}
        for r in rs.replicas:
            devs = {d for leaf in jax.tree.leaves(r.engine.params)
                    for d in leaf.devices()}
            devs |= r.engine.kv_k.devices() | r.engine.kv_v.devices()
            check(devs == {r.spec.devices[0]},
                  f"replica {r.name} holds arrays on {devs}")
            homes[r.name] = r.spec.devices[0].id
        return {"outs": outs, "device_ids": homes,
                "warm_s": round(warm_s, 1),
                "served_prompt_tokens": {
                    r.name: r.engine.prompt_tokens_total - before[r.name]
                    for r in rs.replicas},
                "post_warmup_compiles": rs.post_warmup_compiles()}
    finally:
        if service is not None:
            await service.stop()
        if kvr is not None:
            await kvr.stop()
        if client is not None:
            await client.close()
        if rs is not None:
            await rs.stop()
        await drt.shutdown()


async def phase_replicas(s: Settings) -> None:
    import numpy as np

    from dynamo_tpu import run

    cfg, ecfg, _params, _quant, _mesh = run._jax_engine_setup(_run_args(s))
    if s.trim_grid_4chip:
        # four warm-ups in a row, each cold (the device assignment is in
        # the compile-cache key), at four chips' price per second: keep
        # the widths, warm one bucket of each kind instead of the grid
        ecfg = dataclasses.replace(
            ecfg, max_batch=16, batch_buckets=(16,), page_buckets=(16,),
            prefill_buckets=(64, 512))
    rng = np.random.RandomState(s.seed)
    prompts = [_word_text(rng, int(n)) for n in rng.randint(
        s.prompt_lens[0], s.prompt_lens[1] + 1, N_ROUTED)]
    four = await _routed_leg(s, "dp4", 4, cfg, ecfg, prompts)
    one = await _routed_leg(s, "dp1", 1, cfg, ecfg, prompts)
    same = [a == b for a, b in zip(four["outs"], one["outs"])]
    n_tok = [len(o["logprobs"]) for o in four["outs"]]
    served = four["served_prompt_tokens"]
    ok = (all(same) and all(n == s.osl for n in n_tok)
          and len(set(four["device_ids"].values())) == 4
          and all(v > 0 for v in served.values())
          and not any(four["post_warmup_compiles"].values())
          and not any(one["post_warmup_compiles"].values()))
    emit("replicas", ok=ok, requests=len(prompts),
         identical_to_one_replica=sum(same),
         compared="text, finish_reason and the per-token logprob "
                  "sequence of every greedy completion, bit for bit",
         device_ids=four["device_ids"], served_prompt_tokens=served,
         post_warmup_compiles=four["post_warmup_compiles"],
         one_replica_post_warmup_compiles=one["post_warmup_compiles"],
         warm_s={"four_replicas": four["warm_s"],
                 "one_replica_after": one["warm_s"]},
         warmed_grid=ecfg.warmed_grid())
    check(ok, "four one-chip replicas did not match one replica")


async def phase_tp(s: Settings) -> None:
    """One model=4 engine against the one-chip engine: same seeded
    weights, same prompt; first-token logprobs within AGREE_ATOL and the
    length of the matching greedy prefix."""
    import jax
    import numpy as np

    from dynamo_tpu import run
    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.parallel.mesh import MeshSpec

    cfg, ecfg, _p, _q, _m = run._jax_engine_setup(_run_args(s))
    rng = np.random.RandomState(s.seed + 2)
    prompt = rng.randint(1, cfg.vocab_size, 96).tolist()
    n = 32
    # no warm-up on either side: each compiles the one prefill and the
    # one window this prompt reaches, nothing else (four chips cost four
    # times a second)
    results = {}
    text = ""
    for tag, mesh in (("one_chip", None),
                      ("model4", MeshSpec(model=s.tp).build(
                          jax.devices()[:s.tp]))):
        eng = await asyncio.to_thread(
            lambda: JaxEngine(cfg, ecfg, seed=s.seed, mesh=mesh))
        try:
            results[tag] = await _greedy(eng, prompt, n)
            if mesh is not None:
                text = await asyncio.to_thread(_window_text, eng, False)
                shards = {len(leaf.sharding.device_set)
                          for leaf in jax.tree.leaves(eng.params)}
        finally:
            await eng.stop()
    (t1, l1), (t4, l4) = results["one_chip"], results["model4"]
    prefix = next((i for i, (a, b) in enumerate(zip(t1, t4)) if a != b),
                  min(len(t1), len(t4)))
    ids = list(l1[0].keys())
    diff = max(abs(l1[0][i] - l4[0].get(i, float("-inf"))) for i in ids
               if i in l4[0])
    overlap = sum(1 for i in ids if i in l4[0])
    has_kernel = "tpu_custom_call" in text
    has_coll = any(c in text for c in ("all-reduce", "all-gather",
                                       "reduce-scatter",
                                       "collective-permute"))
    ok = (t1[0] == t4[0] and diff <= AGREE_ATOL and overlap >= 15
          and (s.interpret or has_kernel) and has_coll
          and shards == {s.tp})
    emit("model4", ok=ok, atol=AGREE_ATOL,
         first_token_equal=t1[0] == t4[0],
         first_token_max_abs_logprob_diff=round(float(diff), 5),
         top20_ids_shared=overlap, greedy_prefix_equal=prefix, of=n,
         window_has_tpu_custom_call=has_kernel,
         window_has_collective=has_coll,
         param_shard_device_counts=sorted(shards))
    check(ok, "the model=4 engine does not agree with the one-chip engine")


async def four_chips(s: Settings) -> None:
    await phase_replicas(s)
    await phase_tp(s)


# -------------------------------------------------------------------- main


def run_smoke(s: Settings) -> int:
    """Every phase in order; 0 and the contract's last line only when
    all of them passed."""
    os.environ.setdefault("DYN_JIT_FENCE", "raise")
    phase = "device"
    try:
        from dynamo_tpu.runtime.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        dev = phase_device(s)
        phase = "run"
        asyncio.run(four_chips(s) if s.chips == 4
                    else one_chip(s, cache_dir))
    except PhaseFailed as e:
        emit("failed", ok=False, error=str(e))
        return 1
    except Exception as e:  # noqa: BLE001 — any crash is a failed smoke
        import traceback

        traceback.print_exc()
        emit("failed", ok=False, after=phase,
             error=f"{type(e).__name__}: {e}"[:500])
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = only the cross-chip phase (builder-run)")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    return run_smoke(Settings(chips=a.chips, seed=a.seed))


if __name__ == "__main__":
    sys.exit(main())
