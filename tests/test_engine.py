"""Engine tests: page manager prefix caching/eviction/events, and the JAX
engine end-to-end — continuous batching, prefix reuse, cancellation,
preemption, and the full HTTP-chain integration."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.kv_manager import PageManager, chain_hashes, hash_block
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context


def test_chain_hashes_deterministic_and_chained():
    ids = list(range(32))
    h1 = chain_hashes(ids, 16)
    h2 = chain_hashes(ids, 16)
    assert h1 == h2 and len(h1) == 2
    # chaining: second block hash depends on the first
    other = chain_hashes([1] + ids[1:], 16)
    assert other[0] != h1[0] and other[1] != h1[1]
    assert hash_block(0, ids[:16]) == h1[0]


def test_page_manager_prefix_reuse_and_eviction():
    pm = PageManager(num_pages=8, page_size=4)  # 7 usable pages
    prompt = list(range(12))  # 3 blocks
    alloc = pm.allocate_sequence(prompt)
    assert alloc is not None
    pages, cached = alloc
    assert len(pages) == 3 and cached == 0
    # commit the full blocks (as prefill does)
    hashes = chain_hashes(prompt, 4)
    for i, h in enumerate(hashes):
        pm.commit(pages[i], h, parent_hash=hashes[i - 1] if i else None)
    stored = pm.drain_events()
    assert [e.kind for e in stored] == ["stored"] * 3

    # same prompt again: full prefix reuse (capped to leave the tail block)
    alloc2 = pm.allocate_sequence(prompt)
    pages2, cached2 = alloc2
    assert cached2 == 8  # 2 blocks reused; last block recomputed
    assert pages2[:2] == pages[:2]

    pm.release_sequence(pages)
    pm.release_sequence(pages2)
    # all pages now reusable; allocating 7 fresh pages must evict some and
    # emit removed events
    big = pm.allocate_sequence(list(range(100, 128)))  # 7 blocks
    assert big is not None
    removed = [e for e in pm.drain_events() if e.kind == "removed"]
    assert removed  # evictions happened
    assert pm.available == 0


def test_page_manager_oom_returns_none():
    pm = PageManager(num_pages=4, page_size=4)
    a = pm.allocate_sequence(list(range(12)))  # uses all 3 usable pages
    assert a is not None
    assert pm.allocate_sequence(list(range(100, 104))) is None
    assert pm.allocate_page() is None
    pm.release_sequence(a[0])
    assert pm.allocate_page() is not None


def mk_engine(**eng_kw):
    cfg = ModelConfig.tiny()
    defaults = dict(page_size=8, num_pages=64, max_batch=8, prefill_chunk=32)
    defaults.update(eng_kw)
    return JaxEngine(cfg, EngineConfig(**defaults), seed=0)


def mk_request(tokens, max_tokens=8, **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens),
        sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=max_tokens),
        eos_token_ids=[258])


async def collect(engine, req, ctx=None):
    ctx = ctx or Context()
    toks, finish = [], None
    async for out in engine.generate(req, ctx):
        toks.extend(out.token_ids)
        if out.finish_reason:
            finish = out.finish_reason
            break
    return toks, finish


def test_engine_generates_deterministically(run_async):
    async def main():
        engine = mk_engine()
        req = mk_request(range(10, 30), max_tokens=6)
        toks1, fin1 = await collect(engine, req)
        assert len(toks1) == 6 and fin1 == "length"
        # greedy → identical rerun (and exercises prefix cache reuse)
        toks2, fin2 = await collect(engine, mk_request(range(10, 30),
                                                       max_tokens=6))
        assert toks2 == toks1
        assert engine.prefix_hit_tokens_total > 0  # second run hit the cache
        stats = engine.stats()
        assert stats["request_active_slots"] == 0
        assert stats["kv_active_blocks"] == 0  # everything released
        await engine.stop()

    run_async(main())


def test_engine_concurrent_requests(run_async):
    """Continuous batching: concurrent requests with different lengths and
    sampling all complete; distinct prompts give distinct outputs."""

    async def main():
        engine = mk_engine()
        reqs = [mk_request(range(i * 7 + 1, i * 7 + 12 + i), max_tokens=4 + i)
                for i in range(5)]
        results = await asyncio.gather(*(collect(engine, r) for r in reqs))
        for i, (toks, fin) in enumerate(results):
            assert len(toks) == 4 + i, f"req {i}: {toks}"
            assert fin == "length"
        await engine.stop()

    run_async(main())


def test_engine_cancellation_frees_pages(run_async):
    async def main():
        engine = mk_engine()
        ctx = Context()
        req = mk_request(range(20), max_tokens=10_000)

        async def consume():
            count = 0
            async for out in engine.generate(req, ctx):
                count += len(out.token_ids)
                if count >= 3:
                    ctx.stop_generating()
                if out.finish_reason:
                    return out.finish_reason
            return None

        fin = await asyncio.wait_for(consume(), 30)
        assert fin == "cancelled"
        await asyncio.sleep(0.05)
        assert engine.stats()["kv_active_blocks"] == 0
        await engine.stop()

    run_async(main())


def test_engine_preemption_under_memory_pressure(run_async):
    """More concurrent work than the page pool can hold: preemption +
    re-admission must still complete every request."""

    async def main():
        # 15 usable pages of 8 tokens; 4 requests × (16-token prompt +
        # 16 generated) ≈ 16 pages → forced preemption
        engine = mk_engine(num_pages=16, max_batch=4, watermark_pages=1)
        reqs = [mk_request(range(i * 16, i * 16 + 16), max_tokens=16)
                for i in range(4)]
        results = await asyncio.wait_for(
            asyncio.gather(*(collect(engine, r) for r in reqs)), 120)
        for toks, fin in results:
            assert len(toks) == 16 and fin == "length"
        assert engine.stats()["kv_active_blocks"] == 0
        await engine.stop()

    run_async(main())


def test_engine_behind_full_llm_chain(run_async):
    """JaxEngine behind Backend + preprocessor + HTTP service: the complete
    aggregated serving slice (SURVEY §7 step 3) on CPU."""

    async def main():
        import aiohttp

        from dynamo_tpu.llm.engines import LocalChatChain
        from dynamo_tpu.llm.http.service import HttpService
        from dynamo_tpu.llm.model_card import ModelDeploymentCard

        engine = mk_engine()
        mdc = ModelDeploymentCard(name="tiny-jax", tokenizer_kind="byte",
                                  context_length=256)
        service = HttpService()
        service.manager.add_chat_model("tiny-jax",
                                       LocalChatChain(mdc, engine))
        await service.start(host="127.0.0.1", port=0)
        async with aiohttp.ClientSession() as http:
            body = {"model": "tiny-jax", "stream": False, "max_tokens": 8,
                    "messages": [{"role": "user", "content": "hello"}]}
            async with http.post(
                    f"http://127.0.0.1:{service.port}/v1/chat/completions",
                    json=body) as r:
                assert r.status == 200, await r.text()
                data = await r.json()
        assert data["choices"][0]["finish_reason"] == "length"
        await service.stop()
        await engine.stop()

    run_async(main())


@pytest.mark.parametrize("rng_seed,lens,k,n,concurrent", [
    (7, (9, 21), 4, 11, False), (3, (7, 18, 33), 3, 9, True),
    (5, (2, 6, 14), 4, 11, True)],
    ids=["sequential", "concurrent", "page-straddle"])
def test_multi_step_decode_matches_single_step(run_async, rng_seed, lens, k,
                                               n, concurrent):
    """The fused K-step decode window must produce exactly the same
    tokens as K single steps: a greedy and a seeded row one after the
    other (K 4), three seeded rows sharing pipelined windows (K 3: the
    device carry is exact, not speculative), and three rows whose prompts
    end two tokens short of a page (length ps - 2 mod ps), so that every
    row's first window commits on both sides of a page boundary."""
    def sampling(i):
        if concurrent:
            return SamplingOptions(temperature=0.7, top_k=12, seed=100 + i)
        return (SamplingOptions() if i == 0 else
                SamplingOptions(temperature=0.8, top_k=20, seed=42))

    cfg = ModelConfig.tiny()
    rng = np.random.RandomState(rng_seed)
    prompts = [rng.randint(1, 500, m).tolist() for m in lens]

    async def gen_all(engine):
        async def one(i, p):
            req = PreprocessedRequest(
                token_ids=p, sampling=sampling(i),
                stop=StopConditions(max_tokens=n, ignore_eos=True),
                eos_token_ids=[])
            toks = []
            async for out in engine.generate(req, Context()):
                toks.extend(out.token_ids)
                if out.finish_reason:
                    break
            return toks
        if concurrent:
            outs = list(await asyncio.gather(
                *(one(i, p) for i, p in enumerate(prompts))))
        else:
            outs = [await one(i, p) for i, p in enumerate(prompts)]
        await engine.stop()
        return outs

    results = {}
    for steps in (1, k):
        ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                            prefill_chunk=32, prefill_buckets=(32,),
                            batch_buckets=(4,), page_buckets=(16,),
                            decode_steps=steps)
        results[steps] = run_async(gen_all(JaxEngine(cfg, ecfg, seed=0)))

    assert results[1] == results[k]
    assert all(len(t) == n for t in results[k])


def test_on_device_eos_stops_mid_window(run_async):
    """On-device stop masking: pick a token the greedy run emits mid-window
    and declare it EOS on a second run — generation must stop right after
    emitting it, with no trailing tokens from the rest of the window (the
    device freezes the row; the host discards nothing it shouldn't)."""
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    prompt = list(range(40, 60))

    async def gen(engine, eos_ids, n):
        req = PreprocessedRequest(
            token_ids=prompt, sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=n), eos_token_ids=eos_ids)
        toks, fin = [], None
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                fin = out.finish_reason
                break
        await engine.stop()
        return toks, fin

    free, fin1 = run_async(gen(JaxEngine(cfg, ecfg, seed=0), [], 12))
    assert fin1 == "length" and len(free) == 12
    # make the 6th greedy token (lands mid-window for K=4) the stop token
    eos = free[5]
    cut = free[: free.index(eos) + 1]
    got, fin2 = run_async(gen(JaxEngine(cfg, ecfg, seed=0), [eos], 12))
    assert fin2 == "eos"
    assert got == cut


def test_prefill_token_budget_mixing(run_async):
    """Budgeted chunked-prefill mixing: tokens identical to pure
    prefill-priority, and decode windows demonstrably dispatch while a
    prompt backlog is still prefilling (the decode-starvation fix)."""
    cfg = ModelConfig.tiny()
    rng = np.random.RandomState(7)
    # a running request first, then a burst of long prompts to create a
    # prefill backlog that pure priority would drain before any decode
    first = rng.randint(1, 500, 9).tolist()
    burst = [rng.randint(1, 500, 60).tolist() for _ in range(4)]

    async def gen_all(engine):
        async def one(p, i, delay=0.0):
            if delay:
                await asyncio.sleep(delay)
            req = PreprocessedRequest(
                token_ids=p,
                sampling=SamplingOptions(temperature=0.6, top_k=8,
                                         seed=200 + i),
                stop=StopConditions(max_tokens=12, ignore_eos=True),
                eos_token_ids=[])
            toks = []
            async for out in engine.generate(req, Context()):
                toks.extend(out.token_ids)
                if out.finish_reason:
                    break
            return toks
        outs = await asyncio.gather(
            one(first, 0),
            *(one(p, i + 1, delay=0.05) for i, p in enumerate(burst)))
        await engine.stop()
        return outs

    results = {}
    mixed = {}
    for budget in (None, 32):
        ecfg = EngineConfig(page_size=4, num_pages=128, max_batch=8,
                            prefill_chunk=32, prefill_buckets=(32,),
                            batch_buckets=(8,), page_buckets=(16,),
                            decode_steps=3, prefill_token_budget=budget)
        eng = JaxEngine(cfg, ecfg, seed=0)
        results[budget] = run_async(gen_all(eng))
        mixed[budget] = eng.mixed_dispatches

    assert results[None] == results[32], "budgeted mixing changed tokens"
    assert mixed[32] > 0, "no decode window overlapped the prefill backlog"
    assert mixed[None] == 0  # pure priority never mixes


def test_admission_clamped_to_warmed_grid(run_async):
    """No mid-serving compile: prompts beyond the largest page bucket are
    rejected at admission, and generation is cut at the grid capacity
    instead of growing the page table past the warmed bucket."""
    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(8,),
                        decode_steps=4)

    async def main():
        engine = JaxEngine(cfg, ecfg, seed=0)
        assert engine.cap_tokens == 32
        # over-capacity prompt → error finish, no pages leaked
        req = PreprocessedRequest(
            token_ids=list(range(1, 41)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=4), eos_token_ids=[])
        fin = None
        async for out in engine.generate(req, Context()):
            if out.finish_reason:
                fin = out.finish_reason
                break
        assert fin == "error"
        # near-capacity prompt: generation cut at cap_tokens, not max_tokens
        req2 = PreprocessedRequest(
            token_ids=list(range(1, 29)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=50, ignore_eos=True),
            eos_token_ids=[])
        toks, fin2 = [], None
        async for out in engine.generate(req2, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                fin2 = out.finish_reason
                break
        assert fin2 == "length"
        assert len(toks) == 32 - 28
        assert engine.pm.active == 0
        await engine.stop()

    run_async(main())


def test_prefill_pallas_flag_token_identity(run_async, monkeypatch):
    """DYN_PALLAS_INTERPRET routes chunked prefill through the paged
    prefill kernel (interpret mode on the CPU, what a TPU backend runs
    by default): served tokens must be identical to the XLA gather
    path: the kernel-in-engine integration, not just the kernel math."""
    prompt = list(range(40, 40 + 21))

    def run(flagged):
        if flagged:
            monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("DYN_PALLAS_INTERPRET", raising=False)
        engine = mk_engine(page_size=4, num_pages=32, prefill_chunk=16)

        async def gen():
            toks, fin = await collect(
                engine, mk_request(prompt, max_tokens=6))
            await engine.stop()
            return toks, fin

        return run_async(gen())

    want = run(False)
    got = run(True)
    assert got == want
