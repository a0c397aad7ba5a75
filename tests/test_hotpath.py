"""dynaturbo hot-path tests (ISSUE 16): token identity across every
scheduler arm with the hot-path optimizations on vs off, zero post-warmup
compiles under default AND exotic warmed_grid configs, async-detok
ordering/cancellation, the cost_diff evidence tool, and the CPU hotpath
bench smoke so the evidence pipeline itself can't silently rot."""

import asyncio
import json

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.protocols.common import (EngineOutput,
                                             PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context

LEGACY = dict(overlap_idle_prefill=False, coalesce_window_emissions=False,
              cache_sampler_params=False, admit_in_step=False)


def _ecfg(**kw):
    base = dict(page_size=4, num_pages=64, max_batch=4, prefill_chunk=32,
                prefill_buckets=(32,), batch_buckets=(4,),
                page_buckets=(16,))
    base.update(kw)
    return EngineConfig(**base)


def _req(tokens, mt=10, eos=(), **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=mt, ignore_eos=not eos),
        eos_token_ids=list(eos))


async def _collect(engine, req):
    toks, fin = [], None
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        if out.finish_reason:
            fin = out.finish_reason
            break
    return toks, fin


def _mixed_requests():
    """greedy, penalties, logit_bias, and a seeded sampled row — the
    pinned token-identity surface (unseeded sampling is exempt by
    design: the sampler-param cache freezes its build-time reseeds)."""
    rng = np.random.RandomState(11)
    p = [rng.randint(1, 400, n).tolist() for n in (8, 15, 22, 30)]
    return [
        _req(p[0]),
        _req(p[1], repetition_penalty=1.3, frequency_penalty=0.4),
        _req(p[2], logit_bias={7: -100.0, 19: 4.0}),
        _req(p[3], temperature=0.8, top_k=16, seed=123),
    ]


@pytest.mark.parametrize("arm", ["single", "windowed", "pipelined"])
def test_token_identity_optimizations_on_off(run_async, arm):
    """Every scheduler arm must emit bit-identical tokens with the
    dynaturbo optimizations on (defaults) and off (legacy)."""
    arm_kw = {"single": dict(decode_steps=1),
              "windowed": dict(decode_steps=4, pipeline_decode=False),
              "pipelined": dict(decode_steps=4, pipeline_decode=True)}[arm]
    cfg = ModelConfig.tiny()

    async def gen_all(engine):
        outs = await asyncio.gather(
            *(_collect(engine, r) for r in _mixed_requests()))
        await engine.stop()
        return outs

    results = {}
    for name, toggles in (("legacy", LEGACY), ("new", {})):
        eng = JaxEngine(cfg, _ecfg(**arm_kw, **toggles), seed=0)
        results[name] = run_async(gen_all(eng))
    assert results["legacy"] == results["new"]
    assert all(len(t) == 10 and f == "length"
               for t, f in results["new"])


def test_token_identity_spec_arm(run_async):
    """Spec-decode arm: same identity contract (admission moved into the
    step; the spec step itself is untouched)."""
    cfg = ModelConfig.tiny()
    prompt = [5, 6, 7, 5, 6, 7, 5, 6] * 3  # spec-friendly motif

    async def gen(engine):
        out = await _collect(engine, _req(prompt, mt=12))
        await engine.stop()
        return out

    results = {}
    for name, toggles in (("legacy", LEGACY), ("new", {})):
        eng = JaxEngine(cfg, _ecfg(page_size=8, spec_decode=True,
                                   spec_tokens=2, decode_steps=2,
                                   **toggles), seed=0)
        results[name] = run_async(gen(eng))
    assert results["legacy"] == results["new"]
    assert len(results["new"][0]) == 12


def test_stop_string_identity_through_backend(run_async):
    """e2e stop-string arm: Backend + real engine. A stop string cut from
    the free-running text must truncate identically (text and finish
    reason) with the optimizations on and off."""
    cfg = ModelConfig.tiny()
    tok = ByteTokenizer()

    async def gen(toggles, stop):
        eng = JaxEngine(cfg, _ecfg(decode_steps=4, **toggles), seed=0)
        be = Backend(eng, tok)
        req = PreprocessedRequest(
            token_ids=list(range(60, 80)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=16, ignore_eos=True,
                                stop=stop),
            eos_token_ids=[])
        text, fin = "", None
        async for out in be.generate(req, Context()):
            text += out.text or ""
            if out.finish_reason:
                fin = out.finish_reason
                break
        await eng.stop()
        return text, fin

    free, fin = run_async(gen({}, None))
    assert fin == "length" and len(free) > 4
    needle = free[2:5]
    a = run_async(gen(LEGACY, [needle]))
    b = run_async(gen({}, [needle]))
    assert a == b
    assert b[1] == "stop" and needle not in b[0]


def _run_fence_grid(run_async, name, ecfg):
    """post_warmup_compiles must stay 0 while serving a mixed workload on
    the given warmed grid — the warmed_grid() enumeration must cover the
    coalesced window's emitted-counts output too."""
    cfg = ModelConfig.tiny()
    eng = JaxEngine(cfg, ecfg, seed=0)
    eng.warmup()
    assert eng.fence.armed

    async def main():
        # no penalty rows here: the penalized window variant is
        # deliberately NOT warmed (a first penalty request pays one
        # compile per bucket, by documented contract)
        reqs = [_req(list(range(1, 20)), mt=9),
                _req(list(range(30, 64)), mt=7),
                _req([9, 9, 9, 9, 9, 9], mt=6,
                     temperature=0.9, seed=3)]
        outs = await asyncio.gather(*(_collect(eng, r) for r in reqs))
        await eng.stop()
        return outs

    outs = run_async(main())
    assert all(len(t) >= 6 for t, _ in outs)
    assert eng.stats()["post_warmup_compiles_total"] == 0, (
        f"{name} grid compiled mid-serving")
    eng.fence.disarm()


def test_fence_zero_default_grid(run_async):
    _run_fence_grid(run_async, "default", _ecfg(decode_steps=4))


@pytest.mark.slow
def test_fence_zero_exotic_grid(run_async):
    """Exotic grid: prefill_chunk above the largest prefill bucket,
    max_batch off the bucket list, odd window length."""
    _run_fence_grid(run_async, "exotic", EngineConfig(
        page_size=4, num_pages=64, max_batch=3, prefill_chunk=48,
        prefill_buckets=(16, 32), batch_buckets=(1, 2),
        page_buckets=(8, 16), max_prefill_batch=2, decode_steps=5))


class _ChunkEngine:
    """Fake engine: yields pre-cut token chunks with tiny await points, so
    Backend chunk handling interleaves across concurrent streams."""

    def __init__(self, chunks):
        self.chunks = chunks

    async def generate(self, request, context):
        for c in self.chunks:
            await asyncio.sleep(0)
            yield EngineOutput(token_ids=list(c))
        yield EngineOutput(token_ids=[], finish_reason="length")


def test_async_detok_ordering_under_concurrency(run_async):
    """DYN_ASYNC_DETOK (default on): per-request chunk texts must come
    back in chunk order and concatenate to exactly the inline decode of
    the same ids, across many concurrent streams."""
    tok = ByteTokenizer()
    texts = [f"stream-{i}: héllo wörld →🌍 {'x' * i}" for i in range(6)]

    async def one(text):
        ids = tok.encode(text, add_special_tokens=False)
        chunks = [ids[j:j + 3] for j in range(0, len(ids), 3)]
        be = Backend(_ChunkEngine(chunks), tok)
        req = PreprocessedRequest(
            token_ids=[1], sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=len(ids) + 1, ignore_eos=True),
            eos_token_ids=[])
        parts = []
        async for out in be.generate(req, Context()):
            if out.text:
                parts.append(out.text)
            if out.finish_reason:
                break
        return parts

    async def main():
        return await asyncio.gather(*(one(t) for t in texts))

    all_parts = run_async(main())
    for text, parts in zip(texts, all_parts):
        assert "".join(parts) == text
        assert "�" not in "".join(parts)


def test_async_detok_cancellation_isolated(run_async):
    """Cancelling one stream mid-decode must not corrupt or stall a
    concurrent stream sharing the detok executor."""
    tok = ByteTokenizer()
    text = "the quick brown fox jumps over the lazy dog " * 4

    async def victim(started=None):
        ids = tok.encode(text, add_special_tokens=False)
        be = Backend(_ChunkEngine([ids[j:j + 2]
                                   for j in range(0, len(ids), 2)]), tok)
        req = PreprocessedRequest(
            token_ids=[1], sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=len(ids) + 1, ignore_eos=True),
            eos_token_ids=[])
        got = ""
        async for out in be.generate(req, Context()):
            got += out.text or ""
            if started is not None:
                started.set()
            await asyncio.sleep(0)  # cancellation window
            if out.finish_reason:
                break
        return got

    async def main():
        # cancel on t1's first chunk, not after a fixed sleep: on a fast
        # machine all 88 chunks were through before 10 ms had passed
        started = asyncio.Event()
        t1 = asyncio.ensure_future(victim(started))
        t2 = asyncio.ensure_future(victim())
        await started.wait()
        t1.cancel()
        survivor = await t2
        with pytest.raises(asyncio.CancelledError):
            await t1
        return survivor

    assert run_async(main()) == text


def _bench_record(disp, dev, extra_bucket=None, **headline):
    buckets = {"decode_window:4x16x4": {
        "samples": 10, "dispatch_us": disp, "device_us": dev,
        "tokens_per_s": 1000.0}}
    if extra_bucket:
        buckets[extra_bucket] = {"samples": 2, "dispatch_us": 5.0,
                                 "device_us": 1.0, "tokens_per_s": 0.0}
    detail = {"bucket_cost": buckets, "itl_raw_chunk_p99_ms": 10.0,
              "loop_lag_p99_ms": 2.0, "post_warmup_compiles": 0}
    detail.update(headline)
    return {"metric": "m", "value": 1.0, "unit": "ms", "detail": detail}


def test_cost_diff_tool(tmp_path, capsys):
    from tools import cost_diff

    before = _bench_record(100.0, 50.0, itl_raw_chunk_p99_ms=12.0)
    after = _bench_record(60.0, 50.0, extra_bucket="admit:host",
                          itl_raw_chunk_p99_ms=9.0)
    diff = cost_diff.diff_reports(before, after)
    by_bucket = {r["bucket"]: r for r in diff["buckets"]}
    assert by_bucket["decode_window:4x16x4"]["dispatch_us_delta"] == -40.0
    assert by_bucket["decode_window:4x16x4"]["device_us_delta"] == 0.0
    # one-sided bucket: missing side stays None, no crash
    assert by_bucket["admit:host"]["dispatch_us_before"] is None
    assert by_bucket["admit:host"]["dispatch_us_delta"] is None
    assert diff["headline"]["itl_raw_chunk_p99_ms"]["delta"] == -3.0

    bf, af = tmp_path / "b.json", tmp_path / "a.json"
    bf.write_text(json.dumps(before))
    af.write_text(json.dumps(after))
    assert cost_diff.main([str(bf), str(af)]) == 0
    out = capsys.readouterr().out
    assert "decode_window:4x16x4" in out and "-40.0" in out
    assert cost_diff.main(["--json", str(bf), str(af)]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["headline"]["itl_raw_chunk_p99_ms"]["after"] == 9.0
    # reports without a cost table are a hard error, not an empty diff
    nf = tmp_path / "n.json"
    nf.write_text(json.dumps({"metric": "m", "detail": {}}))
    assert cost_diff.main([str(nf), str(nf)]) == 1


def test_hotpath_scenario_cpu_smoke():
    """CI smoke for the evidence pipeline: the CPU hotpath scenario must
    produce ONE record with a non-empty per-bucket cost table,
    post_warmup_compiles == 0, and itl_raw_chunk_p99_ms present."""
    import sys

    import bench

    argv = sys.argv
    sys.argv = ["bench.py", "--cpu", "--model", "tiny",
                "--scenario", "hotpath", "--requests", "4",
                "--concurrency", "2", "--isl", "48", "--osl", "24",
                "--decode-steps", "4"]
    try:
        args = bench.parse_args()
    finally:
        sys.argv = argv
    record = bench._run_scenario(args)
    detail = record["detail"]
    assert record["unit"] == "ms"
    assert isinstance(record["value"], (int, float))
    assert detail["bucket_cost"], "cost table empty — --prof-sample rot"
    assert any(k.startswith("decode_window:")
               for k in detail["bucket_cost"])
    assert detail["post_warmup_compiles"] == 0
    assert "itl_raw_chunk_p99_ms" in detail
    assert "loop_lag_p99_ms" in detail


# ------------------------- dynahot DL022 fix regressions (ISSUE 18)


def test_sequence_stop_set_cached_once():
    """The per-token stop check reads ONE cached frozenset (built on
    first access) instead of rebuilding `x or []` defaults per token —
    later mutation of the request's lists must not change it (proves
    the cache is actually hit, not rebuilt)."""
    from dynamo_tpu.engine.jax_engine import Sequence

    req = _req([1, 2, 3], mt=10, eos=(7,))
    req.stop.stop_token_ids = [9]
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=[1, 2, 3], num_prompt=3)
    first = seq.stop_set
    assert first == frozenset({7, 9})
    assert seq.dev_stop_count == 2
    req.stop.stop_token_ids.append(11)   # post-hoc mutation: ignored
    assert seq.stop_set is first
    assert seq.dev_stop_count == 2


def test_sequence_stop_set_respects_ignore_eos():
    from dynamo_tpu.engine.jax_engine import Sequence

    req = _req([1], mt=10, eos=(7,))
    req.stop.ignore_eos = True
    req.stop.stop_token_ids = [9]
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=[1], num_prompt=1)
    assert seq.stop_set == frozenset({9})
    assert seq.dev_stop_count == 1


def test_emit_routes_by_thread_id_without_exception_probe():
    """_emit's on/off-loop routing is one thread-id compare: on the
    captured thread it puts directly; off it goes through
    call_soon_threadsafe; with no captured tid (engine not started) it
    puts directly — and no asyncio loop probe is involved at all."""
    import threading
    import types

    from dynamo_tpu.engine.jax_engine import JaxEngine, Sequence

    calls = []
    fake_loop = types.SimpleNamespace(
        call_soon_threadsafe=lambda fn, *a: calls.append(a))
    q = asyncio.Queue()
    seq = Sequence(req=_req([1]), context=Context(), out=q,
                   tokens=[1], num_prompt=1)
    eng = types.SimpleNamespace(
        latency=types.SimpleNamespace(observe=lambda *a, **k: None),
        _aio_loop=fake_loop, _aio_loop_tid=threading.get_ident())
    out = EngineOutput(token_ids=[5], prompt_tokens=1)
    JaxEngine._emit(eng, seq, out)          # on-thread: direct put
    assert q.qsize() == 1 and not calls
    eng._aio_loop_tid = threading.get_ident() + 1
    JaxEngine._emit(eng, seq, out)          # off-thread: via the loop
    assert q.qsize() == 1 and len(calls) == 1
    eng._aio_loop_tid = None
    JaxEngine._emit(eng, seq, out)          # pre-start: direct put
    assert q.qsize() == 2 and len(calls) == 1


def test_router_decision_overlap_consistent():
    """KvScheduler.schedule reads the chosen worker's capped overlap
    once: the decision record, the optimistic accounting, and the
    hit-rate event must all carry the SAME value."""
    from dynamo_tpu.llm.kv_router.indexer import OverlapScores
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.llm.kv_router.scheduler import KvScheduler

    events = []
    sched = KvScheduler(block_size=16, on_hit_rate_event=events.append)
    sched.update_metrics({1: ForwardPassMetrics(
        request_active_slots=0, request_total_slots=8,
        kv_active_blocks=0, kv_total_blocks=100)})
    chosen = sched.schedule(64, OverlapScores({1: 2}), request_id="r1")
    assert chosen == 1
    dec = sched.decisions[-1]
    expect = min(2, (64 + 15) // 16)
    assert dec["overlap_blocks"] == expect
    assert events[-1].overlap_blocks == expect
    assert sched.workers[1].extra_blocks == (64 + 15) // 16 - expect
