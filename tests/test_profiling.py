"""dynaprof: loop-lag monitor, stall watchdog, per-request cost
attribution, /debug/profile round-trip.

The central invariant: attribution conserves dispatches. Every dispatch
distributes exactly 1.0 of step share across its batch, so the
per-request shares sum to the engine's dispatch counter.
"""

import asyncio
import json
import time

import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.profiler import PHASES
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context, profiling, tracing


@pytest.fixture
def run_async():
    def run(coro):
        return asyncio.run(coro)

    return run


# ------------------------------------------------------- loop lag monitor


def test_loop_lag_monitor_records_stall(run_async):
    """An injected blocking callback shows up as sleep-drift ≥ its
    duration in the monitor's percentiles."""

    async def main():
        mon = profiling.LoopLagMonitor(interval_s=0.01)
        mon.start()
        await asyncio.sleep(0.05)          # a few clean samples
        time.sleep(0.15)                   # the stalled callback
        await asyncio.sleep(0.05)          # let the late wakeup land
        snap = mon.snapshot()
        await mon.stop()
        return snap

    snap = run_async(main())
    assert snap["samples"] >= 2
    assert snap["max_s"] >= 0.1
    assert snap["p99_s"] >= 0.1
    assert snap["p50_s"] < snap["max_s"] + 1e-9


def _deliberate_stall(duration: float) -> None:
    time.sleep(duration)


def test_stall_watchdog_captures_folded_stack(run_async):
    """While a loop callback overruns the threshold, the watchdog samples
    the loop thread's stack; the stalling frame appears in the
    flamegraph-ready collapsed output."""

    async def main():
        mon = profiling.LoopLagMonitor(interval_s=0.01)
        dog = profiling.StallWatchdog(mon, threshold_s=0.05, poll_s=0.02)
        mon.start()
        dog.start()
        await asyncio.sleep(0.05)          # heartbeat established
        _deliberate_stall(0.4)             # watchdog fires during this
        dog.stop()
        folded = dog.folded()
        snap = dog.snapshot()
        await mon.stop()
        return folded, snap

    folded, snap = run_async(main())
    assert snap["captures"] >= 1
    assert "_deliberate_stall" in folded
    # collapsed-stack format: "frame;frame;... count" lines
    line = folded.splitlines()[0]
    stack, count = line.rsplit(" ", 1)
    assert ";" in stack and int(count) >= 1


def test_fold_stack_format():
    import sys

    folded = profiling.fold_stack(sys._getframe())
    assert folded.endswith("test_profiling.test_fold_stack_format")


def test_watchdog_bounded_stacks(run_async):
    """Past max_stacks, new distinct stacks are counted as dropped, not
    accumulated (the ring is bounded)."""

    async def main():
        mon = profiling.LoopLagMonitor(interval_s=0.01)
        mon.start()
        await asyncio.sleep(0.02)
        dog = profiling.StallWatchdog(mon, threshold_s=10.0, max_stacks=1)
        dog.capture()                       # first shape: kept

        def other_frame():
            return dog.capture()            # second shape: dropped

        other_frame()
        snap = dog.snapshot()
        await mon.stop()
        return snap

    snap = run_async(main())
    assert snap["captures"] == 2
    assert snap["distinct_stacks"] == 1
    assert snap["dropped"] == 1


# ------------------------------------------------------ engine attribution


def _req(tokens, mt=6, **sampling):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(**sampling),
        stop=StopConditions(max_tokens=mt, ignore_eos=True),
        eos_token_ids=[])


def _tiny_engine(**overrides) -> JaxEngine:
    cfg = ModelConfig.tiny()
    kw = dict(page_size=8, num_pages=64, max_batch=4, prefill_chunk=32,
              batch_buckets=(1, 2, 4), prefill_buckets=(16, 32),
              page_buckets=(8,), max_prefill_batch=2, decode_steps=2)
    kw.update(overrides)
    eng = JaxEngine(cfg, EngineConfig(**kw), seed=0)
    eng.warmup()
    return eng


async def _drive(eng, reqs):
    """Run requests to completion; returns (token lists, finish cost
    blocks)."""
    costs = []

    async def one(r):
        toks = []
        async for out in eng.generate(r, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason is not None:
                assert out.finish_reason != "error"
                costs.append(out.cost)
        return toks

    results = await asyncio.gather(*(one(r) for r in reqs))
    return results, costs


def test_attribution_sums_to_engine_totals(run_async):
    """Conservation: each dispatch distributes exactly 1.0 step share
    over its batch, so per-request shares sum to the engine's dispatch
    counter; per-request token counts sum to the engine totals."""
    eng = _tiny_engine()
    reqs = [_req(list(range(1, 20)), mt=6),
            _req([3] * 24, mt=5),
            _req(list(range(60, 70)), mt=4),
            _req(list(range(80, 85)), mt=3)]

    async def main():
        out = await _drive(eng, reqs)
        await eng.stop()
        return out

    _results, costs = run_async(main())
    assert len(costs) == len(reqs)
    share_sum = sum(c["device_step_share"] for c in costs)
    assert share_sum == pytest.approx(eng.batch_dispatches_total,
                                      rel=1e-4)
    # per-request generated counts include the first token (sampled by
    # the prefill dispatch); the engine's decode counter starts after it
    assert sum(c["decode_tokens"] for c in costs) == \
        eng.decode_tokens_total + len(reqs)
    assert sum(c["prompt_tokens"] for c in costs) == \
        eng.prompt_tokens_total
    for c in costs:
        assert c["queue_wait_ms"] >= 0.0
        assert c["kv_pages_peak"] >= 1
        assert c["kv_bytes_peak"] > 0
        assert c["dispatches"] >= 1
    assert eng.fence.post_warmup_compiles == 0
    kinds = {e["kind"] for e in eng.step_timeline.snapshot()}
    assert {"admit", "prefill", "decode_window"} <= kinds <= {
        "admit", "prefill", "decode", "decode_window", "spec_verify",
        "compile"}
    # the engine also registered every attribution in the process ring
    assert profiling.request_attribution is not None
    eng.fence.disarm()


# ------------------------------------------------ stats -> ForwardPassMetrics


def test_engine_gauges_reach_forward_pass_metrics(run_async):
    """The dynaprof + engine-internal stats() keys map onto
    ForwardPassMetrics fields (that name match is what carries them to
    the aggregator's dyn_engine_* gauges)."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics

    eng = _tiny_engine()

    async def main():
        await _drive(eng, [_req(list(range(1, 12)), mt=4)])
        await eng.stop()

    run_async(main())
    m = ForwardPassMetrics.from_dict(eng.stats())
    assert m.kv_free_blocks > 0
    assert m.batch_dispatches_total >= 2
    assert m.queue_wait_seconds_total >= 0.0
    # aggregator render path
    from dynamo_tpu.metrics.component import MetricsAggregator

    agg = MetricsAggregator.__new__(MetricsAggregator)
    agg.namespace = "t"
    agg.worker_metrics = {1: m}
    agg.hit_rate_isl_blocks = agg.hit_rate_overlap_blocks = 0
    agg.hit_rate_events = 0
    agg.scrape_failures_total = agg.consecutive_scrape_failures = 0
    agg._client = None
    text = agg.render_prometheus()
    assert 'quantile="p99"' in text
    assert "dyn_engine_kv_free_blocks" in text
    # the phase ledger is what /debug/profile's `engines` carries
    led = profiling.profiles_snapshot()[eng.profiler.name]
    assert led["step_iterations"] == eng.stats()["step_iterations_total"] > 0
    assert set(led["phase_seconds"]) == set(PHASES)
    assert led["phase_seconds"]["dispatch_window"] > 0.0
    eng.fence.disarm()


# -------------------------------------------------------- timeline anchors


def test_step_timeline_anchor_alignment():
    """Rings constructed at different times export alignable wall
    ``ts_ms``: two events recorded at (nearly) the same instant land
    within tolerance of each other despite different ring anchors."""
    tl1 = tracing.StepTimeline(8)
    time.sleep(0.05)
    tl2 = tracing.StepTimeline(8)
    tl1.add("x")
    tl2.add("x")
    e1 = tl1.snapshot()[0]
    e2 = tl2.snapshot()[0]
    # raw monotonic offsets differ by the construction gap...
    assert e1["mono_ms"] - e2["mono_ms"] > 25
    # ...but the anchor-aligned wall stamps agree
    assert abs(e1["ts_ms"] - e2["ts_ms"]) < 25
    a = tl1.anchors()
    assert set(a) == {"anchor_wall_ms", "anchor_monotonic_ms"}


# ------------------------------------------------- HTTP /debug + /v1/traces


def test_debug_profile_round_trip(run_async):
    """/debug/profile snapshot, collapsed-stack dump, jax trace
    start/stop, and cost attribution under /v1/traces/{rid}."""

    async def main():
        import aiohttp

        from dynamo_tpu.llm.http.service import HttpService

        service = HttpService()
        await service.start(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        profiling.record_attribution("prof-rid-1", {
            "queue_wait_ms": 1.0, "device_step_share": 2.5,
            "decode_tokens": 8})
        try:
            async with aiohttp.ClientSession() as http:
                async with http.get(f"{base}/debug/profile") as r:
                    assert r.status == 200
                    body = await r.json()
                    assert body["loop"]["loop_lag"]["interval_s"] > 0
                    assert "engines" in body
                async with http.get(f"{base}/debug/profile/stacks") as r:
                    assert r.status == 200
                    assert r.content_type == "text/plain"
                async with http.get(f"{base}/v1/traces/prof-rid-1") as r:
                    assert r.status == 200
                    body = await r.json()
                    assert body["cost"]["device_step_share"] == 2.5
                    assert body["spans"] == []
                # jax.profiler capture round-trip (CPU backend works)
                import tempfile

                tdir = tempfile.mkdtemp(prefix="dynaprof-test-")
                async with http.post(f"{base}/debug/profile/start",
                                     json={"dir": tdir}) as r:
                    started = r.status == 200
                    if started:
                        body = await r.json()
                        assert body["dir"] == tdir
                if started:
                    # double-start is a clean 409, then stop succeeds
                    async with http.post(
                            f"{base}/debug/profile/start") as r:
                        assert r.status == 409
                    async with http.post(
                            f"{base}/debug/profile/stop") as r:
                        assert r.status == 200
                async with http.post(f"{base}/debug/profile/stop") as r:
                    assert r.status in (409, 500)
        finally:
            await service.stop()

    run_async(main())


def test_usage_cost_extension(monkeypatch):
    """DYN_PROF_USAGE gates the usage `cost` block; the Usage model
    round-trips it and exclude_none keeps OpenAI payloads clean."""
    from dynamo_tpu.llm.engines import usage_cost
    from dynamo_tpu.llm.protocols.openai import Usage, _merge_usage

    ctx = Context("usage-rid-1")
    profiling.record_attribution(ctx.id, {"decode_tokens": 4})
    assert usage_cost(ctx) is None          # default off
    monkeypatch.setenv("DYN_PROF_USAGE", "1")
    assert usage_cost(ctx) == {"decode_tokens": 4}
    assert usage_cost(Context("never-seen-rid")) is None

    u = Usage(prompt_tokens=3, completion_tokens=2, total_tokens=5,
              cost={"decode_tokens": 4})
    assert json.loads(u.model_dump_json())["cost"] == {"decode_tokens": 4}
    plain = Usage(prompt_tokens=1, completion_tokens=1, total_tokens=2)
    assert "cost" not in plain.model_dump(exclude_none=True)
    merged = _merge_usage(plain, u)
    assert merged.cost == {"decode_tokens": 4}


def test_attribution_ring_bounded(monkeypatch):
    monkeypatch.setenv("DYN_PROF_ATTR_RING", "4")
    for i in range(10):
        profiling.record_attribution(f"ring-{i}", {"i": i})
    assert profiling.request_attribution("ring-0") is None
    assert profiling.request_attribution("ring-9") == {"i": 9}
    assert len(profiling.attributions_snapshot(10 ** 6)) <= 4
