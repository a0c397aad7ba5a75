"""dynacache: end-to-end KV/prefix-cache observability (ISSUE 11).

Covers the four planes the tentpole wires together:

- PageManager lifecycle telemetry: allocation prefix split (device hit /
  host restore / fresh) with conservation, eviction fates + block age,
  restore-queue depth and drain latency, bounded hot-prefix tracking;
- engine surfaces: windowed vs lifetime hit rate, the per-request cost
  block's prefix split (conservation like PR 10's dispatch-share test),
  host-restored attribution, /debug/cache;
- the stats()→ForwardPassMetrics→Prometheus SYNC GATE: every numeric
  stats key either rides a rendered gauge or sits on an explicit
  skip-list (the drift class PR 10 found by hand, made impossible);
- the REAL stack: a shared-prefix workload through aiohttp → HttpService
  → Processor → KvRouter → token worker → JaxEngine reports
  prefix_hit_rate > 0 with router-predicted vs engine-realized
  attribution and zero post-warmup compiles.
"""

import asyncio
import os
import sys
import types
from collections import deque

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dynamo_tpu.engine.kv_manager import (PageManager,  # noqa: E402
                                          chain_hashes)


# ------------------------------------------------- PageManager telemetry


def test_alloc_split_counters_and_conservation():
    pm = PageManager(num_pages=32, page_size=4)
    prompt = list(range(17))  # 5 blocks (4 full + tail)
    a = pm.allocate_sequence(prompt)
    assert (a.device_hit_blocks, a.host_restored_blocks) == (0, 0)
    assert a.fresh_blocks == len(a.pages) == 5
    # commit the full blocks, release, re-allocate the same prompt
    for i, h in enumerate(chain_hashes(prompt[:16], 4)):
        pm.commit(a.pages[i], h)
    pm.release_sequence(a.pages)
    b = pm.allocate_sequence(prompt)
    assert b.device_hit_blocks == 4 and b.host_restored_blocks == 0
    # conservation: split sums to the allocated page count, and the
    # cumulative counters add up the same way
    assert (b.device_hit_blocks + b.host_restored_blocks
            + b.fresh_blocks) == len(b.pages)
    assert pm.device_hit_blocks_total == 4
    assert pm.fresh_blocks_total == 5 + 1  # first alloc + b's tail block
    # hot-prefix tracking saw the 4 reused hashes
    top = pm.top_prefixes(10)
    assert len(top) == 4 and all(t["hits"] == 1 for t in top)
    assert all(t["tier"] == "device" for t in top)


def test_eviction_fate_split_and_age():
    # no host tier: every committed eviction is a drop
    pm = PageManager(num_pages=6, page_size=2)
    a = pm.allocate_sequence([1, 2, 3, 4])  # 2 pages
    for i, h in enumerate(chain_hashes([1, 2, 3, 4], 2)):
        pm.commit(a.pages[i], h)
    pm.release_sequence(a.pages)
    # pool has 5 usable pages; claim them all so reusable pages evict
    claimed = [pm.allocate_page() for _ in range(5)]
    assert all(p is not None for p in claimed)
    assert pm.evict_dropped_total == 2
    assert pm.evict_offloaded_total == 0
    assert pm.evict_age_seconds_total >= 0.0

    # host tier: the same churn offloads instead
    pm2 = PageManager(num_pages=6, page_size=2, host_pages=8)
    b = pm2.allocate_sequence([1, 2, 3, 4])
    for i, h in enumerate(chain_hashes([1, 2, 3, 4], 2)):
        pm2.commit(b.pages[i], h)
    pm2.release_sequence(b.pages)
    for _ in range(5):
        pm2.allocate_page()
    assert pm2.evict_offloaded_total == 2
    assert pm2.evict_dropped_total == 0
    assert pm2.cache_stats()["evict_offloaded_total"] == 2


def test_restore_queue_depth_and_drain_wait():
    pm = PageManager(num_pages=6, page_size=2, host_pages=8)
    prompt = [1, 2, 3, 4, 5]
    a = pm.allocate_sequence(prompt)
    for i, h in enumerate(chain_hashes(prompt[:4], 2)):
        pm.commit(a.pages[i], h)
    pm.release_sequence(a.pages)
    for _ in range(5):  # evict both committed blocks into the host tier
        pm.allocate_page()
    assert pm.evict_offloaded_total == 2
    pm.drain_tier_ops()  # flush the offload copies; no restores yet
    assert pm.restores_drained_total == 0
    # free the pool again and re-allocate: host hits queue restores
    for p in range(1, pm.num_pages):
        if pm.pages[p].refcount:
            pm.release_sequence([p])
    b = pm.allocate_sequence(prompt)
    assert b.host_restored_blocks == 2
    assert pm.cache_stats()["restore_queue_depth"] == 2
    _, res = pm.drain_tier_ops()
    assert len(res) == 2
    st = pm.cache_stats()
    assert st["restore_queue_depth"] == 0
    assert st["restores_drained_total"] == 2
    assert st["restore_wait_seconds_total"] >= 0.0
    assert pm._restore_enq == {}  # stamps consumed


def test_hot_prefix_tracking_is_bounded():
    pm = PageManager(num_pages=8, page_size=2)
    pm._hit_track_cap = 3
    for h in range(10):
        if h in pm._hit_counts:
            pm._hit_counts[h] += 1
        elif len(pm._hit_counts) < pm._hit_track_cap:
            pm._hit_counts[h] = 1
    assert len(pm._hit_counts) == 3
    assert len(pm.top_prefixes(2)) == 2


# ------------------------------------------------------- engine surfaces


def _tiny_engine(host_pages=0, num_pages=64, seed=0):
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=4, num_pages=num_pages, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(16,),
                        host_pages=host_pages, watermark_pages=2)
    return JaxEngine(cfg, ecfg, seed=seed)


async def _gen(engine, prompt, n=6, rid=None):
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        eos_token_ids=[])
    ctx = Context(rid) if rid else Context()
    cost = None
    async for out in engine.generate(req, ctx):
        if out.finish_reason:
            cost = out.cost
            break
    return cost


def test_windowed_hit_rate_tracks_recent_traffic():
    """The windowed rate forgets old traffic; the lifetime ratio cannot
    (the ISSUE 11 satellite: the aggregator gauge must reflect recent
    traffic)."""
    from dynamo_tpu.engine.jax_engine import JaxEngine

    eng = object.__new__(JaxEngine)  # windowed math only — no device
    eng._hit_window = deque(maxlen=4)
    for _ in range(4):
        eng._hit_window.append((8, 8))  # 100% hits
    assert JaxEngine._windowed_hit_rate(eng) == 1.0
    for _ in range(4):
        eng._hit_window.append((0, 8))  # recent traffic: all misses
    assert JaxEngine._windowed_hit_rate(eng) == 0.0
    assert JaxEngine._windowed_hit_rate(
        types.SimpleNamespace(_hit_window=deque())) == 0.0


def test_cost_block_prefix_split_conservation(run_async):
    """device_hit + host_restored + fresh == prompt blocks on every cost
    block (the dynacache analog of PR 10's dispatch-share conservation),
    with host_restored > 0 after an evict→restore round trip."""

    async def scenario():
        engine = _tiny_engine(host_pages=32, num_pages=16)
        rng = np.random.RandomState(0)
        prompt_a = rng.randint(1, 500, 20).tolist()  # 5 blocks
        c1 = await _gen(engine, prompt_a)
        # churn the tiny pool so A's blocks spill to the host tier
        for _ in range(4):
            await _gen(engine, rng.randint(1, 500, 20).tolist())
        c2 = await _gen(engine, prompt_a)
        snap = engine.cache_snapshot()
        await engine.stop()
        return c1, c2, snap

    c1, c2, snap = run_async(scenario())
    for cost in (c1, c2):
        assert cost is not None
        assert (cost["device_hit_blocks"] + cost["host_restored_blocks"]
                <= cost["prompt_blocks"])
        fresh = (cost["prompt_blocks"] - cost["device_hit_blocks"]
                 - cost["host_restored_blocks"])
        assert fresh >= 0
    assert c1["device_hit_blocks"] == 0 and c1["host_restored_blocks"] == 0
    assert c2["host_restored_blocks"] > 0, \
        "evicted prompt should restore from the host tier"
    assert c2["restore_wait_ms"] >= 0.0
    # snapshot mirrors the counters and carries the hot chains
    assert snap["host_restored_blocks_total"] >= c2["host_restored_blocks"]
    assert snap["restores_drained_total"] > 0
    assert snap["pool"]["total_blocks"] == 15
    assert isinstance(snap["top_prefixes"], list)


# ----------------------------------------------- stats→Prometheus sync gate


def test_stats_prometheus_sync_gate(run_async):
    """Every numeric engine stats() key must either be a
    ForwardPassMetrics field that the aggregator RENDERS, or sit on the
    explicit STATS_PROMETHEUS_SKIP list. Sentinel-value rendering makes
    silent drift (a counter that stops at the stats plane) impossible."""
    from dynamo_tpu.llm.kv_router.protocols import (
        STATS_PROMETHEUS_SKIP, ForwardPassMetrics)
    from dynamo_tpu.metrics.component import MetricsAggregator

    engine = _tiny_engine()

    async def scenario():
        await _gen(engine, list(range(1, 9)))
        st = engine.stats()
        await engine.stop()
        return st

    st = run_async(scenario())
    fpm_fields = set(ForwardPassMetrics.__dataclass_fields__)
    numeric = {k for k, v in st.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    unrouted = numeric - fpm_fields - set(STATS_PROMETHEUS_SKIP)
    assert not unrouted, (
        f"engine stats() keys {sorted(unrouted)} reach neither a "
        f"ForwardPassMetrics field nor STATS_PROMETHEUS_SKIP — add a "
        f"gauge or an explicit skip entry")
    # skip-list hygiene: every entry is a REAL stats key with a reason
    for k, why in STATS_PROMETHEUS_SKIP.items():
        assert k in st and why

    # sentinel render: every numeric FPM field must appear in the
    # aggregator's exposition text
    sentinels = {}
    fpm = ForwardPassMetrics()
    for i, name in enumerate(sorted(fpm_fields)):
        if isinstance(getattr(fpm, name), (dict, str)):
            # dicts render as labeled families; strings are identity
            # LABELS (worker_label/mesh_shape — dynashard), not counters
            continue
        val = 900000 + i if isinstance(getattr(fpm, name), int) \
            else round(0.5 + i / 1000.0, 3)
        setattr(fpm, name, val)
        sentinels[name] = val
    agg = MetricsAggregator.__new__(MetricsAggregator)
    agg.namespace = "gate"
    agg.worker_metrics = {7: fpm}
    agg.hit_rate_isl_blocks = agg.hit_rate_overlap_blocks = 0
    agg.hit_rate_events = 0
    agg.scrape_failures_total = agg.consecutive_scrape_failures = 0
    agg._client = None
    text = agg.render_prometheus()
    missing = [name for name, val in sentinels.items()
               if f" {val}" not in text]
    assert not missing, (
        f"ForwardPassMetrics fields {missing} are never rendered by the "
        f"metrics aggregator — every stats-plane field must reach a "
        f"Prometheus gauge")


# -------------------------------------------------- /debug/cache endpoint


def test_debug_cache_endpoint(run_async):
    """GET /debug/cache renders every registered cache view — the tiny
    engine registered itself at construction."""

    async def main():
        import aiohttp

        from dynamo_tpu.llm.http.service import HttpService

        engine = _tiny_engine()
        await _gen(engine, list(range(1, 9)))
        service = HttpService()
        await service.start(host="127.0.0.1", port=0)
        try:
            async with aiohttp.ClientSession() as http:
                async with http.get(
                        f"http://127.0.0.1:{service.port}/debug/cache"
                ) as resp:
                    assert resp.status == 200
                    body = await resp.json()
        finally:
            await service.stop()
            await engine.stop()
        return body

    body = run_async(main())
    engines = [v for k, v in body["caches"].items()
               if k.startswith("jax-engine-")]
    assert engines, body["caches"].keys()
    snap = engines[-1]
    assert {"pool", "host_tier", "hit_rate_windowed", "top_prefixes",
            "restore_queue_depth"} <= set(snap)


# ------------------------------------------- the REAL stack, shared-prefix


def _shared_args(**over):
    base = dict(
        sweep=None, scenario="shared", shared_shape="multi_tenant",
        isl=96, osl=8, requests=8, concurrency=4, model="tiny",
        dtype="bf16", users=3, turns=3, host_pages=0,
        disagg_threshold=256, seed=0, decode_steps=2,
        prefill_token_budget=None, host_tier_int8=False, max_batch=None,
        spec=False, cpu=True, trace=False,
        shared_prefix=False)
    base.update(over)
    return types.SimpleNamespace(**base)


def test_shared_prefix_bench_e2e_through_real_stack():
    """The acceptance scenario: a shared-prefix workload through
    HTTP→Processor→KvRouter→JaxEngine reports prefix_hit_rate > 0 with
    the router-predicted vs engine-realized attribution breakdown, cost
    blocks conserve the prefix split, the TTFT A/B is present, and no
    post-warmup compile fired."""
    import bench

    report = asyncio.run(bench.run_shared(_shared_args()))
    assert report["post_warmup_compiles"] == 0
    assert report["prefix_hit_rate"] > 0
    shape = report["shapes"]["multi_tenant"]
    share, noshare = shape["share"], shape["noshare"]
    assert share["errors"] == 0 and noshare["errors"] == 0
    # no-sharing control cannot hit; the shared leg must
    assert noshare["prefix_hit_rate"] == 0.0
    assert share["prefix_hit_rate"] > 0
    assert share["device_hit_blocks"] > 0
    # router calibration: predictions were compared against realized
    # splits, and overlap routing onto one worker should be exact here
    calib = report["calibration"]
    assert calib["compared"] > 0
    assert calib["predicted_blocks_total"] > 0
    assert calib["realized_blocks_total"] > 0
    # cost-block conservation over the whole leg (router-predicted vs
    # engine-realized vs host-restored breakdown present)
    for leg in (share, noshare):
        cs = leg["cost_split"]
        assert cs["requests_with_cost"] == leg["requests"]
        assert (cs["device_hit_blocks"] + cs["host_restored_blocks"]
                + cs["fresh_blocks"]) == cs["prompt_blocks"]
    assert share["cost_split"]["router_overlap_blocks"] > 0
    assert "ttft_delta_ms" in shape


def test_disagg_shared_prefix_ab_smoke():
    """--shared-prefix disagg leg: same engines, shared-prefix prompts —
    the transfer-vs-reuse A/B reports transfer pages per remote prefill
    for both legs plus the decode engine's realized hit split."""
    import bench

    args = _shared_args(scenario="disagg", isl=96, osl=4, requests=3,
                        concurrency=2, disagg_threshold=16,
                        kv_chunk_pages="2", shared_prefix=True)
    report = asyncio.run(bench.run_disagg(args))
    ab = report["shared_prefix_ab"]
    assert ab["fresh"]["remote_prefills"] > 0
    # the shared leg reuses decode-side blocks...
    assert ab["shared"]["decode_hit_blocks"] > 0
    # ...and therefore ships fewer total pages over the wire for the
    # same request count (per-remote ratios can even rise: big hits
    # shrink the remaining prefill below the disagg threshold and route
    # LOCAL — also reuse at work, so totals are the honest comparison)
    assert ab["shared"]["transfer_pages"] < ab["fresh"]["transfer_pages"]
