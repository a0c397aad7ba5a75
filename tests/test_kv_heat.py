"""dynaheat: cost-aware eviction, batched/overlapped restores, int8
host-tier default, and router-overlap autotune.

Eviction policy is A/B'd at the PageManager level (`lru` is the
pre-dynaheat control, `cost` the GreedyDual hot-prefix policy); the
restore-overlap pipeline is pinned by engine-level token identity against
the serial drain.
"""

import numpy as np
import pytest

from dynamo_tpu.engine.kv_manager import PageManager, chain_hashes


def _commit_all(pm, pages, prompt):
    hashes = chain_hashes(prompt, pm.page_size)
    for i, h in enumerate(hashes):
        pm.commit(pages[i], h, parent_hash=hashes[i - 1] if i else None)


def _heat(pm, hot, rounds):
    """Re-allocate ``hot`` (+ a partial tail so BOTH full blocks are
    matchable — the tail cap would otherwise shield the last block from
    ever being hit) to build up its hit counts."""
    for _ in range(rounds):
        a = pm.allocate_sequence(hot + [900, 901, 902])
        assert a is not None
        pm.release_sequence(a.pages)


def _churn(pm, n, base=5000):
    """n distinct single-block prompts, committed + released, so each one
    consumes a free page (or evicts a reusable one) and then parks in the
    reusable pool itself."""
    for i in range(n):
        prompt = [base + 4 * i + j for j in range(4)]
        a = pm.allocate_sequence(prompt)
        assert a is not None
        _commit_all(pm, a.pages, prompt)
        pm.release_sequence(a.pages)


@pytest.mark.parametrize("policy,survives", [("cost", True), ("lru", False)])
def test_hot_prefix_vs_cold_churn(policy, survives):
    """The policy split dynaheat exists for: a hot 2-block prefix (12
    reuses) against a stream of one-shot cold blocks. LRU evicts the hot
    blocks first (they were freed before the churn), GreedyDual keeps
    them (priority = clock + 1 + hits, and the clock only advances ~1
    per cold eviction — a 12-hit block outlives 12 cold evictions)."""
    pm = PageManager(num_pages=10, page_size=4, evict_policy=policy)
    hot = list(range(8))  # 2 full blocks
    a = pm.allocate_sequence(hot)
    _commit_all(pm, a.pages, hot)
    pm.release_sequence(a.pages)
    _heat(pm, hot, rounds=12)
    hot_hashes = chain_hashes(hot, 4)
    assert all(h in pm.by_hash for h in hot_hashes)
    # 9 usable pages, 2 hold the hot blocks: 10 cold blocks = 7 via the
    # free list + 3 evictions
    _churn(pm, 10)
    resident = [h for h in hot_hashes if h in pm.by_hash]
    if survives:
        assert resident == hot_hashes, "cost policy must keep the hot prefix"
        b = pm.allocate_sequence(hot + [903])
        assert b.cached_tokens == 8 and b.device_hit_blocks == 2
        pm.release_sequence(b.pages)
    else:
        assert resident == [], "lru control must have evicted the hot prefix"


def test_cost_policy_hot_block_ages_out():
    """GreedyDual aging: once-hot blocks must not squat forever. After
    enough cold evictions push the clock past the hot priority, the hot
    blocks go too (no immortal entries)."""
    pm = PageManager(num_pages=10, page_size=4, evict_policy="cost")
    hot = list(range(8))
    a = pm.allocate_sequence(hot)
    _commit_all(pm, a.pages, hot)
    pm.release_sequence(a.pages)
    _heat(pm, hot, rounds=4)  # priority ~ clock + 5
    # ~43 evictions over 7 circulating cold pages pushes the clock past
    # the hot priority (clock climbs ~1 per cold generation)
    _churn(pm, 50)
    hot_hashes = chain_hashes(hot, 4)
    assert not any(h in pm.by_hash for h in hot_hashes)


def test_conservation_and_evict_fates():
    """Invariants the counters must keep under mixed traffic: every
    allocation's prefix split sums to its page count, HBM evictions of
    committed blocks split exactly into offloaded + dropped, and no slot
    pin survives a full drain."""
    pm = PageManager(num_pages=4, page_size=4, host_pages=2,
                     evict_policy="cost")  # 3 usable HBM, 2 host slots
    prompt = list(range(12))  # 3 blocks
    a = pm.allocate_sequence(prompt)
    assert (a.device_hit_blocks + a.host_restored_blocks
            + a.fresh_blocks) == len(a.pages)
    _commit_all(pm, a.pages, prompt)
    pm.release_sequence(a.pages)

    # 3 committed blocks evicted into a 2-slot host tier: two get slots,
    # the third finds both slots pinned by the queued offloads → dropped.
    # Fates partition the evictions exactly.
    b = pm.allocate_sequence(list(range(100, 112)))
    assert (b.device_hit_blocks + b.host_restored_blocks
            + b.fresh_blocks) == len(b.pages)
    off, res = pm.drain_tier_ops()
    assert pm.evict_offloaded_total + pm.evict_dropped_total == 3
    assert pm.evict_offloaded_total == len(off) == 2
    _commit_all(pm, b.pages, list(range(100, 112)))
    pm.release_sequence(b.pages)

    # host hit → restore: split counts it as host_restored
    c = pm.allocate_sequence(prompt)
    assert c.host_restored_blocks == len(c.restores) > 0
    assert (c.device_hit_blocks + c.host_restored_blocks
            + c.fresh_blocks) == len(c.pages)
    off, res = pm.drain_tier_ops()
    assert pm.restore_batches_total == 1
    assert pm.restore_batch_pages_total == len(res)
    # totals mirror the per-alloc splits
    st = pm.cache_stats()
    allocs = (a, b, c)
    assert st["device_hit_blocks_total"] == sum(x.device_hit_blocks
                                                for x in allocs)
    assert st["host_restored_blocks_total"] == sum(x.host_restored_blocks
                                                   for x in allocs)
    assert st["fresh_blocks_total"] == sum(x.fresh_blocks for x in allocs)
    assert st["evict_policy"] == "cost"
    assert pm._slot_pins == {}, "pins must drain to zero with the queues"


@pytest.mark.parametrize("policy", ["lru", "cost"])
def test_fully_pinned_host_tier_drops(policy):
    """When every host slot is pinned by queued restores, a new eviction
    must take the drop path (removed event + evict_dropped) — never
    reassign an in-flight slot — and the pins must still drain to
    zero."""
    pm = PageManager(num_pages=4, page_size=4, host_pages=2,
                     evict_policy=policy)  # 3 usable, 2 host slots
    p1 = list(range(8))  # 2 blocks
    a = pm.allocate_sequence(p1)
    _commit_all(pm, a.pages, p1)
    pm.release_sequence(a.pages)
    b = pm.allocate_sequence(list(range(100, 112)))  # evicts both to host
    pm.drain_tier_ops()
    _commit_all(pm, b.pages, list(range(100, 112)))
    pm.release_sequence(b.pages)
    pm.drain_events()

    dropped0 = pm.evict_dropped_total
    # p1 + a tail token so BOTH blocks clear the last-block reuse cap:
    # queues 2 restores (pinning both slots), and the same call's 3
    # fresh-page pops evict b's committed blocks into the fully-pinned
    # host tier → dropped, with removed events
    c = pm.allocate_sequence(p1 + [77])
    assert len(c.restores) == 2
    assert sum(pm._slot_pins.values()) >= 2
    assert pm.evict_dropped_total > dropped0
    assert [e for e in pm.drain_events() if e.kind == "removed"]
    pm.drain_tier_ops()
    assert pm._slot_pins == {}


def test_host_eviction_accounting():
    """A full, unpinned host tier evicts ITS policy victim to admit a new
    offload — counted host_evictions (the HBM eviction itself is still
    offloaded), with a removed event once the block leaves both tiers."""
    pm = PageManager(num_pages=2, page_size=2, host_pages=1)  # 1 usable
    a = pm.allocate_sequence([0, 1])
    _commit_all(pm, a.pages, [0, 1])
    pm.release_sequence(a.pages)
    b = pm.allocate_sequence([10, 11])   # evicts A → offload to slot 0
    off, _ = pm.drain_tier_ops()         # unpins slot 0
    assert len(off) == 1
    _commit_all(pm, b.pages, [10, 11])
    pm.release_sequence(b.pages)
    pm.drain_events()
    c = pm.allocate_sequence([20, 21])   # evicts B → host full → evict A
    assert c is not None
    assert pm.host_evictions_total == 1
    assert pm.evict_offloaded_total == 2
    assert pm.evict_dropped_total == 0
    assert [e for e in pm.drain_events() if e.kind == "removed"]


def test_evict_policy_validation():
    with pytest.raises(ValueError):
        PageManager(num_pages=4, page_size=4, evict_policy="mru")


def test_host_tier_int8_default_resolution(monkeypatch):
    """dynaheat flips int8 page moves DEFAULT-ON whenever a host tier
    exists; DYN_HOST_TIER_FP16=1 is the lossless fallback; an explicit
    EngineConfig value always wins."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny()

    def make(**kw):
        ecfg = EngineConfig(page_size=4, num_pages=8, max_batch=2,
                            prefill_chunk=16, prefill_buckets=(16,),
                            batch_buckets=(2,), page_buckets=(8,), **kw)
        return JaxEngine(cfg, ecfg, seed=0)

    monkeypatch.delenv("DYN_HOST_TIER_FP16", raising=False)
    assert make(host_pages=16).ecfg.host_tier_int8 is True
    assert make(host_pages=0).ecfg.host_tier_int8 is False
    monkeypatch.setenv("DYN_HOST_TIER_FP16", "1")
    assert make(host_pages=16).ecfg.host_tier_int8 is False
    assert make(host_pages=16,
                host_tier_int8=True).ecfg.host_tier_int8 is True


def _engine_restore_cycle(run_async, overlap):
    """One engine run of the churn-out-then-restore workload; returns
    (first, again, restore_pages_total)."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.runtime.engine import Context

    cfg = ModelConfig.tiny()
    ecfg = EngineConfig(page_size=4, num_pages=24, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(16,),
                        host_pages=64, watermark_pages=2,
                        host_tier_int8=False,  # identity: lossless tier
                        restore_overlap=overlap)
    engine = JaxEngine(cfg, ecfg, seed=0)

    async def gen(prompt, n=8):
        req = PreprocessedRequest(
            token_ids=prompt, sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        return toks

    async def scenario():
        rng = np.random.RandomState(7)
        prompt_a = rng.randint(1, 500, 24).tolist()  # 6 pages
        first = await gen(prompt_a)
        for _ in range(4):  # churn A out of the 23-page HBM pool
            await gen(rng.randint(1, 500, 24).tolist())
        again = await gen(prompt_a)
        await engine.stop()
        return first, again, engine.restore_pages_total

    return run_async(scenario())


def test_restore_overlap_token_identity(run_async):
    """Overlapped drain (stage at drain N, inject at drain N+1) must
    reproduce the original continuation exactly — the staged rows carry
    the same content the serial path injects, and prefill on the pages
    stays gated until injection."""
    first, again, restored = _engine_restore_cycle(run_async, overlap=True)
    assert len(first) == 8
    assert first == again
    assert restored > 0, "workload must actually exercise restores"


@pytest.mark.slow
def test_restore_overlap_matches_serial(run_async):
    """A/B: the overlapped pipeline and the serial drain produce
    token-identical output and restore the same page count."""
    f_o, a_o, r_o = _engine_restore_cycle(run_async, overlap=True)
    f_s, a_s, r_s = _engine_restore_cycle(run_async, overlap=False)
    assert f_o == a_o == f_s == a_s
    assert r_o == r_s > 0


def test_router_autotune_moves_weight():
    """Over-prediction (index promises overlap the engines don't hold)
    must shift load_balance_weight toward load; perfect calibration must
    not move it; the weight stays clamped and is exported as a gauge."""
    from dynamo_tpu.llm.kv_router.scheduler import KvScheduler
    from dynamo_tpu.runtime import guard

    s = KvScheduler(block_size=4, autotune=True, autotune_gain=0.5,
                    autotune_window=4)
    w0 = s.load_balance_weight
    for _ in range(4):  # predicted 8, realized 2 of 8 → bias 0.75
        s.observe_calibration(predicted=8, realized=2, isl_blocks=8)
    assert s.load_balance_weight > w0
    assert s.autotune_adjustments == 1
    assert abs(guard.counter_value("dyn_kv_router_load_balance_weight")
               - s.load_balance_weight) < 1e-9

    # zero bias: window fills, weight holds
    w1 = s.load_balance_weight
    for _ in range(4):
        s.observe_calibration(predicted=4, realized=4, isl_blocks=8)
    assert s.load_balance_weight == w1

    # clamp: huge sustained bias cannot push past alpha_max
    for _ in range(40):
        s.observe_calibration(predicted=8, realized=0, isl_blocks=8)
    assert s.alpha_min <= s.load_balance_weight <= s.alpha_max

    # toggle off: a disabled scheduler never moves
    s2 = KvScheduler(block_size=4, autotune=False)
    for _ in range(128):
        s2.observe_calibration(predicted=8, realized=0, isl_blocks=8)
    assert s2.load_balance_weight == 0.3
    assert s2.autotune_adjustments == 0
