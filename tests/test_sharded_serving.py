"""dynashard: mesh-sharded serving with data-parallel replicas (ISSUE 12).

Covers the tentpole's four planes:

- submesh planning: DevicePool assignment/release/re-partitioning and
  mesh-shape parsing (pure units, no jax);
- the sharded engine serving path: a mesh>1 JaxEngine serves
  token-identical to the unsharded control with the compile fence at
  zero — the committed-carry warmup variants must hold (in-process,
  riding conftest's forced-8-device CPU host);
- the REAL stack end-to-end in a SUBPROCESS (XLA's device-count flag is
  read once at backend init, so the suite's own backend can't be
  trusted): HTTP → Processor → KvRouter → 2 sharded replicas, asserting
  token identity vs the unsharded control, post_warmup_compiles == 0
  per replica, the KV-router overlap hit landing on the replica that
  committed the prefix, and per-replica `replica="rN"` gauge rows;
- the dynafleet `sharded` scenario: the planner scales sharded replicas,
  joins/drains re-partition the modeled device pool, the SLO report
  shows recovery.
"""

import asyncio
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dynamo_tpu.parallel.serving import (DevicePool,  # noqa: E402
                                         NoFreeDevices, devices_per_replica,
                                         mesh_shape_str, parse_mesh_shape,
                                         plan_replicas)


# ------------------------------------------------------------- pure units


def test_parse_mesh_shape():
    assert parse_mesh_shape(None) == {}
    assert parse_mesh_shape("") == {}
    assert parse_mesh_shape("model=2") == {"model": 2}
    assert parse_mesh_shape("data=2, model=4") == {"data": 2, "model": 4}
    assert mesh_shape_str({"model": 2, "data": 2}) == "data=2,model=2"
    assert mesh_shape_str({}) == "single"
    assert mesh_shape_str({"model": 1}) == "single"
    assert devices_per_replica({"data": 2, "model": 4}) == 8
    with pytest.raises(ValueError):
        parse_mesh_shape("model2")
    with pytest.raises(ValueError):
        parse_mesh_shape("warp=2")
    with pytest.raises(ValueError):
        parse_mesh_shape("model=0")


def test_device_pool_assign_release_repartition():
    pool = DevicePool(list(range(8)))
    assert pool.acquire("r0", 2) == [0, 1]
    assert pool.acquire("r1", 2) == [2, 3]
    assert pool.free == [4, 5, 6, 7]
    # drain r0 → its devices return; the next join re-partitions onto
    # the LOWEST free indices (the freed submesh first)
    assert pool.release("r0") == [0, 1]
    assert pool.acquire("r2", 4) == [0, 1, 4, 5]
    assert pool.assignment() == {"r1": [2, 3], "r2": [0, 1, 4, 5]}
    # exhaustion is a typed error, never a silent unsharded fallback
    with pytest.raises(NoFreeDevices):
        pool.acquire("r3", 4)
    # double-acquire under one name is a bug, not a replacement
    with pytest.raises(ValueError):
        pool.acquire("r1", 1)


def test_plan_replicas():
    specs = plan_replicas({"model": 2}, 3, list(range(8)))
    assert [s.name for s in specs] == ["r0", "r1", "r2"]
    assert [s.devices for s in specs] == [[0, 1], [2, 3], [4, 5]]
    assert specs[0].mesh_shape == "model=2"
    with pytest.raises(NoFreeDevices):
        plan_replicas({"model": 4}, 3, list(range(8)))


# ------------------------------------------ per-replica metric identity


def test_aggregator_replica_labels():
    """N replicas in one process must render DISTINCT per-worker gauge
    rows keyed by the stable `replica` label (the ISSUE 12 metric-
    identity satellite), plus the submesh-size gauge."""
    from dynamo_tpu.llm.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.metrics.component import MetricsAggregator

    agg = MetricsAggregator.__new__(MetricsAggregator)
    agg.namespace = "shardtest"
    agg.worker_metrics = {
        0x10: ForwardPassMetrics(worker_label="r0", mesh_shape="model=2",
                                 mesh_devices=2, request_active_slots=1),
        0x11: ForwardPassMetrics(worker_label="r1", mesh_shape="model=2",
                                 mesh_devices=2, request_active_slots=2),
        0x12: ForwardPassMetrics(),  # unlabeled legacy worker
    }
    agg.hit_rate_isl_blocks = agg.hit_rate_overlap_blocks = 0
    agg.hit_rate_events = 0
    agg.scrape_failures_total = agg.consecutive_scrape_failures = 0
    agg._client = None
    text = agg.render_prometheus()
    assert ('dyn_worker_request_active_slots{namespace="shardtest",'
            'worker="10",replica="r0"} 1') in text
    assert ('dyn_worker_request_active_slots{namespace="shardtest",'
            'worker="11",replica="r1"} 2') in text
    # unlabeled workers keep the legacy label set (no empty replica="")
    assert ('dyn_worker_request_active_slots{namespace="shardtest",'
            'worker="12"} 0') in text
    assert ('dyn_engine_mesh_devices{namespace="shardtest",worker="10",'
            'replica="r0"} 2') in text
    # the labeled families carry the replica label too
    assert 'worker="10",replica="r0",quantile="p99"' in text


# ------------------------------- sharded engine serving path, in-process


def _tiny_ecfg(**over):
    from dynamo_tpu.engine.jax_engine import EngineConfig

    base = dict(page_size=4, num_pages=64, max_batch=4, prefill_chunk=32,
                prefill_buckets=(32,), batch_buckets=(4,),
                page_buckets=(16,))
    base.update(over)
    return EngineConfig(**base)


async def _collect(engine, prompt, n=8, rid=None):
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        eos_token_ids=[])
    toks = []
    ctx = Context(rid) if rid else Context()
    async for out in engine.generate(req, ctx):
        toks.extend(out.token_ids)
        if out.finish_reason:
            break
    return toks


def test_sharded_engine_token_identity_and_fence(run_async):
    """A model=2 submesh engine (2 of the conftest-forced 8 CPU devices)
    serves mixed concurrent traffic token-identical to the unsharded
    control with post_warmup_compiles == 0 — the committed-carry warmup
    variants (the sharding-specific compile-fence fix) under load."""
    import jax
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshSpec

    if len(jax.devices()) < 4:
        pytest.skip("needs the forced multi-device CPU host")
    cfg = ModelConfig.tiny()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 400, int(n)).tolist()
               for n in rng.randint(8, 30, size=5)]

    async def serve(engine):
        outs = await asyncio.gather(
            *(_collect(engine, p, n=6) for p in prompts))
        # second wave: chained windows + prefix hits on a warm engine
        outs += await asyncio.gather(
            *(_collect(engine, p, n=6) for p in prompts[:2]))
        await engine.stop()
        return outs

    control = JaxEngine(cfg, _tiny_ecfg(), seed=3)
    control.warmup()
    want = run_async(serve(control))

    mesh = MeshSpec(model=2).build(jax.devices()[2:4])
    sharded = JaxEngine(cfg, _tiny_ecfg(), seed=3, mesh=mesh,
                        worker_label="r0")
    sharded.warmup()
    got = run_async(serve(sharded))
    assert got == want
    assert sharded.fence.post_warmup_compiles == 0, \
        "compile fence broke under sharding"
    st = sharded.stats()
    assert st["worker_label"] == "r0"
    assert st["mesh_shape"] == "model=2"
    assert st["mesh_devices"] == 2


def test_one_device_replicas_live_on_their_own_devices(run_async):
    """DYN_DP_REPLICAS=N with one chip each: every replica's params, KV
    pool and step outputs live on ITS device (spec.devices[0]), not the
    process default — on one CPU nobody could tell, on virtual devices
    the arrays say where they are. Tokens stay identical to the plain
    engine and the compile fence holds on the non-default device (the
    committed-carry warm-up variants cover one-device meshes too)."""
    import jax
    import numpy as np

    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.serving import (build_replica_engine,
                                             plan_replicas)

    if len(jax.devices()) < 4:
        pytest.skip("needs the forced multi-device CPU host")
    cfg = ModelConfig.tiny()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 400, int(n)).tolist()
               for n in rng.randint(8, 30, size=4)]
    specs = plan_replicas({}, 2, jax.devices()[1:3])
    engines = [build_replica_engine(cfg, _tiny_ecfg(), s, seed=3)
               for s in specs]
    for spec, eng in zip(specs, engines):
        want = {spec.devices[0]}
        assert eng.device == spec.devices[0]
        assert eng.kv_k.devices() == want and eng.kv_v.devices() == want
        assert all(leaf.devices() == want
                   for leaf in jax.tree.leaves(eng.params))
    assert engines[0].kv_k.devices() != engines[1].kv_k.devices()

    async def serve(engine):
        outs = await asyncio.gather(
            *(_collect(engine, p, n=6) for p in prompts))
        outs += await asyncio.gather(
            *(_collect(engine, p, n=6) for p in prompts[:2]))
        # the pool is rebound to each step's output: still on the device
        where = engine.kv_k.devices()
        await engine.stop()
        return outs, where

    control = JaxEngine(cfg, _tiny_ecfg(), seed=3)
    control.warmup()
    want_toks, _ = run_async(serve(control))
    for spec, eng in zip(specs, engines):
        # compiles are process-global: the later warm-ups above already
        # counted on the earlier fences, so judge serving by its delta
        before = eng.fence.post_warmup_compiles
        got, where = run_async(serve(eng))
        assert got == want_toks
        assert where == {spec.devices[0]}
        assert eng.fence.post_warmup_compiles == before


def test_replica_identity_in_cost_block(run_async):
    """The PR 10 per-request cost block names the replica/submesh that
    served the request (the /v1/traces/{rid} surface)."""
    import jax

    from dynamo_tpu.engine.jax_engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshSpec
    from dynamo_tpu.runtime import profiling

    if len(jax.devices()) < 2:
        pytest.skip("needs the forced multi-device CPU host")
    mesh = MeshSpec(model=2).build(jax.devices()[:2])
    engine = JaxEngine(ModelConfig.tiny(), _tiny_ecfg(), seed=0,
                       mesh=mesh, worker_label="r7")
    engine.warmup()

    async def main():
        await _collect(engine, list(range(1, 13)), n=4, rid="shard-rid-1")
        await engine.stop()

    run_async(main())
    cost = profiling.request_attribution("shard-rid-1")
    assert cost is not None
    assert cost["replica"] == "r7"
    assert cost["mesh_shape"] == "model=2"


def test_backend_harvests_remote_cost_after_length_cap(run_async):
    """When the Backend's own token cap fires before the engine's finish
    chunk, the cost block riding that chunk (replica, prefix split —
    everything /v1/traces/{rid} and router calibration need in a
    MULTI-PROCESS deployment) must still be drained and registered.
    Found live by the dynashard cross-process verify: the Backend
    returned at the cap and the remote cost never landed."""
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.protocols.common import (EngineOutput,
                                                 PreprocessedRequest,
                                                 StopConditions)
    from dynamo_tpu.llm.tokenizer import ByteTokenizer
    from dynamo_tpu.runtime import profiling
    from dynamo_tpu.runtime.engine import Context

    cost_block = {"replica": "r1", "mesh_shape": "model=2",
                  "device_hit_blocks": 3, "prompt_blocks": 5}

    class RemoteLikeEngine:
        """Token chunks first, the cost-bearing finish in a SEPARATE
        later chunk — the remote worker wire shape."""

        async def generate(self, request, context):
            tok = ByteTokenizer()
            yield EngineOutput(token_ids=tok.encode("abcd", False))
            await asyncio.sleep(0.01)
            yield EngineOutput(token_ids=[], finish_reason="length",
                               cost=dict(cost_block)).to_dict()

    async def main():
        backend = Backend(RemoteLikeEngine(), ByteTokenizer())
        req = PreprocessedRequest(token_ids=[1],
                                  stop=StopConditions(max_tokens=4,
                                                      ignore_eos=True),
                                  eos_token_ids=[])
        ctx = Context("harvest-rid-1")
        outs = [o async for o in backend.generate(req, ctx)]
        return outs

    outs = run_async(main())
    assert outs[-1].finish_reason == "length"
    # the finish chunk the CLIENT sees carries the harvested cost...
    assert outs[-1].cost == cost_block
    # ...and the frontend-process attribution ring has it too
    assert profiling.request_attribution("harvest-rid-1") == cost_block


def test_backend_skips_harvest_on_stop_string(run_async):
    """A stop-STRING match is host-side only — the engine will not
    finish within the bound, so the Backend must not stall the final
    chunk waiting for a cost block that is not coming."""
    import time

    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.protocols.common import (EngineOutput,
                                                 PreprocessedRequest,
                                                 StopConditions)
    from dynamo_tpu.llm.tokenizer import ByteTokenizer
    from dynamo_tpu.runtime.engine import Context

    class NeverFinishingEngine:
        async def generate(self, request, context):
            tok = ByteTokenizer()
            yield EngineOutput(token_ids=tok.encode("abcSTOP", False))
            while not context.stopped:
                await asyncio.sleep(0.05)

    async def main():
        backend = Backend(NeverFinishingEngine(), ByteTokenizer())
        req = PreprocessedRequest(token_ids=[1],
                                  stop=StopConditions(stop=["STOP"],
                                                      ignore_eos=True),
                                  eos_token_ids=[])
        t0 = time.monotonic()
        outs = [o async for o in backend.generate(req, Context())]
        return outs, time.monotonic() - t0

    outs, dt = run_async(main())
    assert outs[-1].finish_reason == "stop"
    assert outs[-1].cost is None
    assert dt < Backend.COST_HARVEST_BOUND_S, \
        f"stop-string finish stalled {dt:.3f}s waiting for a cost block"


# ------------------------------------------------- fleet sharded scenario


def test_sharded_fleet_scenario(run_async):
    """The planner scales SHARDED replicas: the burst forces a scale-up
    (fresh submeshes partitioned), the post-burst drain releases devices,
    the late join re-partitions onto them — with the SLO met and
    recovery measured (ISSUE 12 tentpole part c)."""
    from dynamo_tpu.fleet.harness import run_scenario
    from dynamo_tpu.fleet.scenarios import get_scenario

    report = run_async(run_scenario(get_scenario("sharded"), seed=0))
    assert report["slo"]["met"], report["phases"]
    assert report["slo"]["time_to_recover_s"] is not None
    ups = [a for a in report["actuations"] if a["action"] == "scale-up"]
    assert ups, "planner never scaled the sharded pool up"
    assert report["workers"]["peak_live"] > 2

    sh = report["sharding"]
    assert sh["devices_per_replica"] == 2
    assert sh["max_devices_in_use"] <= sh["device_pool_size"]
    # replay the timeline: no device may be assigned to two live
    # replicas at once, and every assignment is exactly 2 devices
    live = {}
    reused_released = False
    released_pool = set()
    for ev in sh["timeline"]:
        if ev["event"] == "assign":
            assert len(ev["devices"]) == 2
            for d in ev["devices"]:
                owners = [w for w, devs in live.items() if d in devs]
                assert not owners, \
                    f"device {d} double-assigned: {owners} + {ev}"
            if released_pool & set(ev["devices"]):
                reused_released = True
            live[ev["worker"]] = set(ev["devices"])
        elif ev["event"] == "release":
            released_pool |= set(ev["devices"])
            live.pop(ev["worker"], None)
    releases = [e for e in sh["timeline"] if e["event"] == "release"]
    assert releases, "scale-down never released a submesh"
    assert reused_released, \
        "join never re-partitioned onto released devices"
    # per-replica identity rode the stats plane into the fleet report
    assert report["engine_gauges"]["workers_scraped"] >= 2


# ------------------------------------- the REAL stack e2e (subprocess)

E2E_WORKER = r'''
import asyncio, json, sys

import jax

assert len(jax.devices()) == 8, jax.devices()

import aiohttp
import numpy as np

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.kv_router.router import KvRouter
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.processor import Processor
from dynamo_tpu.llm.worker import serve_token_model
from dynamo_tpu.metrics.component import MetricsAggregator
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.serving import ShardedReplicaSet
from dynamo_tpu.runtime.runtime import DistributedRuntime

CFG = ModelConfig.tiny()
PS = 4


def ecfg():
    # max_batch 8 > the 6-wide concurrent wave: the router's optimistic
    # slot accounting (reset only at scrapes) must never see the single
    # control worker as saturated. 160 pages hold the whole wave plus
    # the warm prefix WITHOUT evictions (the overlap assertion needs the
    # warm request's committed blocks still resident), and the 32-page
    # bucket gives a 128-token grid capacity so the 65-token
    # prefix-extending request is admissible.
    return EngineConfig(page_size=PS, num_pages=160, max_batch=8,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(32,))


WORDS = ("alpha bravo charlie delta echo foxtrot golf hotel india "
         "juliet kilo lima mike oscar papa romeo").split()


def words(rng, n):
    out, ln = [], 0
    while ln < n:
        w = WORDS[rng.randint(0, len(WORDS) - 1)]
        out.append(w)
        ln += len(w) + 1
    return " ".join(out)[:n]


async def drive(http, port, reqs, osl=8):
    texts = {}

    async def one(rid, prompt):
        async with http.post(
                f"http://127.0.0.1:{port}/v1/completions",
                json={"model": "m", "prompt": prompt, "stream": True,
                      "max_tokens": osl, "temperature": 0.0},
                headers={"X-Request-Id": rid}) as resp:
            assert resp.status == 200, (rid, resp.status)
            pieces = []
            async for raw in resp.content:
                line = raw.strip()
                if line == b"data: [DONE]":
                    break
                if not line.startswith(b"data: "):
                    continue
                chunk = json.loads(line[len(b"data: "):])
                for c in chunk.get("choices", []):
                    pieces.append(c.get("text") or "")
            texts[rid] = "".join(pieces)

    await asyncio.gather(*(one(rid, p) for rid, p in reqs))
    return texts


async def trace_cost(http, port, rid):
    async with http.get(f"http://127.0.0.1:{port}/v1/traces/{rid}") as r:
        assert r.status == 200, (rid, r.status)
        return (await r.json()).get("cost") or {}


async def main():
    rng = np.random.RandomState(0)
    base = words(rng, 48)
    reqs = [(f"q-{i:02d}", words(rng, 40 + 4 * (i % 3)))
            for i in range(6)]
    out = {"devices": len(jax.devices())}

    # ---- leg A: unsharded control through the same stack
    drt = await DistributedRuntime.detached()
    mdc = ModelDeploymentCard(name="m", tokenizer_kind="byte",
                              kv_block_size=PS, model_type="completions")
    control = JaxEngine(CFG, ecfg(), seed=0)
    await asyncio.to_thread(control.warmup)
    handle, publisher = await serve_token_model(
        drt, mdc, control, namespace="ns", component="ctrl")
    kvr = KvRouter(drt, "ns", "ctrl", block_size=PS, seed=0)
    await kvr.start(run_loop=False)
    await kvr.scrape_once()
    client = await drt.namespace("ns").component("ctrl") \
        .endpoint("generate_tokens").client()
    service = HttpService()
    service.manager.add_completions_model(
        "m", Processor(mdc, client, kvr).completion)
    await service.start(host="127.0.0.1", port=0)
    async with aiohttp.ClientSession() as http:
        ctrl_texts = await drive(http, service.port, reqs)
    await service.stop()
    await kvr.stop()
    await client.close()
    await publisher.stop()
    await handle.stop()
    await control.stop()
    out["control_compiles"] = control.fence.post_warmup_compiles
    await drt.shutdown()

    # ---- leg B: 2 data-parallel model=2 replicas behind the KV router
    drt = await DistributedRuntime.detached()
    rs = ShardedReplicaSet(CFG, ecfg(), mesh_axes={"model": 2},
                           replicas=2, namespace="ns", component="shard",
                           mdc=mdc, dcp_address=drt.dcp.address, seed=0)
    await rs.start()
    kvr = KvRouter(drt, "ns", "shard", block_size=PS, seed=0)
    await kvr.start(run_loop=False)
    await kvr.scrape_once()
    client = await drt.namespace("ns").component("shard") \
        .endpoint("generate_tokens").client()
    service = HttpService()
    service.manager.add_completions_model(
        "m", Processor(mdc, client, kvr).completion)
    await service.start(host="127.0.0.1", port=0)
    agg = MetricsAggregator(drt, "ns", "shard")
    await agg.start(run_loop=False)

    async with aiohttp.ClientSession() as http:
        shard_texts = await drive(http, service.port, reqs)
        # overlap phase: warm one replica with `base`, settle the event
        # plane, then a base-prefixed request must land on THAT replica
        # and realize a device prefix hit
        warm_texts = await drive(http, service.port, [("warm-0", base)])
        await rs.flush_kv_events()
        await asyncio.sleep(0.05)
        await kvr.scrape_once()
        hit_texts = await drive(
            http, service.port,
            [("hit-0", base + " " + words(rng, 16))])
        warm_cost = await trace_cost(http, service.port, "warm-0")
        hit_cost = await trace_cost(http, service.port, "hit-0")
    await agg.scrape_once()
    render = agg.render_prometheus()

    out["texts_identical"] = (shard_texts == ctrl_texts)
    out["overlap_nonempty"] = bool(warm_texts.get("warm-0")
                                   and hit_texts.get("hit-0"))
    out["n_texts"] = len(shard_texts)
    out["nonempty"] = all(len(t) > 0 for t in shard_texts.values())
    out["per_replica_compiles"] = rs.post_warmup_compiles()
    out["per_replica_served"] = {
        r.name: r.engine.prompt_tokens_total for r in rs.replicas}
    out["mesh_shape"] = rs.mesh_shape
    out["assignment"] = rs.assignment()
    out["warm_replica"] = warm_cost.get("replica")
    out["hit_replica"] = hit_cost.get("replica")
    out["hit_device_hit_blocks"] = hit_cost.get("device_hit_blocks")
    out["hit_router_overlap_blocks"] = hit_cost.get(
        "router_overlap_blocks")
    out["hit_mesh_shape"] = hit_cost.get("mesh_shape")
    out["render_has_r0"] = ',replica="r0"}' in render \
        or ',replica="r0",' in render
    out["render_has_r1"] = ',replica="r1"}' in render \
        or ',replica="r1",' in render
    out["render_mesh_rows"] = render.count("dyn_engine_mesh_devices{")

    await service.stop()
    await agg.stop()
    await kvr.stop()
    await client.close()
    await rs.stop()
    await drt.shutdown()
    print("RESULT " + json.dumps(out))


asyncio.run(main())
'''


@pytest.mark.slow  # heavyweight e2e: tier-1 wall budget (cheaper siblings stay in the gate)
def test_sharded_serving_e2e_subprocess(device_subprocess):
    """The acceptance scenario, subprocess-isolated on a forced-8-device
    CPU host: concurrent HTTP requests through processor + KV router to
    2 mesh-sharded replicas are token-identical to the unsharded
    control, every replica's compile fence reads zero, the overlap hit
    lands on the replica that committed the prefix, and the aggregator
    renders per-replica gauge rows."""
    proc = device_subprocess(E2E_WORKER, devices=8, timeout=600)
    assert proc.returncode == 0, f"e2e worker failed:\n{proc.stdout[-6000:]}"
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])

    assert out["devices"] == 8
    assert out["n_texts"] == 6 and out["nonempty"]
    assert out["texts_identical"], \
        "sharded replicas are not token-identical to the control"
    assert out["control_compiles"] == 0
    assert out["per_replica_compiles"] == {"r0": 0, "r1": 0}, \
        f"compile fence broke under sharding: {out['per_replica_compiles']}"
    # both replicas actually served traffic (router load spreading)
    assert all(v > 0 for v in out["per_replica_served"].values()), \
        out["per_replica_served"]
    assert out["mesh_shape"] == "model=2"
    assert out["assignment"] == {"r0": [0, 1], "r1": [2, 3]}
    # overlap routing: the prefix-extending request landed on the SAME
    # replica that committed the prefix, predicted AND realized
    assert out["overlap_nonempty"], "overlap-phase request error-finished"
    assert out["warm_replica"] in ("r0", "r1")
    assert out["hit_replica"] == out["warm_replica"], \
        (out["warm_replica"], out["hit_replica"])
    assert out["hit_router_overlap_blocks"] > 0
    assert out["hit_device_hit_blocks"] > 0
    assert out["hit_mesh_shape"] == "model=2"
    # per-replica metric identity on the aggregator exposition
    assert out["render_has_r0"] and out["render_has_r1"]
    assert out["render_mesh_rows"] >= 2
