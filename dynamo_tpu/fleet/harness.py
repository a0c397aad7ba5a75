"""The fleet simulator harness: real stack, scripted workers, stepped time.

One :class:`FleetSim` run assembles the **production** serving plane
in-process —

  ``aiohttp client → HttpService → Processor (byte tokenizer) → KvRouter
  → SimWorker endpoints`` over an embedded DCP control plane, with the
  real :class:`MetricsAggregator` scraping stats and the real
  :class:`Planner` deciding scale — and drives it step by step on a
  :class:`VirtualClock`:

  1. apply scripted faults due this step (crash / join / blackout),
  2. inject this step's trace arrivals through the HTTP frontend
     (sequentially: each request is awaited until it is enqueued at a
     worker, so router state evolves in a fixed order),
  3. advance every worker's service model one step (admissions, prefill,
     token releases — all lifecycle stamps in virtual time),
  4. scrape: aggregator then router (manual ``scrape_once``),
  5. tick the planner (virtual clock; advisories stamped in virtual
     time),
  6. actuate: wait for the advisory fanout, let the fleet controller
     spawn/drain workers, sync discovery, optionally reconcile the
     k8s dry-run cluster,
  7. sample fleet state for the scorer and advance the clock.

After the last trace step the loop keeps stepping (no new arrivals)
until every request has drained, then joins the HTTP client tasks and
renders the report. Wall-clock time never enters the report, so a seeded
run is byte-identical across hosts.
"""

from __future__ import annotations

import asyncio
import logging
import random
from typing import Dict, List, Optional, Set

from ..llm.http.service import HttpService
from ..llm.kv_router.router import KvRouter
from ..llm.model_card import ModelDeploymentCard
from ..llm.processor import Processor
from ..metrics.component import MetricsAggregator
from ..parallel.serving import DevicePool, NoFreeDevices
from ..planner.planner import Planner, WatchTarget
from ..planner.policy import PLANNER_KV_PREFIX
from ..runtime import blackbox, revive
from ..runtime.component import Client
from ..runtime.config import env_float
from ..runtime.dcp_client import pack, unpack
from ..runtime.runtime import DistributedRuntime
from ..runtime.slo import GoodputTracker, SloRegistry, collapse_roles
from ..runtime.tasks import spawn_tracked
from .clock import VirtualClock
from .controller import FleetController
from .k8s_dryrun import K8sDryRun
from .report import SloScorer
from .scenarios import Scenario
from .worker import PrefillPool, SimWorker

log = logging.getLogger("dynamo_tpu.fleet")

NAMESPACE = "fleetsim"
COMPONENT = "sim"
MODEL = "sim"
DEPLOYMENT = "fleet-sim"


class FleetSim:
    """One deterministic scenario run. Use :func:`run_scenario`."""

    def __init__(self, scenario: Scenario, seed: int):
        self.scenario = scenario
        self.seed = seed
        self.clock = VirtualClock(scenario.step_seconds)
        self.trace = scenario.traffic(seed)
        self.scorer = SloScorer(self.trace, scenario.slo,
                                scenario.step_seconds)
        self._max_tokens = {r.rid: r.max_tokens for r in self.trace.requests}
        self._enqueued: Dict[str, asyncio.Event] = {
            r.rid: asyncio.Event() for r in self.trace.requests}
        self._client_tasks: List[asyncio.Task] = []
        # dynacache: run-long per-worker (hit_tokens, prompt_tokens) view
        # folded from every scrape (survives drained workers)
        self._cache_seen: Dict[int, tuple] = {}
        # dynashard: the modeled accelerator pool replicas draw their
        # submeshes from (None in unsharded scenarios) + the assignment
        # timeline for the report's `sharding` block
        self.device_pool: Optional[DevicePool] = None
        if scenario.devices_per_replica > 0:
            self.device_pool = DevicePool(
                range(scenario.device_pool_size))
        self._sharding_events: List[dict] = []
        self._max_devices_in_use = 0
        # dynaslo: shared prefill capacity pool (remote_prefill
        # profiles), explicit SLO registry (objectives evaluated on the
        # virtual clock inside the aggregator's SloEngine), worker role
        # assignment sequence, and per-step merged latency snapshots for
        # the report's per-phase per-role quantiles
        self.prefill_pool: Optional[PrefillPool] = (
            PrefillPool() if scenario.profile.remote_prefill else None)
        self.slo_registry = (
            SloRegistry.parse(scenario.slo_objectives,
                              fast_fraction=scenario.slo_fast_fraction,
                              burn_threshold=scenario.slo_burn_threshold)
            if scenario.slo_objectives else SloRegistry())
        self._role_seq = 0
        self._slo_step_hists: Dict[int, dict] = {}
        # dynablack: a deterministic flight recorder on the virtual
        # clock. The harness owns one ShadowRing per worker (fed by the
        # lifecycle callback); on the first fired burn-rate alert the
        # recorder trips, the capture fans out over the blackbox.capture
        # DCP frame, every worker contributes its ring, and the merged
        # bundle lands in the report's `incident` block — byte-identical
        # per seed (virtual time only, canonical sorted serialization)
        self.recorder: Optional[blackbox.FlightRecorder] = None
        self._worker_rings: Dict[str, blackbox.ShadowRing] = {}
        self._bb_workers: Set[str] = set()
        self._incident_bundle: Optional[dict] = None
        if scenario.capture_incident:
            horizon = float(scenario.steps + scenario.drain_steps + 1) \
                * scenario.step_seconds
            self.recorder = blackbox.FlightRecorder(
                window_s=horizon, cooldown_s=0.0, out_dir=None,
                triggers="all", clock=self.clock.now, wall=self.clock.now,
                id_factory=lambda: f"incident-{scenario.name}-{seed}",
                include_process_state=False)
        # dynarevive: SLO-aware shed controller (wired in setup() when
        # the scenario sets shed_queue_depth)
        self.admission: Optional[revive.AdmissionController] = None
        self._discovery_timeout = env_float(
            "DYN_FLEET_DISCOVERY_TIMEOUT") or 10.0
        # wired in setup()
        self.drt: Optional[DistributedRuntime] = None
        self.controller: Optional[FleetController] = None
        self.router: Optional[KvRouter] = None
        self.agg: Optional[MetricsAggregator] = None
        self.planner: Optional[Planner] = None
        self.service: Optional[HttpService] = None
        self.token_client: Optional[Client] = None
        self._http = None
        self._base_url = ""
        self.k8s: Optional[K8sDryRun] = None
        self._k8s_replicas: Optional[int] = None

    # ------------------------------------------------------------- setup

    async def setup(self) -> None:
        sc = self.scenario
        self.drt = await DistributedRuntime.detached()

        self.controller = FleetController(
            self.drt, NAMESPACE, COMPONENT, self._worker_factory)
        await self.controller.start()
        names = await self.controller.spawn_initial(sc.initial_workers)
        for name in names:
            self.scorer.worker_event(self.clock.now(), "spawn", name)

        self.router = KvRouter(self.drt, NAMESPACE, COMPONENT,
                               block_size=sc.block_size,
                               scrape_interval=1.0, seed=self.seed)
        await self.router.start(run_loop=False)

        self.agg = MetricsAggregator(self.drt, NAMESPACE, COMPONENT,
                                     slo_registry=self.slo_registry,
                                     slo_clock=self.clock.now)
        await self.agg.start(run_loop=False)
        if self.recorder is not None:
            # the ISSUE-mandated "last fleet-aggregator scrape" evidence
            self.recorder.add_source("fleet_scrape", self.agg.last_scrape)

        self.planner = Planner(
            self.drt, NAMESPACE,
            [WatchTarget(component=COMPONENT,
                         endpoint="generate_tokens",
                         deployment=DEPLOYMENT if sc.k8s_dry_run else None,
                         service=COMPONENT,
                         config=sc.planner)],
            apply=sc.k8s_dry_run,
            clock=self.clock.now, wall_clock=self.clock.now,
            # dynaslo advisory input: the aggregator's SLO engine burn
            # rates (virtual clock) feed the P/D rebalance policy
            pressure_source=self.agg.slo.pressures)
        await self.planner.start(run_loop=False)

        if sc.k8s_dry_run:
            self.k8s = K8sDryRun(DEPLOYMENT, COMPONENT)
            cr = self.k8s.make_cr(sc.initial_workers)
            await self.drt.dcp.kv_put(f"deployments/{DEPLOYMENT}", pack(cr))

        mdc = ModelDeploymentCard(name=MODEL, tokenizer_kind="byte",
                                  kv_block_size=sc.block_size,
                                  model_type="completions")
        self.token_client = await self.drt.namespace(NAMESPACE) \
            .component(COMPONENT).endpoint("generate_tokens").client()
        processor = Processor(mdc, self.token_client, self.router)

        if sc.shed_queue_depth > 0:
            # dynarevive admission control over the aggregator's view,
            # with a seeded rng so the jittered Retry-After (and thus the
            # report) stays byte-identical per seed
            # window=4: signals refresh once per virtual step (scrape),
            # so a long peak-hold would keep shedding for many steps
            # after a burst clears; the sim never runs the wall-clock
            # sampler task
            self.admission = revive.AdmissionController(
                lambda: revive.signals_from_metrics(
                    self.agg.worker_metrics),
                cfg=revive.ShedConfig(queue_depth=sc.shed_queue_depth),
                rng=random.Random(self.seed ^ 0x5EED),
                window=4)
        self.service = HttpService(admission=self.admission)
        self.service.manager.add_completions_model(MODEL,
                                                   processor.completion)
        await self.service.start(host="127.0.0.1", port=0)
        self._base_url = f"http://127.0.0.1:{self.service.port}"

        import aiohttp

        self._http = aiohttp.ClientSession()

        await self._sync_discovery()
        # warm the scheduler/aggregator view before the first arrivals
        await self._scrape()

    async def _worker_factory(self, name: str) -> SimWorker:
        submesh = None
        if self.device_pool is not None:
            # partition a submesh for the new replica BEFORE any await:
            # an exhausted pool must fail the spawn, not serve unsharded
            submesh = self.device_pool.acquire(
                name, self.scenario.devices_per_replica)
            idx = self.device_pool.assignment()[name]
            self._sharding_events.append(
                {"at": self.clock.now(), "event": "assign",
                 "worker": name, "devices": idx})
            in_use = sum(len(d) for d in
                         self.device_pool.assigned.values())
            self._max_devices_in_use = max(self._max_devices_in_use,
                                           in_use)
            submesh = idx
        drt = await DistributedRuntime.attach(self.drt.dcp.address)
        # dynaslo P/D roles: in remote-prefill scenarios the first
        # initial_prefill_workers spawned are the prefill side, every
        # later spawn (scale-up, join) lands decode-side; the planner's
        # pd policy then re-ratios by flipping roles
        role = "unified"
        if self.prefill_pool is not None:
            role = ("prefill"
                    if self._role_seq < self.scenario.initial_prefill_workers
                    else "decode")
        self._role_seq += 1
        worker = SimWorker(
            drt, NAMESPACE, COMPONENT, name, self.scenario.profile,
            self.scenario.block_size, self.clock.now,
            lambda rid, ev, vt, n=name: self._lifecycle(n, rid, ev, vt),
            submesh=submesh, role=role, prefill_pool=self.prefill_pool)
        await worker.start()
        if self.recorder is not None:
            # one shadow ring per worker, anchored at its (virtual) spawn
            # time; the worker joins the capture fan-out and answers an
            # origin announcement with exactly its own ring
            ring = blackbox.ShadowRing(name, maxlen=2048,
                                       clock=self.clock.now,
                                       wall=self.clock.now)
            self._worker_rings[name] = ring
            await blackbox.attach_dcp(
                worker.drt, NAMESPACE, self.recorder, name,
                rings_fn=lambda n=name: {
                    n: self._worker_rings[n].export()})
            self._bb_workers.add(name)
        return worker

    # --------------------------------------------------------- lifecycle

    def _lifecycle(self, worker: str, rid: str, event: str,
                   vt: float) -> None:
        ring = self._worker_rings.get(worker)
        if ring is not None:
            ring.note(event, rid=rid, vt=vt)
        rec = self.scorer.record(rid)
        if rec is None:
            return
        # first-stamp-wins on arrival/admission/first-token: a resumed
        # request (dynarevive failover re-submits the same rid on a
        # sibling worker) keeps its ORIGINAL latency stamps — TTFT is
        # what the client saw, not what the resume saw
        if event == "enqueued":
            rec.worker = worker
            if rec.arrival_vt is None:
                rec.arrival_vt = vt
            ev = self._enqueued.get(rid)
            if ev is not None:
                ev.set()
        elif event == "admitted":
            if rec.admitted_vt is None:
                rec.admitted_vt = vt
        elif event == "first_token":
            if rec.first_token_vt is None:
                rec.first_token_vt = vt
        elif event == "done":
            rec.done_vt = vt
            rec.tokens_out = self._max_tokens.get(rid, 0)
        elif event == "crashed":
            rec.status = "crashed"

    # ------------------------------------------------------------ inject

    async def _do_request(self, spec) -> None:
        rec = self.scorer.record(spec.rid)
        try:
            body = {"model": MODEL, "prompt": spec.prompt,
                    "stream": True, "max_tokens": spec.max_tokens}
            async with self._http.post(
                    f"{self._base_url}/v1/completions", json=body,
                    headers={"X-Request-Id": spec.rid}) as resp:
                rec.http_status = resp.status
                if resp.status == 503:
                    # admission control answered an early 503 with
                    # Retry-After: shed, not failed — the client was
                    # told when to come back
                    rec.status = "shed"
                    return
                if resp.status != 200:
                    rec.status = "failed"
                    return
                errored = False
                async for raw in resp.content:
                    line = raw.strip()
                    if line.startswith(b"event: error"):
                        errored = True
                    elif line == b"data: [DONE]":
                        break
                if rec.status in ("pending", "crashed"):
                    if errored:
                        rec.status = "failed"
                    else:
                        # a "crashed" record whose stream still finished
                        # clean is a dynarevive mid-stream failover: the
                        # worker died, the resume completed the stream
                        rec.resumed = rec.status == "crashed"
                        rec.status = "ok"
        except Exception:
            log.debug("client request %s failed", spec.rid, exc_info=True)
            if rec.status in ("pending", "crashed"):
                rec.status = "failed"

    async def _inject(self, step: int) -> None:
        for spec in self.trace.at(step):
            task = spawn_tracked(self._do_request(spec),
                                 name=f"fleet-req-{spec.rid}")
            self._client_tasks.append(task)
            # sequential admission: wait until the request is enqueued at
            # a worker (or failed fast) before injecting the next one, so
            # router decisions replay in a fixed order
            ev = self._enqueued[spec.rid]
            waiter = spawn_tracked(ev.wait(),
                                   name=f"fleet-enq-{spec.rid}")
            done, _pending = await asyncio.wait(
                {task, waiter}, timeout=self._discovery_timeout,
                return_when=asyncio.FIRST_COMPLETED)
            waiter.cancel()
            if not done:
                raise RuntimeError(
                    f"request {spec.rid} neither enqueued nor failed "
                    f"within {self._discovery_timeout}s — sim wedged")

    # ----------------------------------------------------------- helpers

    def _workers_in_order(self) -> List[SimWorker]:
        return list(self.controller.workers.values())

    async def _advance_workers(self) -> None:
        if self.prefill_pool is not None:
            # shared prefill capacity this step = the prefill-role side
            # of the fleet (role flips change this one step later — the
            # actuation latency the rebalance loop pays)
            capacity = sum(
                self.scenario.profile.prefill_tokens_per_step
                for w in self.controller.live
                if w.model.role == "prefill")
            self.prefill_pool.step(capacity)
        for worker in self._workers_in_order():
            events = worker.model.step()
            if events and not worker.draining:
                await worker.publish_kv_events(events)
        retired = await self.controller.retire_idle_drained()
        for name in retired:
            self.scorer.worker_event(self.clock.now(), "removed", name)
            if self.device_pool is not None:
                # a retired replica's submesh returns to the pool — the
                # next join re-partitions onto these devices
                devs = self.device_pool.assignment().get(name, [])
                self.device_pool.release(name)
                self._sharding_events.append(
                    {"at": self.clock.now(), "event": "release",
                     "worker": name, "devices": devs})
        # let woken handlers push their token frames down the wire
        await asyncio.sleep(0)

    async def _scrape(self) -> None:
        try:
            await self.agg.scrape_once()
        except Exception:
            log.exception("aggregator scrape failed")
        # dynacache: fold each scrape's per-worker hit/prompt totals into
        # a run-long view — a drained worker's counters leave the
        # aggregator with it, but its realized hits still happened (the
        # hot-tenant worker is often the one newest-first scale-down
        # retires). Counters are per-worker monotonic, so overwrite.
        for wid, m in self.agg.worker_metrics.items():
            self._cache_seen[wid] = (m.prefix_hit_tokens_total,
                                     m.prompt_tokens_total)
        try:
            await self.router.scrape_once()
        except Exception:
            log.exception("router scrape failed")

    async def _actuate(self) -> None:
        await self.controller.wait_advisories(len(self.planner.advisories))
        actions = await self.controller.reconcile()
        vt = self.clock.now()
        for act in actions:
            self.scorer.actuation(vt, act["action"], act["desired"],
                                  act["workers"])
            for name in act["workers"]:
                if act["action"] == "scale-up":
                    self.scorer.worker_event(vt, "spawn", name)
                elif act["action"] == "scale-down":
                    self.scorer.worker_event(vt, "drain", name)
                elif act["action"].startswith("pd-shift"):
                    # dynaslo role flip: record it on the worker
                    # timeline (no discovery churn — the flip is a
                    # stats-plane label the scheduler honors next scrape)
                    self.scorer.worker_event(vt, act["action"], name)
        if actions:
            await self._sync_discovery()
        if self.k8s is not None:
            raw = await self.drt.dcp.kv_get(f"deployments/{DEPLOYMENT}")
            if raw is not None:
                replicas = self.k8s.reconcile(unpack(raw))
                if replicas is not None:
                    self._k8s_replicas = replicas

    def _observers(self) -> List[Client]:
        obs = [self.token_client, self.router.client, self.agg._client]
        obs.extend(self.planner._clients.values())
        return [c for c in obs if c is not None]

    async def _sync_discovery(self) -> None:
        """Block (wall-bounded) until every client's discovery view shows
        the live workers and has dropped the drained ones."""
        present: Set[int] = {w.instance_id for w in self.controller.live}
        absent: Set[int] = {
            w.instance_id for w in self.controller.workers.values()
            if w.draining}
        absent |= {w.instance_id for w in self.controller.retired}
        deadline = asyncio.get_running_loop().time() \
            + self._discovery_timeout
        while asyncio.get_running_loop().time() < deadline:
            views = [set(c.instances) for c in self._observers()]
            if all(present <= v and not (absent & v) for v in views):
                return
            await asyncio.sleep(0.005)
        raise RuntimeError("discovery views did not converge "
                           f"(want +{present} -{absent})")

    async def _apply_faults(self, step: int) -> None:
        for fault in [f for f in self.scenario.faults if f.step == step]:
            vt = self.clock.now()
            if fault.kind == "crash":
                live = self.controller.live
                if live:
                    worker = live[min(fault.arg, len(live) - 1)]
                    await worker.crash()
                    self.scorer.worker_event(vt, "crash", worker.name)
                    if self.recorder is not None:
                        self.recorder.note("sim-harness", "fault",
                                           fault="crash", step=step,
                                           name=worker.name, vt=vt)
            elif fault.kind == "drain":
                # rolling-restart wave: graceful drain of one live
                # worker — discovery out, in-flight finishes, the
                # router must never route to it again (dynarevive)
                live = self.controller.live
                if live:
                    worker = live[min(fault.arg, len(live) - 1)]
                    # sim-model lifecycle drain, not a socket drain
                    await worker.drain()  # dynalint: disable=unbounded-await
                    self.scorer.worker_event(vt, "drain", worker.name)
                    await self._sync_discovery()
            elif fault.kind == "join":
                try:
                    name = await self.controller._spawn()
                except NoFreeDevices:
                    # the modeled accelerator pool is the hard capacity
                    # limit: a join with no free submesh is DENIED, not
                    # served unsharded (recorded for the report)
                    self._sharding_events.append(
                        {"at": vt, "event": "join_denied_no_devices",
                         "worker": None, "devices": []})
                    self.scorer.worker_event(vt, "join_denied", "*")
                    continue
                self.scorer.worker_event(vt, "join", name)
                await self._sync_discovery()
            elif fault.kind == "blackout_start":
                for worker in self.controller.live:
                    worker.set_blackout(True)
                self.scorer.worker_event(vt, "blackout_start", "*")
            elif fault.kind == "blackout_end":
                for worker in self.controller.live:
                    worker.set_blackout(False)
                self.scorer.worker_event(vt, "blackout_end", "*")
            elif fault.kind in ("flap_start", "flap_end"):
                live = self.controller.live
                if live:
                    worker = live[min(fault.arg, len(live) - 1)]
                    worker.set_blackout(fault.kind == "flap_start")
                    self.scorer.worker_event(vt, fault.kind, worker.name)

    async def _capture_incident(self, alert: dict, step: int) -> None:
        """First fired burn-rate alert: trip the recorder, broadcast the
        capture over DCP, and wall-bounded-wait until every subscribed
        worker's ring has merged into the bundle (the wait is for
        determinism: the bundle must hold the same ring set every run)."""
        rec = self.recorder
        rec.note("sim-harness", "alert", step=step, **alert)
        bundle = rec.trip("slo_burn_rate", alert)
        if bundle is None:
            return
        await blackbox.broadcast_capture(self.drt, NAMESPACE, bundle,
                                         worker_label="sim-harness")
        want = set(self._bb_workers)
        deadline = asyncio.get_running_loop().time() \
            + self._discovery_timeout
        while not want <= set(bundle["workers"]):
            if asyncio.get_running_loop().time() >= deadline:
                raise RuntimeError(
                    "incident contributions did not converge "
                    f"(have {sorted(bundle['workers'])}, want "
                    f"{sorted(want)})")
            await asyncio.sleep(0.005)
        self._incident_bundle = bundle

    def _fleet_sample(self) -> None:
        waiting = sum(len(w.model.queue)
                      for w in self._workers_in_order())
        active = sum(len(w.model.active)
                     for w in self._workers_in_order())
        self.scorer.sample_step(self.clock.now(), waiting, active,
                                len(self.controller.live))

    # -------------------------------------------------------------- run

    async def _step(self, step: int, *, inject: bool = True) -> None:
        await self._apply_faults(step)
        if inject:
            await self._inject(step)
        await self._advance_workers()
        await self._scrape()
        # dynaslo: per-step fleet-merged latency snapshot (fresh
        # Histogram objects each call) — the report diffs these at phase
        # boundaries into per-phase per-role quantiles
        self._slo_step_hists[step] = self.agg.merged_latency()
        if self.recorder is not None and self._incident_bundle is None:
            fired = [e for e in self.agg.slo.alert_events
                     if e["state"] == "fired"]
            if fired:
                await self._capture_incident(fired[0], step)
        await self.planner.tick()
        await self._actuate()
        self._fleet_sample()
        self.clock.advance()

    async def run(self) -> dict:
        sc = self.scenario
        await self.setup()
        try:
            for step in range(sc.steps):
                await self._step(step)
            # drain: no arrivals, keep stepping until all requests settle
            for extra in range(sc.drain_steps):
                if self._drained():
                    break
                await self._step(sc.steps + extra, inject=False)
            await self._join_clients()
            return await self._report()
        finally:
            await self.teardown()

    def _drained(self) -> bool:
        return all(r.status != "pending" or r.done_vt is not None
                   for r in self.scorer.records.values())

    async def _join_clients(self) -> None:
        if self._client_tasks:
            await asyncio.wait(self._client_tasks, timeout=30.0)

    async def _report(self) -> dict:
        advisories = [a.to_dict() for a in self.planner.advisories]
        stored = await self.drt.dcp.kv_get_prefix(PLANNER_KV_PREFIX)
        extra = {
            "router": self.router.stats(),
            "stats_evictions": {
                "aggregator": self.agg._client.evicted_ids(),
                "router": self.router.client.evicted_ids(),
            },
            # circuit-breaker evidence for the breaker scenario: how many
            # times each collector's stats-plane breakers opened over the
            # run, and which instances are open at the end
            "breakers": {
                "aggregator": {
                    "opened_total":
                        self.agg._client.breakers.opened_total("stats"),
                    "open_now": self.agg._client.evicted_ids(),
                },
                "router": {
                    "opened_total":
                        self.router.client.breakers.opened_total("stats"),
                    "open_now": self.router.client.evicted_ids(),
                },
            },
            "advisories_in_kv": len(stored),
            # dynaprof plane: the new dyn_engine_*/dyn_runtime_* gauges
            # as scraped from worker ForwardPassMetrics at run end, so
            # fleet scenarios regression-gate scheduler overhead next to
            # the SLO verdicts (virtual-state values only: deterministic)
            "engine_gauges": self._engine_gauges(),
            # dynacache plane: the router's PREDICTED overlap hit rate
            # next to the workers' REALIZED (engine-side) hit rate, so
            # scenarios like hot-tenant can assert both views agree
            "cache": self._cache_block(),
        }
        if self.admission is not None or any(
                f.kind in ("crash", "drain") for f in self.scenario.faults):
            # dynarevive plane: mid-stream failover + drain + shed story
            # of the run (scorer-derived counts only — process-global
            # revive counters never enter the report, keeping seeded
            # runs byte-identical across processes)
            recs = self.scorer.records.values()
            extra["failover"] = {
                "resumed_requests": len([r for r in recs if r.resumed]),
                "still_crashed": len([r for r in recs
                                      if r.status == "crashed"]),
                "shed_requests": len([r for r in recs
                                      if r.status == "shed"]),
                "shed_by_signal": (dict(sorted(
                    self.admission.shed_by_signal.items()))
                    if self.admission else {}),
                "drains": [e for e in self.scorer.worker_events
                           if e["event"] == "drain"],
            }
        if self.slo_registry.objectives or self.prefill_pool is not None:
            extra["dynaslo"] = self._dynaslo_block()
        if self.recorder is not None:
            # dynablack plane: the merged incident bundle (or the armed-
            # but-untripped recorder state) — virtual-time values only
            extra["incident"] = (
                self._incident_bundle if self._incident_bundle is not None
                else {"captured": False,
                      "captures_total": self.recorder.captures_total})
        if self.device_pool is not None:
            # dynashard plane: the submesh-assignment story of the run —
            # every partition/release with its virtual timestamp, the
            # final assignment, and the peak device usage (all modeled
            # state: byte-identical per seed)
            extra["sharding"] = {
                "device_pool_size": self.scenario.device_pool_size,
                "devices_per_replica": self.scenario.devices_per_replica,
                "assignment": self.device_pool.assignment(),
                "timeline": self._sharding_events,
                "max_devices_in_use": self._max_devices_in_use,
            }
        if self.k8s is not None:
            extra["k8s_dry_run"] = {
                "deployment_replicas": self._k8s_replicas,
                "objects": sorted(f"{k}/{n}" for (k, _ns, n)
                                  in self.k8s.kube.store),
            }
        return self.scorer.report(
            scenario=self.scenario.name, seed=self.seed,
            steps=self.scenario.steps, advisories=advisories,
            disturb_end_step=self.scenario.disturb_end_step, extra=extra)

    def _engine_gauges(self) -> dict:
        """Fleet-level rollup of the dynaprof ForwardPassMetrics gauges
        from the final aggregator scrape (sorted per-worker rows keep the
        JSON byte-stable across runs)."""
        wm = [m for _, m in sorted(self.agg.worker_metrics.items())]
        return {
            "workers_scraped": len(wm),
            "inflight_sequences": sum(m.request_active_slots for m in wm),
            "admission_queue_depth": sum(m.num_requests_waiting
                                         for m in wm),
            "kv_free_blocks_min": min((m.kv_free_blocks for m in wm),
                                      default=0),
            "loop_lag_p99_seconds_max": max(
                (m.loop_lag_p99_seconds for m in wm), default=0.0),
            "queue_wait_seconds_total": round(
                sum(m.queue_wait_seconds_total for m in wm), 6),
        }

    def _phase_role_quantiles(self) -> Dict[str, dict]:
        """Per-phase, per-role latency quantiles from the mergeable
        histograms: phase window = snapshot at the phase's last step
        minus the snapshot before its first (the FINAL phase extends
        through the drain tail so late observations land somewhere).
        Counters are monotonic, so diffs are exact."""
        steps_rec = sorted(self._slo_step_hists)
        if not steps_rec:
            return {}
        last = steps_rec[-1]
        empty: Dict[str, dict] = {}
        out: Dict[str, dict] = {}
        phases = self.trace.phases
        for i, phase in enumerate(phases):
            top_step = last if i == len(phases) - 1 \
                else min(phase.end - 1, last)
            top = self._slo_step_hists.get(top_step, empty)
            base = self._slo_step_hists.get(phase.start - 1, empty)
            rows: Dict[str, dict] = {}
            for role in sorted(top):
                per = {}
                for metric, h in sorted(top[role].items()):
                    b = base.get(role, {}).get(metric)
                    d = h.diff(b) if b is not None else h
                    if d.count == 0:
                        continue
                    per[metric] = {"p50": d.quantile(0.5),
                                   "p95": d.quantile(0.95),
                                   "p99": d.quantile(0.99),
                                   "count": d.count}
                if per:
                    rows[role] = per
            out[phase.name] = rows
        return out

    def _dynaslo_block(self) -> dict:
        """The dynaslo story of the run: objective evaluation + alert
        timeline off the aggregator's SLO engine (virtual clock),
        goodput over the request records, per-phase per-role quantiles,
        the prefill pool's totals, and the post-rebalance verdict the
        pd_rebalance scenario regression-gates (final-phase TTFT p95 and
        ITL p99 vs their objective thresholds)."""
        gp = GoodputTracker(self.slo_registry)
        for rec in self.scorer.records.values():
            if rec.status != "ok":
                gp.observe_failed()
                continue
            metrics: Dict[str, float] = {}
            if rec.ttft is not None:
                metrics["ttft"] = rec.ttft
            if rec.queue_wait is not None:
                metrics["queue_wait"] = rec.queue_wait
            if rec.done_vt is not None and rec.arrival_vt is not None:
                metrics["e2e"] = rec.done_vt - rec.arrival_vt
            gp.observe_request(metrics)
        phase_q = self._phase_role_quantiles()
        block = {
            "registry": self.slo_registry.to_dict(),
            "evaluation": self.agg.slo.evaluate(),
            "alerts": list(self.agg.slo.alert_events),
            "pressures": self.agg.slo.pressures(),
            "goodput": gp.snapshot(),
            "phase_role_quantiles": phase_q,
        }
        if self.prefill_pool is not None:
            block["prefill_pool"] = {
                "enqueued": self.prefill_pool.enqueued_total,
                "completed": self.prefill_pool.completed_total,
                "final_depth": self.prefill_pool.depth,
            }
            block["roles_final"] = {
                name: w.model.role
                for name, w in sorted(self.controller.workers.items())}
        # post-rebalance verdict: final-phase quantiles (role-collapsed)
        # against the ttft/itl objective thresholds
        if self.trace.phases and phase_q:
            final = self.trace.phases[-1].name
            rows = phase_q.get(final, {})
            hists: Dict[str, dict] = {}
            for role, per in rows.items():
                hr = {}
                for m in per:
                    h = self._hist_for(final, role, m)
                    if h is not None:
                        hr[m] = h
                hists[role] = hr
            merged = collapse_roles(hists)
            verdict: Dict[str, object] = {"phase": final}
            for metric, q, tag in (("ttft", 0.95, "ttft_p95_s"),
                                   ("itl", 0.99, "itl_p99_s")):
                h = merged.get(metric)
                val = h.quantile(q) if h is not None and h.count else None
                verdict[tag] = val
                objs = self.slo_registry.for_metric(metric)
                if objs:
                    verdict[f"{metric}_met"] = (
                        val is not None and val <= objs[0].threshold_s)
            block["post_rebalance"] = verdict
        return block

    def _hist_for(self, phase_name: str, role: str, metric: str):
        """The final-phase window histogram for (role, metric) — same
        diff _phase_role_quantiles renders quantiles from."""
        steps_rec = sorted(self._slo_step_hists)
        last = steps_rec[-1]
        phase = next(p for p in self.trace.phases if p.name == phase_name)
        top = self._slo_step_hists[last].get(role, {}).get(metric)
        base = self._slo_step_hists.get(
            phase.start - 1, {}).get(role, {}).get(metric)
        return top.diff(base) if (top is not None and base is not None) \
            else top

    def _cache_block(self) -> dict:
        """Predicted (router overlap scoring) vs realized (worker-side
        stored-chain replay) hit rates, folded over every scrape of the
        run so drained workers' totals still count — sorted per-worker
        rows keep the JSON byte-stable across runs."""
        rows = sorted(self._cache_seen.items())
        hits = sum(h for _, (h, _p) in rows)
        prompts = sum(p for _, (_h, p) in rows)
        rstats = self.router.stats()
        return {
            "router_predicted_hit_rate": rstats["avg_hit_rate"],
            "engine_realized_hit_rate": hits / max(prompts, 1),
            "per_worker_realized": [h / max(p, 1)
                                    for _, (h, p) in rows],
        }

    async def teardown(self) -> None:
        if self._http is not None:
            await self._http.close()
        for task in self._client_tasks:
            task.cancel()
        if self.service is not None:
            await self.service.stop()
        if self.planner is not None:
            await self.planner.stop()
        if self.agg is not None:
            await self.agg.stop()
        if self.router is not None:
            await self.router.stop()
        if self.token_client is not None:
            await self.token_client.close()
        if self.controller is not None:
            await self.controller.teardown()
            for w in self.controller.retired:
                # runtimes of drained workers were already shut down in
                # retire_idle_drained; nothing further
                pass
        if self.drt is not None:
            await self.drt.shutdown()


async def run_scenario(scenario: Scenario, seed: int) -> dict:
    """Run one scenario to completion and return its report dict."""
    return await FleetSim(scenario, seed).run()
