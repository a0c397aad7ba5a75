"""Scripted fleet workers: a real serving endpoint over a virtual-time
service model.

Each :class:`SimWorker` owns its own :class:`DistributedRuntime`
attachment (own lease → own instance id, exactly like a separate worker
process) and serves the token-level ``generate_tokens`` endpoint the real
processor/KV-router path calls into. What it does *not* do is run a model:
service is simulated by :class:`SimEngineModel`, a discrete queueing model
advanced one virtual step at a time by the harness —

- arrivals enter a FIFO queue (``num_requests_waiting``),
- up to ``slots`` requests are in service; each consumes
  ``prefill_steps`` steps of prefill, then releases
  ``tokens_per_step`` output tokens per step until its budget is spent,
- every lifecycle stamp (arrival, admission, first token, done) is a
  virtual-clock value written synchronously inside ``step()``,

so latency percentiles are exact functions of the trace + fleet size, not
of host speed. The endpoint handler bridges the model to the real wire:
it parks on the request's event queue and yields ``EngineOutput`` frames
as the model releases tokens.

Fault hooks (scenario-scripted):

- ``crash()``   — drop the request-plane subscriptions *without*
  deregistering discovery (the lease keepalive is still running, exactly
  like a wedged process), and error every in-flight stream. The stale
  instance record is what the Client eviction path must clean up.
- ``blackout(on)`` — the stats handler raises, simulating a scrape
  blackout while serving continues.
- ``drain()``  — graceful scale-down: deregister from discovery, finish
  what's in flight, then shut down.
"""

from __future__ import annotations

import asyncio
import logging
from collections import deque
from dataclasses import dataclass
from typing import Callable, Deque, List, Optional, Tuple

from ..engine.kv_manager import chain_hashes
from ..llm.kv_router.protocols import (KV_EVENT_SUBJECT, ForwardPassMetrics,
                                       KvCacheEventWire)
from ..llm.protocols.common import EngineOutput, PreprocessedRequest
from ..runtime.dcp_client import pack
from ..runtime.engine import Context
from ..runtime.runtime import DistributedRuntime
from ..runtime.slo import LatencyRecorder

log = logging.getLogger("dynamo_tpu.fleet.worker")

_CRASH = object()   # sentinel pushed into request event queues on crash


@dataclass(frozen=True)
class WorkerProfile:
    """Scripted service capacity of one worker."""

    slots: int = 4                  # concurrent in-service requests
    total_slots: int = 32           # advertised slot capacity (saturation)
    prefill_steps: int = 1          # virtual steps of prefill per request
    tokens_per_step: int = 8        # decode tokens released per step
    kv_total_blocks: int = 4096
    publish_kv_events: bool = True  # feed the router's radix index
    # dynaslo P/D modeling (all default-off: the legacy scenarios'
    # behavior is bit-identical when unset):
    # remote_prefill — admitted requests enqueue their prompt into the
    # harness's shared PrefillPool instead of counting local
    # prefill_steps; the first token releases once the pool has
    # processed the prompt (disagg: prefill capacity is fleet-shared).
    remote_prefill: bool = False
    # tokens of shared prefill capacity ONE prefill-role worker
    # contributes to the pool per virtual step
    prefill_tokens_per_step: int = 0
    # shared decode-token budget per worker per step, split evenly over
    # in-decode requests (each still capped by tokens_per_step) — decode
    # contention now shows up as ITL, not just queue wait. 0 = legacy
    # fixed tokens_per_step per request.
    decode_budget_per_step: int = 0


class _SimRequest:
    """One request inside the model."""

    __slots__ = ("rid", "token_ids", "max_tokens", "prompt_tokens",
                 "prefill_left", "tokens_left", "events", "finished",
                 "arrival_vt", "pool_left", "pool_done", "last_tok_vt")

    def __init__(self, rid: str, token_ids: List[int], max_tokens: int,
                 prefill_steps: int):
        self.rid = rid
        self.token_ids = token_ids
        self.prompt_tokens = len(token_ids)
        self.max_tokens = max_tokens
        self.prefill_left = max(prefill_steps, 1)
        self.tokens_left = max(max_tokens, 1)
        self.events: asyncio.Queue = asyncio.Queue()
        self.finished = False
        # dynaslo: virtual-time stamps for the worker-side latency
        # histograms + shared-prefill-pool state (remote_prefill mode)
        self.arrival_vt: float = 0.0
        self.pool_left: int = 0
        self.pool_done = False
        self.last_tok_vt: Optional[float] = None


class PrefillPool:
    """Shared prefill capacity (dynaslo P/D modeling): prefill-role
    workers pool their ``prefill_tokens_per_step`` and prompts drain
    FIFO — exactly the disagg shared-queue shape, so shifting a worker
    decode→prefill raises fleet prefill throughput one step later."""

    def __init__(self) -> None:
        self.jobs: Deque[_SimRequest] = deque()
        self.enqueued_total = 0
        self.completed_total = 0

    def enqueue(self, req: _SimRequest) -> None:
        req.pool_left = max(req.prompt_tokens, 1)
        self.jobs.append(req)
        self.enqueued_total += 1

    @property
    def depth(self) -> int:
        return len(self.jobs)

    def backlog_tokens(self) -> int:
        return sum(r.pool_left for r in self.jobs)

    def step(self, capacity: int) -> None:
        """Drain up to ``capacity`` prompt tokens FIFO; jobs whose
        request finished meanwhile (crash/abandon) are skipped free."""
        while self.jobs and capacity > 0:
            job = self.jobs[0]
            if job.finished:
                self.jobs.popleft()
                continue
            take = min(capacity, job.pool_left)
            job.pool_left -= take
            capacity -= take
            if job.pool_left <= 0:
                job.pool_done = True
                self.jobs.popleft()
                self.completed_total += 1


class SimEngineModel:
    """Discrete-time queueing model behind one worker endpoint."""

    def __init__(self, name: str, profile: WorkerProfile, block_size: int,
                 clock: Callable[[], float],
                 on_lifecycle: Callable[[str, str, float], None],
                 role: str = "unified",
                 pool: Optional[PrefillPool] = None):
        """``clock`` is the shared virtual clock; ``on_lifecycle(rid,
        event, vt)`` with events ``enqueued|admitted|first_token|done|
        crashed`` feeds the scorer."""
        self.name = name
        self.profile = profile
        self.block_size = block_size
        self.clock = clock
        self.on_lifecycle = on_lifecycle
        # dynashard: stable per-replica identity + modeled submesh size,
        # riding the same ForwardPassMetrics fields the real sharded
        # engine exports (the aggregator's `replica` gauge label)
        self.worker_label = name
        self.mesh_devices = 1
        # dynaslo: serving role + per-role latency histograms in virtual
        # time (deterministic), riding the same FPM fields as the real
        # engine; the shared PrefillPool models disagg prefill capacity
        self.role = role
        self.pool = pool
        self.latency = LatencyRecorder(role)
        self.queue: Deque[_SimRequest] = deque()
        self.active: List[_SimRequest] = []
        self.crashed = False
        self.blackout = False
        self.served_total = 0
        self._stored_blocks: int = 0   # modeled resident cache blocks
        # dynacache: modeled engine-side prefix cache — the set of block
        # hashes this worker has stored; a new prompt's REALIZED hit is
        # its longest leading chain already present. Virtual-state only,
        # so seeded reports stay byte-identical.
        self._stored_hashes: set = set()
        self.realized_hit_blocks: int = 0
        self.prompt_blocks_total: int = 0

    # ------------------------------------------------------------ intake

    def set_role(self, role: str) -> None:
        """dynaslo P/D rebalance: flip this worker's serving role live.
        The KV scheduler stops/starts offering it decode work from the
        next scrape; in-flight requests run to completion; latency
        observations before the flip stay attributed to the old role."""
        self.role = role
        self.latency.role = role

    def submit(self, rid: str, token_ids: List[int],
               max_tokens: int) -> _SimRequest:
        if self.crashed:
            raise RuntimeError(f"worker {self.name} crashed")
        req = _SimRequest(rid, token_ids, max_tokens,
                          self.profile.prefill_steps)
        req.arrival_vt = self.clock()
        self.queue.append(req)
        self.on_lifecycle(rid, "enqueued", self.clock())
        return req

    def abandon(self, req: _SimRequest) -> None:
        """Client went away mid-stream: free the slot/queue entry."""
        if req in self.active:
            self.active.remove(req)
        elif req in self.queue:
            self.queue.remove(req)

    # ------------------------------------------------------------- step

    def step(self) -> List[Tuple[List[int], Optional[int]]]:
        """Advance one virtual step at the clock's current time. Returns
        the KV 'stored' events (block-hash chains) for prompts admitted
        this step, for the harness to publish on the bus."""
        vt = self.clock()
        if self.crashed:
            return []
        kv_events: List[Tuple[List[int], Optional[int]]] = []
        # admit from the FIFO into free slots
        while self.queue and len(self.active) < self.profile.slots:
            req = self.queue.popleft()
            self.active.append(req)
            self.on_lifecycle(req.rid, "admitted", vt)
            self.latency.observe("queue_wait", vt - req.arrival_vt)
            if self.profile.remote_prefill and self.pool is not None:
                # disagg shape: the prompt's prefill is fleet-shared —
                # this request decodes once the pool has chewed through
                # its prompt tokens (FIFO across all decode workers)
                self.pool.enqueue(req)
            if self.profile.publish_kv_events and req.token_ids:
                hashes = chain_hashes(req.token_ids, self.block_size)
                if hashes:
                    # realized engine-side hit: the longest leading chain
                    # already stored on THIS worker (the router's overlap
                    # prediction is scored against this in the report's
                    # cache block)
                    hit = 0
                    for h in hashes:
                        if h not in self._stored_hashes:
                            break
                        hit += 1
                    self.realized_hit_blocks += hit
                    self.prompt_blocks_total += len(hashes)
                    self._stored_hashes.update(hashes)
                    kv_events.append((hashes, None))
                    self._stored_blocks = min(
                        self._stored_blocks + len(hashes),
                        self.profile.kv_total_blocks)
        # advance in-service requests: pass 1 resolves prefill (local
        # countdown, or the shared pool's verdict in remote mode) and
        # collects the decode-ready set
        in_decode: List[_SimRequest] = []
        for req in list(self.active):
            if self.profile.remote_prefill and self.pool is not None:
                if not req.pool_done:
                    continue          # prompt still in the shared pool
                if req.prefill_left > 0:
                    # pool finished since last step → first-token boundary
                    req.prefill_left = 0
                    self.on_lifecycle(req.rid, "first_token", vt)
                    self.latency.observe("ttft", vt - req.arrival_vt)
            else:
                if req.prefill_left > 0:
                    req.prefill_left -= 1
                    if req.prefill_left > 0:
                        continue
                    # prefill completed this step → first token batch
                    self.on_lifecycle(req.rid, "first_token", vt)
                    self.latency.observe("ttft", vt - req.arrival_vt)
            in_decode.append(req)
        # pass 2 releases decode tokens. Legacy (budget 0): every request
        # gets its full tokens_per_step. Budget mode: the worker's shared
        # decode throughput splits evenly (deterministic remainder order),
        # still per-request capped — contention degrades ITL, the signal
        # the P/D rebalance loop must NOT regress.
        budget = self.profile.decode_budget_per_step
        if budget > 0 and in_decode:
            base, rem = divmod(budget, len(in_decode))
            grants = [base + (1 if i < rem else 0)
                      for i in range(len(in_decode))]
        else:
            grants = [self.profile.tokens_per_step] * len(in_decode)
        for req, grant in zip(in_decode, grants):
            n = min(self.profile.tokens_per_step, grant, req.tokens_left)
            if n <= 0:
                continue              # budget-starved this step
            if req.last_tok_vt is not None:
                # n per-token gaps of (gap / n): window size never skews
                # the per-token ITL distribution
                self.latency.observe(
                    "itl", (vt - req.last_tok_vt) / n, n)
            req.last_tok_vt = vt
            req.tokens_left -= n
            done = req.tokens_left <= 0
            req.events.put_nowait((n, "length" if done else None))
            if done:
                req.finished = True
                self.active.remove(req)
                self.served_total += 1
                self.on_lifecycle(req.rid, "done", vt)
                self.latency.observe("e2e", vt - req.arrival_vt)
        return kv_events

    # ------------------------------------------------------------ faults

    def crash(self) -> None:
        vt = self.clock()
        self.crashed = True
        for req in list(self.active) + list(self.queue):
            req.events.put_nowait(_CRASH)
            self.on_lifecycle(req.rid, "crashed", vt)
        self.active.clear()
        self.queue.clear()

    # ------------------------------------------------------------- stats

    @property
    def idle(self) -> bool:
        return not self.active and not self.queue

    def stats(self) -> dict:
        if self.blackout:
            raise RuntimeError(f"scrape blackout on {self.name}")
        p = self.profile
        inflight_blocks = sum(
            (r.prompt_tokens + self.block_size - 1) // self.block_size
            for r in self.active)
        blocks = min(inflight_blocks + self._stored_blocks,
                     p.kv_total_blocks)
        return ForwardPassMetrics(
            worker_label=self.worker_label,
            mesh_devices=self.mesh_devices,
            # dynaslo: role gates the KV scheduler (prefill-role workers
            # take no routed decode work) and labels the merged latency
            # histograms in the aggregator
            role=self.role,
            latency_hist=self.latency.to_wire(),
            request_active_slots=len(self.active),
            request_total_slots=p.total_slots,
            kv_active_blocks=blocks,
            kv_total_blocks=p.kv_total_blocks,
            num_requests_waiting=len(self.queue),
            gpu_cache_usage_perc=blocks / max(p.kv_total_blocks, 1),
            # dynacache: realized (engine-side) hit rate from the modeled
            # stored-chain set — reported next to the router's predicted
            # avg_hit_rate in the fleet report's cache block
            gpu_prefix_cache_hit_rate=(
                self.realized_hit_blocks
                / max(self.prompt_blocks_total, 1)),
            gpu_prefix_cache_hit_rate_lifetime=(
                self.realized_hit_blocks
                / max(self.prompt_blocks_total, 1)),
            prefix_hit_tokens_total=(self.realized_hit_blocks
                                     * self.block_size),
            prompt_tokens_total=(self.prompt_blocks_total
                                 * self.block_size),
            cache_device_hit_blocks_total=self.realized_hit_blocks,
            # modeled from virtual state only (so seeded reports stay
            # byte-identical): free pages from the block model
            kv_free_blocks=p.kv_total_blocks - blocks,
        ).to_dict()


class SimWorker:
    """A scripted worker: real endpoint + runtime, simulated service."""

    def __init__(self, drt: DistributedRuntime, namespace: str,
                 component: str, name: str, profile: WorkerProfile,
                 block_size: int, clock: Callable[[], float],
                 on_lifecycle: Callable[[str, str, float], None],
                 endpoint: str = "generate_tokens",
                 submesh: Optional[List[int]] = None,
                 role: str = "unified",
                 prefill_pool: Optional[PrefillPool] = None):
        self.drt = drt
        self.namespace = namespace
        self.component = component
        self.endpoint = endpoint
        self.name = name
        # dynashard scenario: the modeled device ids this replica's
        # submesh occupies (assigned by the harness's DevicePool; None =
        # the unsharded fleet scenarios)
        self.submesh = list(submesh) if submesh else None
        self.model = SimEngineModel(name, profile, block_size, clock,
                                    on_lifecycle, role=role,
                                    pool=prefill_pool)
        if self.submesh:
            self.model.mesh_devices = len(self.submesh)
        self.kv_subject = f"{namespace}.{component}.{KV_EVENT_SUBJECT}"
        self.draining = False
        self._handle = None

    @property
    def instance_id(self) -> int:
        return self.drt.instance_id

    def set_role(self, role: str) -> None:
        self.model.set_role(role)

    async def start(self) -> None:
        comp = self.drt.namespace(self.namespace).component(self.component)
        await comp.create_service()
        self._handle = await comp.endpoint(self.endpoint).serve(
            self._handler, stats_handler=self.model.stats)
        log.info("fleet worker %s serving as instance %x",
                 self.name, self.instance_id)

    async def _handler(self, request: dict, context: Context):
        pre = PreprocessedRequest.from_dict(request)
        req = self.model.submit(context.id,
                                list(pre.token_ids),
                                pre.stop.max_tokens or 16)
        try:
            sent = 0
            while True:
                ev = await req.events.get()
                if ev is _CRASH:
                    raise RuntimeError(
                        f"worker {self.name} crashed mid-stream")
                if context.killed:
                    return
                n, finish = ev
                ids = [pre.token_ids[(sent + i) % max(len(pre.token_ids), 1)]
                       if pre.token_ids else 32 for i in range(n)]
                sent += n
                if n:
                    yield EngineOutput(
                        token_ids=ids,
                        prompt_tokens=pre_prompt_tokens(pre)).to_dict()
                if finish:
                    yield EngineOutput(
                        token_ids=[], finish_reason=finish,
                        prompt_tokens=pre_prompt_tokens(pre)).to_dict()
                    return
        finally:
            if not req.finished:
                self.model.abandon(req)

    async def publish_kv_events(
            self, events: List[Tuple[List[int], Optional[int]]]) -> None:
        """Publish this step's stored-block chains on the router's event
        subject (called by the harness, in deterministic worker order)."""
        if not events:
            return
        payload = pack([KvCacheEventWire(
            worker_id=self.instance_id, kind="stored",
            block_hashes=hashes, parent_hash=parent).to_dict()
            for hashes, parent in events])
        await self.drt.dcp.publish(self.kv_subject, payload)

    # ------------------------------------------------------------ faults

    async def crash(self) -> None:
        """Wedge, don't deregister: subscriptions die but the discovery
        record stays (keepalive thread still renews the lease) — the
        stale-endpoint case the Client eviction path handles."""
        self.model.crash()
        if self._handle:
            for sid in self._handle._sids:
                try:
                    await self.drt.dcp.unsubscribe(sid)
                except Exception:
                    log.debug("unsubscribe during crash failed",
                              exc_info=True)
            self._handle._sids.clear()

    def set_blackout(self, on: bool) -> None:
        self.model.blackout = on

    async def drain(self) -> None:
        """dynarevive graceful drain: leave discovery (no new
        admissions; the handle nacks stragglers) while in-flight
        requests keep stepping to done and their streams finish clean.
        The handle stays owned so ``stop()`` (via retire_idle_drained,
        once the model is idle) completes the state machine."""
        self.draining = True
        handle = self._handle
        if handle:
            await handle.begin_drain()

    async def stop(self) -> None:
        handle, self._handle = self._handle, None
        if handle:
            await handle.stop()
        await self.drt.shutdown()


def pre_prompt_tokens(pre: PreprocessedRequest) -> int:
    return len(pre.token_ids)
