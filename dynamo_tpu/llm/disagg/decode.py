"""Decode-side disaggregation orchestration.

Reference examples/llm/components/worker.py:37-189 (VllmWorker): per
request, consult the disagg router with (prefill_length, prefix_hit);
remote → allocate decode-side KV blocks, enqueue a RemotePrefillRequest,
wait for the prefill worker's block write + completion notification, then
continue decoding locally. Falls back to fully local prefill whenever the
pool is exhausted, the queue is saturated, or the remote path errors.
"""

from __future__ import annotations

import asyncio
import logging
from typing import AsyncIterator, Optional

from ...runtime import guard, tracing
from ...runtime.config import env_float, env_int
from ...runtime.engine import Context
from ..protocols.common import (FINISH_CANCELLED, FINISH_ERROR, EngineOutput,
                                PreprocessedRequest)
from .protocols import RemotePrefillRequest
from .queue import PrefillQueue
from .router import DisaggRouter
from .transfer import KvTransferServer

log = logging.getLogger("dynamo_tpu.llm.disagg")


async def _drain_seq(seq) -> AsyncIterator[EngineOutput]:
    """Engine-sequence queue → chunk stream (remote-prefill decode leg)."""
    while True:
        out: EngineOutput = await seq.out.get()
        yield out
        if out.finish_reason is not None:
            return


class DisaggDecodeEngine:
    """AsyncEngine wrapper adding conditional remote prefill to a JaxEngine.

    Serves the same token-level protocol, so it drops into serve_token_model
    / the Backend pipeline unchanged.
    """

    def __init__(self, engine, queue: PrefillQueue, transfer: KvTransferServer,
                 router: DisaggRouter, engine_id: int,
                 prefill_timeout: Optional[float] = None,
                 max_dispatches: Optional[int] = None):
        # what a JaxEngine's family is refused (models/registry.py REFUSALS)
        if (family := getattr(engine, "family", None)) is not None:
            family.refuse("disagg_decode")
        self.engine = engine
        if hasattr(engine, "set_role"):
            # dynaslo: the wrapped engine serves the decode side of the
            # disagg split — its TTFT/ITL histograms merge under
            # role="decode" fleet-wide
            engine.set_role("decode")
        self.queue = queue
        self.transfer = transfer
        self.router = router
        self.engine_id = engine_id
        self.prefill_timeout = prefill_timeout if prefill_timeout is not None \
            else (env_float("DYN_PREFILL_TIMEOUT", 120.0) or 120.0)
        # hedged re-dispatch: when the transfer plane fails FAST (prefill
        # worker died mid-transfer, severed conn) and budget remains, the
        # job is re-enqueued to the shared queue — another worker picks it
        # up — before giving up and falling back to local prefill. A slow
        # timeout never re-dispatches (the budget is already spent).
        self.max_dispatches = max(1, max_dispatches if max_dispatches
                                  is not None
                                  else (env_int("DYN_REDISPATCH_MAX", 2)
                                        or 1))
        # observability
        self.remote_prefills = 0
        self.local_prefills = 0
        self.remote_fallbacks = 0
        self.redispatches = 0
        # decode-side view of the remote leg: enqueue → KV landed + first
        # token (queue wait + prefill compute + page transfer), the
        # disagg-vs-agg transfer-overhead breakdown the reference's
        # "+30%/GPU" claim hides (docs/architecture.md:57-61)
        self.remote_wait_total_s = 0.0

    def stats(self) -> dict:
        s = dict(self.engine.stats())
        s.update(remote_prefills=self.remote_prefills,
                 local_prefills=self.local_prefills,
                 remote_fallbacks=self.remote_fallbacks,
                 remote_redispatches=self.redispatches,
                 remote_wait_total_s=round(self.remote_wait_total_s, 3),
                 remote_prefill_wait_seconds_total=round(
                     self.remote_wait_total_s, 3))
        # transfer-plane ingest counters (streaming chunk pipeline) — fed
        # into ForwardPassMetrics for the Prometheus gauges
        s.update(self.transfer.stats())
        return s

    async def generate(self, request, context: Context
                       ) -> AsyncIterator[EngineOutput]:
        if not isinstance(request, PreprocessedRequest):
            request = PreprocessedRequest.from_dict(request)
        tokens = request.token_ids
        tracer = tracing.get_tracer()

        # short prompts can never go remote (prefill_len - hit <= prefill_len
        # <= threshold), so skip the reservation churn on the hot path
        res = None
        if (self.router.enabled
                and len(tokens) > self.router.max_local_prefill_length):
            res = await self.engine.reserve_remote(tokens)

        seq = None
        try:
            remote = False
            depth = None
            with tracer.start_span("route.disagg", attributes={
                    "prefill_len": len(tokens)}) as rsp:
                if res is not None:
                    depth = await self.queue.depth()
                    remote = self.router.prefill_remote(
                        len(tokens), res.cached_tokens, depth)
                    rsp.set_attribute("cached_tokens", res.cached_tokens)
                    rsp.set_attribute("queue_depth", depth)
                rsp.set_attribute("remote", remote)
            if not remote:
                if res is not None:
                    # drop ownership before awaiting: a cancellation landing
                    # at the await must not re-release in the finally block
                    pages, res = res.pages, None
                    await self.engine.release_pages(pages)
                self.local_prefills += 1
                dsp = tracer.start_span("decode",
                                        attributes={"mode": "local"})
                async for out in self._traced(
                        dsp, self.engine.generate(request, context),
                        request.stop.max_tokens):
                    yield out
                return

            self.remote_prefills += 1
            with tracer.start_span("prefill.remote", attributes={
                    "queue_depth": depth,
                    "skip_pages": res.skip_pages}) as psp:
                first = await self._remote_prefill(request, context, res)
                psp.set_attribute("ok", first is not None)
            if first is None:  # remote failed/timed out → local fallback
                self.remote_fallbacks += 1
                pages, res = res.pages, None
                await self.engine.release_pages(pages)
                if context.stopped:
                    # deadline expiry surfaces as "timeout", caller
                    # cancellation as "cancelled"
                    yield EngineOutput(
                        finish_reason=context.cancel_reason())
                    return
                log.warning("remote prefill fell back to local for %s",
                            context.id)
                dsp = tracer.start_span("decode", attributes={
                    "mode": "local_fallback"})
                async for out in self._traced(
                        dsp, self.engine.generate(request, context),
                        request.stop.max_tokens):
                    yield out
                return

            seq = await self.engine.submit_prefilled(request, context,
                                                     res.pages, first)
            res = None  # ownership passed to the sequence
        finally:
            if res is not None and seq is None:
                # a failure between reserve and handoff must not leak pages
                await self.engine.release_pages(res.pages)

        dsp = tracer.start_span("decode", attributes={
            "mode": "remote_prefill"})
        async for out in self._traced(dsp, _drain_seq(seq),
                                      request.stop.max_tokens):
            yield out

    async def _traced(self, dsp, stream, max_tokens):
        """Relay ``stream`` under the decode span ``dsp``, ending the span
        the moment the request is observably finished — a finish chunk OR
        the token budget reached. The budget mirror matters: downstream
        (Backend) stamps max_tokens itself and abandons this generator
        right after the last token chunk, so a span ended only by the
        engine's finish chunk would linger until GC-time aclose."""
        n_out = 0
        try:
            async for out in stream:
                n_out += len(out.token_ids)
                if out.finish_reason is not None or (
                        max_tokens is not None and n_out >= max_tokens):
                    dsp.set_attribute("tokens", n_out)
                    if out.finish_reason is not None:
                        dsp.set_attribute("finish", out.finish_reason)
                    dsp.end()  # idempotent; before the abandonable yield
                yield out
        finally:
            dsp.end()

    async def _remote_prefill(self, request: PreprocessedRequest,
                              context: Context, res) -> Optional[int]:
        """Enqueue + await the KV arrival; returns the first token or None.

        The wait is bounded by ``min(prefill_timeout, request deadline)``.
        A FAST failure (the transfer plane fails the waiter: prefill
        worker died mid-transfer, severed connection, ingest error) is
        hedged: while dispatches and budget remain, the job is re-enqueued
        to the shared queue for another worker. A timeout — budget already
        burned — falls straight back to local prefill."""
        import time as _time

        t0 = _time.monotonic()
        deadline = context.deadline
        for dispatch in range(self.max_dispatches):
            fut = self.transfer.expect(context.id)
            await self.queue.put(RemotePrefillRequest(
                request_id=context.id,
                token_ids=list(request.token_ids),
                sampling=request.sampling.to_dict(),
                eos_token_ids=list(request.eos_token_ids),
                page_ids=list(res.pages),
                skip_pages=res.skip_pages,
                engine_id=self.engine_id,
                # join the prefill worker's spans to this request's trace
                # (None when not sampled → field absent on the wire)
                trace_ctx=tracing.get_tracer().current_trace_ctx(),
                # remaining budget travels with the job (absent = none)
                deadline_ms=(deadline.to_wire_ms()
                             if deadline is not None else None),
            ))
            try:
                first = await guard.bound(fut, timeout=self.prefill_timeout,
                                          deadline=deadline,
                                          what="remote prefill")
                self.remote_wait_total_s += _time.monotonic() - t0
                return first
            except asyncio.TimeoutError:
                # covers DeadlineExceeded too: the budget is spent (or
                # the prefill pool is too slow) — no hedge, fall back
                self.transfer.cancel(context.id)
                return None
            except asyncio.CancelledError:
                # handler task cancelled — cancel the waiter and propagate;
                # generate()'s finally releases the reserved pages
                self.transfer.cancel(context.id)
                raise
            except Exception as exc:  # noqa: BLE001
                # fail-fast signal from the transfer plane: hedge if a
                # dispatch remains and the budget can still cover work
                self.transfer.cancel(context.id)
                if dispatch + 1 < self.max_dispatches and \
                        not (deadline is not None and deadline.expired):
                    self.redispatches += 1
                    guard.counter_inc("dyn_guard_hedged_redispatch_total")
                    log.warning("remote prefill for %s failed fast (%s); "
                                "re-enqueueing (dispatch %d/%d)",
                                context.id, exc, dispatch + 2,
                                self.max_dispatches)
                    continue
                log.warning("remote prefill failed for %s (%s); falling "
                            "back to local", context.id, exc)
                return None
        return None


async def build_disagg_decode(drt, engine, *, namespace: str = "dynamo",
                              model: str = "default",
                              router: Optional[DisaggRouter] = None,
                              watch_config: bool = True
                              ) -> DisaggDecodeEngine:
    """Wire the decode side: transfer listener (registered under the
    worker's lease), prefill queue handle, router with live config watch."""
    router = router or DisaggRouter()
    if watch_config:
        await router.start_watch(drt.dcp, namespace, model)
    transfer = KvTransferServer(engine)
    await transfer.start()
    await transfer.register(drt.dcp, namespace, drt.instance_id,
                            lease=drt.primary_lease)
    queue = PrefillQueue(drt.dcp, namespace)
    return DisaggDecodeEngine(engine, queue, transfer, router,
                              drt.instance_id)
