"""The prefill worker: pull queue → prefill → stream KV pages.

Reference examples/llm/components/prefill_worker.py:37-141: pulls the
JetStream prefill queue, lazily fetches the decode engine's NIXL metadata
from etcd on first contact, runs a max_tokens=1 generate, and RDMA-writes
the computed blocks into decode VRAM. Here: DCP work queue, DCP-stored TCP
endpoints, engine.prefill_only + a chunked extract→compress→send pipeline
(transfer.py streaming protocol) so the device→host extract of chunk i+1
overlaps the socket write of chunk i — decode-side TTFT stops being the
sum of prefill + extract + wire + inject.

Elastic xPyD: any number of prefill workers pull the one shared queue;
joining/leaving needs no coordination (docs/disagg_serving.md:93-100).
"""

from __future__ import annotations

import asyncio
import logging
import math
import time
from typing import Dict, List, Optional, Set

import numpy as np

from ...runtime import guard, tracing
from ...runtime.engine import Context
from ..protocols.common import (PreprocessedRequest, SamplingOptions,
                                StopConditions)
from .protocols import RemotePrefillRequest
from .queue import PrefillQueue
from .transfer import KvTransferClient, TransferStats

log = logging.getLogger("dynamo_tpu.llm.disagg")

DEFAULT_CHUNK_PAGES = 4


class PrefillWorker:
    def __init__(self, drt, engine, *, namespace: str = "dynamo",
                 max_inflight: int = 4,
                 compress_kv: Optional[bool] = None,
                 chunk_pages: Optional[int] = None):
        from ...runtime.config import env_bool, env_int

        self.drt = drt
        # what a JaxEngine's family is refused (models/registry.py REFUSALS)
        if (family := getattr(engine, "family", None)) is not None:
            family.refuse("disagg_prefill")
        self.engine = engine
        if hasattr(engine, "set_role"):
            # dynaslo: this engine serves prefill-only — its latency
            # histograms (queue wait of pulled jobs, prefill-side
            # timings) merge under role="prefill" fleet-wide
            engine.set_role("prefill")
        self.namespace = namespace
        # int8-compress shipped pages (~half the DCN bytes; lossy —
        # engine/kv_compress.py). Opt-in: arg, else DYN_KV_TRANSFER_INT8
        self.compress_kv = (compress_kv if compress_kv is not None
                            else env_bool("DYN_KV_TRANSFER_INT8"))
        # pages per streamed chunk frame; 0 = legacy single bulk frame.
        # Arg, else DYN_KV_TRANSFER_CHUNK_PAGES, else the default.
        if chunk_pages is None:
            chunk_pages = env_int("DYN_KV_TRANSFER_CHUNK_PAGES",
                                  DEFAULT_CHUNK_PAGES)
        self.chunk_pages = max(int(chunk_pages), 0)
        self.queue = PrefillQueue(drt.dcp, namespace)
        self.max_inflight = max_inflight
        self._clients: Dict[int, KvTransferClient] = {}
        self._tasks: Set[asyncio.Task] = set()
        self._run_task: Optional[asyncio.Task] = None
        self._stopped = False
        self.completed = 0
        self.failed = 0
        self.expired = 0            # jobs dropped: budget spent in-queue
        self.client_evictions = 0
        # shared retry/breaker plane (replaces the PR 2 ad-hoc
        # evict-and-retry-once): sends to a decode engine run under the
        # RetryPolicy (budget-aware), and a per-engine circuit breaker
        # fails jobs fast while an engine's transfer endpoint stays dead
        self.retry = guard.RetryPolicy.from_env()
        self.breakers = guard.BreakerBoard(f"prefill-worker:{namespace}")
        # per-stage transfer-pipeline accounting, shared by all clients
        self.xfer = TransferStats()

    def start(self) -> None:
        if self._run_task is None:
            self._run_task = asyncio.ensure_future(self._run())

    async def stop(self) -> None:
        self._stopped = True
        if self._run_task:
            self._run_task.cancel()
            try:
                await self._run_task
            except asyncio.CancelledError:
                pass
        for t in list(self._tasks):
            t.cancel()
        for c in self._clients.values():
            c.close()

    async def _run(self) -> None:
        while not self._stopped:
            try:
                if len(self._tasks) >= self.max_inflight:
                    await asyncio.wait(self._tasks,
                                       return_when=asyncio.FIRST_COMPLETED)
                    continue
                req = await self.queue.pull(timeout=0.5)
                if req is None:
                    continue
                task = asyncio.ensure_future(self._handle(req))
                self._tasks.add(task)
                task.add_done_callback(self._tasks.discard)
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — a DCP hiccup must not
                log.exception("prefill pull loop error; retrying")  # kill us
                await asyncio.sleep(1.0)

    async def _handle(self, req: RemotePrefillRequest) -> None:
        """One remote prefill: compute, extract the non-cached pages, ship."""
        pages = None
        tracing.bind_request_id(req.request_id)
        tracer = tracing.get_tracer()
        # rebuild the job's deadline against this host's clock (absent on
        # the wire = no deadline); a job whose budget died in the queue is
        # dropped outright — the decode side has already fallen back
        deadline = guard.Deadline.from_wire_ms(req.deadline_ms)
        if deadline is not None and deadline.expired:
            self.expired += 1
            log.warning("dropping expired remote prefill job %s "
                        "(budget spent in queue)", req.request_id)
            return
        try:
            pre = PreprocessedRequest(
                token_ids=list(req.token_ids),
                sampling=SamplingOptions.from_dict(req.sampling),
                stop=StopConditions(max_tokens=1),
                eos_token_ids=list(req.eos_token_ids),
            )
            ctx = Context(req.request_id)
            # parent = the decode-side request's trace (trace_ctx rides the
            # queue); None roots a worker-local trace instead
            with tracer.start_span(
                    "prefill.forward", parent=req.trace_ctx,
                    attributes={"tokens": len(req.token_ids)},
                    request_id=req.request_id) as fsp:
                first, pages = await self.engine.prefill_only(pre, ctx)
                fsp.set_attribute("pages", len(pages))

            if deadline is not None and deadline.expired:
                # budget died during the prefill compute: shipping now
                # cannot beat the decode side's (already fired) fallback —
                # drop instead of racing a doomed transfer
                self.expired += 1
                log.warning("dropping remote prefill job %s after compute "
                            "(budget spent)", req.request_id)
                return
            ps = self.engine.ecfg.page_size
            n_prompt_pages = math.ceil(len(req.token_ids) / ps)
            local_send = pages[req.skip_pages:n_prompt_pages]
            remote_dst = req.page_ids[req.skip_pages:n_prompt_pages]
            await self._send(req, local_send, remote_dst, first, deadline)
            self.completed += 1
        except Exception:  # noqa: BLE001 — a bad job must not kill the loop
            self.failed += 1
            log.exception("remote prefill job %s failed (decode side will "
                          "fall back)", req.request_id)
        finally:
            if pages is not None:
                await self.engine.release_pages(pages)

    async def _send(self, req: RemotePrefillRequest, local_send: List[int],
                    remote_dst: List[int], first: int,
                    deadline: Optional[guard.Deadline] = None) -> None:
        """Ship the pages, surviving a decode-worker restart: the cached
        client may point at a dead host:port, so each failed attempt
        evicts it, re-resolves the endpoint from DCP, and retries with a
        fresh connection under the shared RetryPolicy (budget-aware —
        never past the job's deadline). A per-engine circuit breaker
        fails jobs fast while an engine's endpoint stays dead. Stage
        times accumulate into a per-send TransferStats (exact per-request
        trace spans) and fold into the shared ``self.xfer`` totals
        afterwards."""
        tracer = tracing.get_tracer()
        per = TransferStats()
        br = self.breakers.get("transfer", req.engine_id)
        span = tracer.start_span(
            "kv_transfer.send", parent=req.trace_ctx,
            attributes={"engine_id": f"{req.engine_id:x}",
                        "pages": len(local_send),
                        "chunk_pages": self.chunk_pages})
        try:
            with span:
                if not br.allow():
                    raise guard.NoCapacity(
                        f"transfer endpoint for engine {req.engine_id:x} "
                        f"is circuit-broken")
                last: Optional[BaseException] = None
                sent = False
                async for _attempt in self.retry.attempts(deadline):
                    client = await self._client(req.engine_id)
                    try:
                        await self._send_once(client, req, local_send,
                                              remote_dst, first, per,
                                              deadline)
                        br.record_success()
                        sent = True
                        break
                    except asyncio.CancelledError:
                        raise
                    except Exception as exc:  # noqa: BLE001 — retry fresh
                        self._evict(req.engine_id, client)
                        self.client_evictions += 1
                        last = exc
                        log.warning("KV send for %s to engine %x failed "
                                    "(%s); re-resolving endpoint and "
                                    "retrying within budget",
                                    req.request_id, req.engine_id, exc)
                if not sent:
                    br.record_failure()
                    raise last if last is not None else \
                        guard.DeadlineExceeded(
                            f"no budget left to send KV for "
                            f"{req.request_id}")
                span.set_attribute("bytes", per.bytes_sent)
                span.set_attribute("chunks", per.chunks_sent)
                # adopt the measured stage accumulators as child spans
                # (stages overlap, so siblings legitimately sum past the
                # parent's wall — that inequality IS the pipelining)
                for stage, secs in (("extract", per.extract_seconds),
                                    ("compress", per.compress_seconds),
                                    ("wire", per.wire_seconds),
                                    ("ack_wait", per.ack_wait_seconds)):
                    if secs > 0:
                        tracer.record_span(f"kv_transfer.{stage}", secs,
                                           parent=span)
        finally:
            self.xfer.merge(per)

    async def _send_once(self, client: KvTransferClient,
                         req: RemotePrefillRequest, local_send: List[int],
                         remote_dst: List[int], first: int,
                         stats: TransferStats,
                         deadline: Optional[guard.Deadline] = None) -> None:
        # the decode side's commit-ack wait is capped by the remaining
        # request budget (None → the registered default)
        timeout = None if deadline is None else max(deadline.cap(None), 0.05)
        cp = self.chunk_pages
        if cp and local_send:
            n_chunks = math.ceil(len(local_send) / cp)
            frames = self._frames(local_send, remote_dst, cp, stats)
            await client.send_kv_chunked(req.request_id, n_chunks, frames,
                                         first, timeout=timeout, stats=stats)
        else:
            t0 = time.monotonic()
            k, v = await self.engine.extract_pages(local_send)
            dt = time.monotonic() - t0
            stats.extract_seconds += dt
            # bulk runs extract BEFORE the send; count it into the wall so
            # the stage-sum-vs-wall overlap comparison is apples-to-apples
            # with the chunked pipeline (whose wall covers extraction)
            stats.wall_seconds += dt
            await client.send_kv(req.request_id, remote_dst, k, v, first,
                                 timeout=timeout,
                                 compress=self.compress_kv, stats=stats)

    async def _frames(self, local_send: List[int], remote_dst: List[int],
                      cp: int, stats: TransferStats):
        """Chunk producer for the streaming protocol: ranged device→host
        extract (pipelined inside the engine) + optional int8 compression
        off the event loop. The client consumes this one chunk ahead, so
        this body runs under the previous chunk's socket write."""
        loop = asyncio.get_running_loop()
        async for off, k, v, dt in self.engine.extract_pages_chunked(
                local_send, cp):
            stats.extract_seconds += dt
            dst = remote_dst[off:off + cp]
            k = np.ascontiguousarray(k)
            v = np.ascontiguousarray(v)
            extra = {"shape": list(k.shape), "dtype": str(k.dtype),
                     "k_len": k.nbytes}
            if self.compress_kv:
                from ...engine.kv_compress import quantize_pages_np

                t0 = time.monotonic()
                kq, ks = await loop.run_in_executor(None, quantize_pages_np,
                                                    k)
                vq, vs = await loop.run_in_executor(None, quantize_pages_np,
                                                    v)
                stats.compress_seconds += time.monotonic() - t0
                extra.update(quant="int8", k_len=kq.nbytes)
                yield dst, extra, [kq, vq, ks, vs], (kq.nbytes + vq.nbytes
                                                     + ks.nbytes + vs.nbytes)
            else:
                yield dst, extra, [k, v], k.nbytes + v.nbytes

    async def _client(self, engine_id: int) -> KvTransferClient:
        client = self._clients.get(engine_id)
        if client is not None:
            return client
        client = await KvTransferClient.lookup(self.drt.dcp,
                                               self.namespace, engine_id,
                                               stats=self.xfer)
        # re-check after the lookup await: a concurrent job for the same
        # engine may have resolved it first — without this, the loser
        # clobbers the cache and the winner's connection leaks
        cached = self._clients.get(engine_id)
        if cached is not None:
            client.close()
            return cached
        self._clients[engine_id] = client
        return client

    def _evict(self, engine_id: int, client: Optional[KvTransferClient]
               ) -> None:
        cached = self._clients.get(engine_id)
        if cached is not None and (client is None or cached is client):
            del self._clients[engine_id]
        if client is not None:
            client.close()

    def stats(self) -> dict:
        return {"inflight": len(self._tasks), "completed": self.completed,
                "failed": self.failed, "expired_jobs": self.expired,
                "client_evictions": self.client_evictions,
                "transfer_breakers_open":
                    len(self.breakers.not_closed("transfer")),
                "chunk_pages": self.chunk_pages,
                **{f"kv_send_{k}": v for k, v in self.xfer.to_dict().items()}}
