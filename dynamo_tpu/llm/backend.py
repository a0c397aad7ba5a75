"""Backend: the detokenizing stage between engine and preprocessor.

Reference lib/llm/src/backend.rs:58-120 + ``Decoder``: wraps the token-level
engine (``ExecutionContext``); incrementally detokenizes the stream, applies
stop-sequence "jailing" (text that could be the prefix of a stop sequence is
withheld until disambiguated), detects EOS / stop-token / max-token finishes,
and stamps finish reasons.
"""

from __future__ import annotations

import asyncio
from concurrent.futures import ThreadPoolExecutor
from typing import AsyncIterator, List, Optional

from ..runtime import profiling
from ..runtime.engine import Context
from .protocols.common import (FINISH_EOS, FINISH_LENGTH, FINISH_STOP,
                               EngineOutput, PreprocessedRequest)
from .tokenizer import Tokenizer

# Shared detokenization executor (dynaturbo change 4): token→text work for
# every stream runs here instead of on the event-loop thread, so a slow
# decode never inflates OTHER streams' inter-chunk latency. Per-request
# ordering needs no queue machinery: Backend.generate awaits each chunk's
# decode before pulling the next engine chunk, so a request never has two
# decodes in flight (an ordered queue of depth one); the DecodeStream's
# state is therefore only ever touched by one thread at a time.
_DETOK_EXEC: Optional[ThreadPoolExecutor] = None


def _detok_executor() -> ThreadPoolExecutor:
    global _DETOK_EXEC
    if _DETOK_EXEC is None:
        _DETOK_EXEC = ThreadPoolExecutor(max_workers=2,
                                         thread_name_prefix="dyn-detok")
    return _DETOK_EXEC


def _decode_many(decode, ids: List[int]) -> str:
    # the worker thread's one bracket (``dyn.detok`` on a trace, `detok`
    # of stats()["loop_phase_seconds_total"])
    led = profiling.worker_ledger("detok")
    led.enter("detok")
    try:
        return "".join(p for p in map(decode.step, ids) if p)
    finally:
        led.leave("detok")


class StopSequenceJail:
    """Holds back emitted text while it matches a proper prefix of any stop
    sequence; releases or truncates once disambiguated (reference backend.rs
    toktrie-based jail)."""

    def __init__(self, stop: List[str]):
        self._stop = [s for s in stop if s]
        self._held = ""

    def feed(self, text: str) -> tuple[str, bool]:
        """Returns (releasable_text, hit_stop)."""
        if not self._stop:
            return text, False
        buf = self._held + text
        # full stop sequence present → truncate at the earliest match
        cut = -1
        for s in self._stop:
            i = buf.find(s)
            if i != -1 and (cut == -1 or i < cut):
                cut = i
        if cut != -1:
            self._held = ""
            return buf[:cut], True
        # otherwise hold the longest suffix that is a prefix of some stop seq
        hold = 0
        for s in self._stop:
            for k in range(min(len(s) - 1, len(buf)), 0, -1):
                if buf.endswith(s[:k]):
                    hold = max(hold, k)
                    break
        if hold:
            self._held = buf[-hold:]
            return buf[:-hold], False
        self._held = ""
        return buf, False

    def flush(self) -> str:
        out, self._held = self._held, ""
        return out


class Backend:
    """Engine wrapper adding detokenization + stop handling.

    ``engine.generate(PreprocessedRequest, Context)`` must yield
    ``EngineOutput`` (or dicts thereof) with ``token_ids`` deltas; this
    stage fills ``text`` and ``finish_reason``.
    """

    # bound (seconds) on draining the engine's in-flight finish chunk
    # after a Backend-side stop: ~an engine iteration, never a hang
    COST_HARVEST_BOUND_S = 0.25

    def __init__(self, engine, tokenizer: Tokenizer):
        self.engine = engine
        self.tokenizer = tokenizer

    async def _harvest_finish_cost(self, agen, context):
        """Drain a few more engine chunks (bounded) for the cost block
        riding the engine's own finish; registers + returns it, or None
        on timeout/exhaustion. Without this, any request the Backend
        finishes first (length cap, eos) would lose its remote cost
        attribution — /v1/traces/{rid} on the frontend, the usage
        extension and the KV router's predicted-vs-realized calibration
        all feed off this block (found live by the dynashard
        multi-process verify: cost never crossed the wire)."""
        try:
            while True:
                raw = await asyncio.wait_for(agen.__anext__(),
                                             self.COST_HARVEST_BOUND_S)
                out = raw if isinstance(raw, EngineOutput) \
                    else EngineOutput.from_dict(raw)
                if out.cost is not None:
                    profiling.record_attribution(context.id, out.cost)
                    return out.cost
                if out.finish_reason:
                    return None
        except (StopAsyncIteration, asyncio.TimeoutError):
            return None

    async def generate(self, request: PreprocessedRequest,
                       context: Context) -> AsyncIterator[EngineOutput]:
        decode = self.tokenizer.decode_stream(
            skip_special_tokens=request.output.skip_special_tokens)
        jail = StopSequenceJail(request.stop.stop or [])
        eos_ids = set() if request.stop.ignore_eos else set(request.eos_token_ids)
        stop_ids = set(request.stop.stop_token_ids or [])
        max_tokens = request.stop.max_tokens
        min_tokens = request.stop.min_tokens or 0
        produced = 0
        finished: Optional[str] = None

        if max_tokens is not None and max_tokens < 1:
            yield EngineOutput(token_ids=[], text="", finish_reason=FINISH_LENGTH,
                               completion_tokens=0)
            context.stop_generating()
            return

        def _final_text(released: str, stop_seq_hit: bool) -> str:
            """Append held decoder/jail text to the finish-bearing chunk
            (downstream consumers stop at the first finish_reason). When a
            stop STRING matched, the jail already truncated at the match and
            held text is intentionally dropped; every other finish (eos,
            stop TOKEN, length, cancel) must flush held text."""
            if stop_seq_hit:
                return released
            tail, _ = jail.feed(decode.flush())
            return released + tail + jail.flush()

        loop = asyncio.get_running_loop()
        # the loop thread's ledger (runtime/profiling.py LOOP_PHASES).
        # What the frontend opened as `intake` ends where the engine's
        # stream is first pulled: the request's first real wait. Each
        # output is then `deliver` up to its hand-off to the detokeniser
        # and `encode_write` from the text's return until the consumer
        # has written it (llm/http/service.py leaves it when resp.write
        # returned) or asks for the next output.
        led = profiling.loop_ledger()
        led.leave("intake")

        agen = _aiter(self.engine.generate(request, context))
        try:
            async for raw in agen:
                led.enter("deliver")
                out = raw if isinstance(raw, EngineOutput) else EngineOutput.from_dict(raw)
                if out.emit_t is not None:
                    # the engine's _emit stamp of the newest tokens on
                    # their way out: the frontend reads it back when the
                    # chunk that carries them is written
                    context.t_emit = out.emit_t
                if out.cost is not None:
                    # remote workers attach dynaprof cost attribution to the
                    # finish chunk; registering it here makes the FRONTEND
                    # process's /v1/traces/{rid} and usage extension work even
                    # when the engine ran in another process
                    profiling.record_attribution(context.id, out.cost)
                # Stop checks are pure host arithmetic and stay inline: they
                # decide which ids are even eligible for decoding (skipped
                # eos under skip_special_tokens, nothing past the finish).
                # Only the tokenizer work ships to the detok executor.
                emit_ids: List[int] = []
                decode_ids: List[int] = []
                for tid in out.token_ids:
                    produced += 1
                    is_eos = tid in eos_ids and produced >= min_tokens
                    is_stop_tok = tid in stop_ids and produced >= min_tokens
                    if not (is_eos and request.output.skip_special_tokens):
                        decode_ids.append(tid)
                    emit_ids.append(tid)
                    if is_eos:
                        finished = FINISH_EOS
                    elif is_stop_tok:
                        finished = FINISH_STOP
                    elif max_tokens is not None and produced >= max_tokens:
                        finished = FINISH_LENGTH
                    if finished:
                        break
                if not decode_ids:
                    text = ""
                else:
                    # awaited before the next engine chunk is pulled — the
                    # per-request decode order is preserved by construction
                    step = loop.run_in_executor(
                        _detok_executor(), _decode_many, decode, decode_ids)
                    led.leave("deliver")
                    text = await step
                led.enter("encode_write")
                released, hit = jail.feed(text) if text else ("", False)
                if hit:
                    finished = finished or FINISH_STOP
                out.token_ids = emit_ids
                out.finish_reason = finished or out.finish_reason
                out.completion_tokens = produced
                if out.finish_reason:
                    out.text = _final_text(released, stop_seq_hit=hit)
                    if out.cost is None and finished is not None and not hit:
                        # the Backend's own stop (token cap / eos / stop
                        # token) fired BEFORE the engine's finish chunk —
                        # the chunk that carries the dynaprof cost block
                        # (replica, prefix split). The engine enforces the
                        # same budget/eos on device, so its finish is
                        # already in flight: drain it (bounded) so remote
                        # cost attribution still lands in this process's
                        # ring. Skipped for stop-STRING matches (`hit`) —
                        # the engine doesn't know host-side stop sequences
                        # and would not finish within the bound.
                        led.leave("encode_write")   # a bounded wait
                        out.cost = await self._harvest_finish_cost(
                            agen, context)
                        led.enter("encode_write")
                    yield out
                    context.stop_generating()
                    return
                out.text = released
                yield out
                led.leave("encode_write")
                if context.stopped:
                    # deadline expiry finishes as "timeout" (client-visible),
                    # caller cancellation as "cancelled"
                    context.stop_generating()
                    yield EngineOutput(text=_final_text("", False) or None,
                                       finish_reason=context.cancel_reason(),
                                       completion_tokens=produced)
                    return
            # engine stream exhausted without a finish reason: flush held text and
            # stamp a terminal reason so downstream never fabricates one
            yield EngineOutput(token_ids=[], text=_final_text("", False) or "",
                               finish_reason=FINISH_STOP, completion_tokens=produced)

        finally:
            led.leave("encode_write")


async def _aiter(gen):
    """Engines may return an async generator directly or a coroutine that
    resolves to one."""
    if hasattr(gen, "__aiter__"):
        async for item in gen:
            yield item
    else:
        async for item in await gen:
            yield item
