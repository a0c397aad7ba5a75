"""Engine adapters: local chains and remote forwarding.

Reference lib/llm/src/engines.rs + the pipeline links in
launch/dynamo-run/src/input/http.rs: a "full" engine speaks OpenAI types
directly; a "core" engine speaks token-level types and is wrapped by
``OpenAIPreprocessor`` + ``Backend``. ``RemoteOpenAIEngine`` is the analog
of the frontend's remote client engine (http/service/discovery.rs:36-56):
it forwards OpenAI requests over the distributed runtime to a worker.
"""

from __future__ import annotations

import logging
from typing import AsyncIterator, Optional

from ..runtime import profiling
from ..runtime.component import Client
from ..runtime.engine import Annotated, Context
from .backend import Backend
from .model_card import ModelDeploymentCard
from .preprocessor import completion_logprobs, OpenAIPreprocessor
from .protocols.openai import (ChatCompletionRequest, CompletionRequest,
                               _finish_reason_openai)

log = logging.getLogger("dynamo_tpu.engines")


def usage_cost(context: Context):
    """dynaprof usage extension: the request's cost-attribution block,
    when DYN_PROF_USAGE is on and the engine (local or remote via the
    Backend relay) recorded one — else None, and the usage payload
    stays byte-for-byte OpenAI-shaped."""
    from ..runtime.config import env_bool
    from ..runtime import profiling

    if not env_bool("DYN_PROF_USAGE"):
        return None
    return profiling.request_attribution(context.id)


class LocalChatChain:
    """preprocessor → backend → core engine, in-process (reference
    EngineConfig::StaticCore pipeline: ServiceFrontend → OpenAIPreprocessor →
    Backend → ExecutionContext)."""

    def __init__(self, mdc: ModelDeploymentCard, core_engine,
                 preprocessor: Optional[OpenAIPreprocessor] = None):
        self.mdc = mdc
        self.preprocessor = preprocessor or OpenAIPreprocessor(mdc)
        self.backend = Backend(core_engine, self.preprocessor.tokenizer)

    def __call__(self, request: ChatCompletionRequest,
                 context: Context) -> AsyncIterator:
        return self._run(request, context)

    async def _run(self, request: ChatCompletionRequest, context: Context):
        pre, annotations = self.preprocessor.preprocess_chat(request)
        for ann in annotations:
            yield ann
        engine_stream = self.backend.generate(pre, context)
        async for chunk in self.preprocessor.chat_stream(
                request, engine_stream, context, len(pre.token_ids)):
            yield chunk


class LocalCompletionChain:
    """Same chain for the /v1/completions endpoint."""

    def __init__(self, mdc: ModelDeploymentCard, core_engine,
                 preprocessor: Optional[OpenAIPreprocessor] = None):
        self.mdc = mdc
        self.preprocessor = preprocessor or OpenAIPreprocessor(mdc)
        self.backend = Backend(core_engine, self.preprocessor.tokenizer)

    def __call__(self, request: CompletionRequest,
                 context: Context) -> AsyncIterator:
        return self._run(request, context)

    async def _run(self, request: CompletionRequest, context: Context):
        import time as _time
        import uuid as _uuid

        pre, annotations = self.preprocessor.preprocess_completion(request)
        for ann in annotations:
            yield ann
        rid = f"cmpl-{context.id or _uuid.uuid4().hex}"
        created = int(_time.time())
        completion_tokens = 0
        text_off = 0
        if pre.output.echo_prompt:
            # OpenAI completions echo=true: the response text starts with
            # the prompt (reconstructed from the request token ids so
            # pre-tokenized prompts echo too); generated-token offsets
            # then start AFTER it
            echo_text = self.preprocessor.tokenizer.decode(
                list(pre.token_ids))
            text_off = len(echo_text)
            yield {
                "id": rid, "object": "text_completion", "created": created,
                "model": request.model,
                "choices": [{"index": 0, "text": echo_text,
                             "finish_reason": None}],
            }
        async for out in self.backend.generate(pre, context):
            completion_tokens += len(out.token_ids)
            if out.text or out.finish_reason or out.logprobs:
                choice = {"index": 0, "text": out.text or "",
                          "finish_reason":
                              _finish_reason_openai(out.finish_reason)}
                lp = completion_logprobs(out, self.preprocessor.tokenizer, text_off)
                if lp:
                    choice["logprobs"] = lp
                text_off += len(out.text or "")
                yield {"id": rid, "object": "text_completion",
                       "created": created, "model": request.model,
                       "choices": [choice]}
            if out.finish_reason:
                if request.stream_options and request.stream_options.include_usage:
                    usage = {
                        "prompt_tokens": len(pre.token_ids),
                        "completion_tokens": completion_tokens,
                        "total_tokens":
                            len(pre.token_ids) + completion_tokens}
                    cost = usage_cost(context)
                    if cost is not None:
                        usage["cost"] = cost
                    yield {"id": rid, "object": "text_completion",
                           "created": created, "model": request.model,
                           "choices": [], "usage": usage}
                return


class RemoteOpenAIEngine:
    """Forwards OpenAI-level requests to a worker endpoint over the
    distributed runtime; the worker streams chunk dicts back in Annotated
    envelopes. ``mode``/``instance_id`` select routing."""

    def __init__(self, client: Client, mode: str = "round_robin"):
        self.client = client
        self.mode = mode

    def __call__(self, request, context: Context) -> AsyncIterator:
        return self._run(request, context)

    async def _run(self, request, context: Context):
        payload = request.model_dump(exclude_none=True) \
            if hasattr(request, "model_dump") else request
        # the frontend's `intake` bracket ends where the request leaves
        # this process (what follows is the network's wait, not the loop's
        # work)
        profiling.loop_ledger().leave("intake")
        stream = await self.client.generate(
            payload, mode=self.mode, context=context)
        try:
            async for env in stream:
                yield env
        finally:
            if context.killed:
                await stream.kill()
            elif context.stopped:
                await stream.stop_generating()
