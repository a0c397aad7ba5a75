"""The load generator's SSE reader against a small fake server: what a
request asks for, and how chunks are counted and timed."""

import asyncio
import json

import pytest
from aiohttp import web

import bm_paths  # noqa: F401

from benchmark.harness import loadgen, stats


def _sse(obj) -> bytes:
    return b"data: " + json.dumps(obj).encode() + b"\n\n"


async def _serve(chunks, seen, status=200, done=True):
    async def chat(request):
        seen.append(await request.json())
        if status != 200:
            return web.Response(status=status, text="busy")
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"})
        await resp.prepare(request)
        await resp.write(_sse({"choices": [{"delta": {"role": "assistant"}}]}))
        for text, finish in chunks:
            await asyncio.sleep(0.02)
            await resp.write(_sse({"choices": [{
                "delta": {"content": text}, "finish_reason": finish}]}))
        n = sum(len(t) for t, _ in chunks)
        await resp.write(_sse({"choices": [], "usage": {
            "prompt_tokens": 5, "completion_tokens": n,
            "total_tokens": 5 + n}}))
        if done:
            await resp.write(b"data: [DONE]\n\n")
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", chat)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}/v1/chat/completions"


def _request(chunks, want, **kw):
    import time

    import aiohttp

    async def go():
        seen = []
        runner, url = await _serve(chunks, seen, **kw)
        req = {"i": 0, "due_s": 0.0, "prompt_len": 5, "output_len": want}
        row = loadgen.new_row(req)
        try:
            async with aiohttp.ClientSession() as http:
                await loadgen.one_request(
                    http, url, "m", [{"role": "user", "content": "hi"}],
                    req, time.monotonic(), row)
        finally:
            await runner.cleanup()
        return row, seen[0]

    return asyncio.run(go())


def test_a_request_is_what_a_chat_client_sends():
    row, body = _request([("abcd", None), ("efgh", "length")], 8)
    assert "logprobs" not in body and "top_logprobs" not in body
    assert body["stream"] is True and body["temperature"] == 0
    assert body["max_tokens"] == 8 and body["ext"]["ignore_eos"] is True
    assert body["stream_options"] == {"include_usage": True}


def test_chunks_are_counted_by_their_characters_and_timed_on_arrival():
    row, _ = _request([("abcd", None), ("efgh", None), ("ij", "length")], 10)
    assert row["chunk_n"] == [4, 4, 2] and row["tokens"] == 10
    assert row["done"] and row["finish"] == "length" and stats.ok(row)
    assert row["chunk_s"] == sorted(row["chunk_s"])
    assert all(g >= 0.015 for g in stats.gaps_s(row))
    assert stats.ttft_s(row) >= 0.02 and row["end_s"] >= row["chunk_s"][-1]


@pytest.mark.parametrize("chunks,want,kw", [
    # a token that decoded to no text leaves the count short
    ([("abc", None), ("", None), ("efgh", "length")], 8, {}),
    # the stream ended without [DONE]
    ([("abcd", "length")], 4, {"done": False}),
    # refused
    ([], 4, {"status": 503}),
], ids=["token_without_text", "no_done", "refused"])
def test_a_short_unfinished_or_refused_response_is_a_failed_request(
        chunks, want, kw):
    row, _ = _request(chunks, want, **kw)
    assert stats.failed(row) and not stats.ok(row)
