"""dyntrace: span recorder, context/wire propagation, sampling no-op,
HTTP trace endpoints, and the end-to-end disagg trace (one trace_id
spanning frontend → route → prefill → kv_transfer stages → decode)."""

import asyncio
import json

import msgpack
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.disagg import DisaggRouter, PrefillWorker
from dynamo_tpu.llm.disagg.decode import build_disagg_decode
from dynamo_tpu.llm.disagg.protocols import RemotePrefillRequest
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import init_params
from dynamo_tpu.runtime import codec, tracing
from dynamo_tpu.runtime.runtime import DistributedRuntime

PS = 8


@pytest.fixture(autouse=True)
def fresh_tracer():
    """Every test gets its own tracer (full sampling, small ring)."""
    tracer = tracing.configure(sample=1.0, ring=4096)
    yield tracer
    tracing.configure(sample=1.0, ring=4096)


def tiny_cfg():
    return ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=8,
                            hidden_size=32, vocab_size=128)


def make_engine(params=None):
    ecfg = EngineConfig(page_size=PS, num_pages=64, max_batch=4,
                        prefill_chunk=32, batch_buckets=(1, 2, 4),
                        prefill_buckets=(8, 32), page_buckets=(8,),
                        watermark_pages=2)
    return JaxEngine(tiny_cfg(), ecfg, params=params)


# ------------------------------------------------------------- tracer core


def test_span_tree_and_ring(fresh_tracer):
    t = fresh_tracer
    with t.start_span("root", request_id="r1") as root:
        with t.start_span("child") as child:
            child.set_attribute("k", 1)
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id
    spans = t.snapshot()
    assert [s.name for s in spans] == ["child", "root"]  # end order
    assert all(s.end_time is not None for s in spans)
    # request join table + stage rollup
    tr = t.get_request_trace("r1")
    assert tr is not None and tr["trace_id"] == root.trace_id
    assert {s["name"] for s in tr["spans"]} == {"root", "child"}
    assert set(tr["stages"]) == {"root", "child"}


def test_wire_ctx_parenting(fresh_tracer):
    t = fresh_tracer
    with t.start_span("upstream") as up:
        ctx = t.current_trace_ctx()
    assert ctx == {"trace_id": up.trace_id, "span_id": up.span_id}
    # a span started from the wire dict (other process) joins the trace
    with t.start_span("downstream", parent=ctx) as down:
        assert down.trace_id == up.trace_id
        assert down.parent_id == up.span_id


def test_record_span_synthesizes_duration(fresh_tracer):
    t = fresh_tracer
    with t.start_span("parent") as p:
        t.record_span("stage", 0.25, parent=p, attributes={"x": 1})
    stage = [s for s in t.snapshot() if s.name == "stage"][0]
    assert stage.parent_id == p.span_id
    assert 0.2 < stage.duration_s < 0.3


def test_sampling_zero_is_total_noop():
    t = tracing.configure(sample=0.0)
    with t.start_span("root", request_id="r") as root:
        assert not root.recording
        # no propagation → wire envelopes gain NO field
        assert t.current_trace_ctx() is None
        with t.start_span("child") as child:
            assert not child.recording
    assert t.spans_recorded == 0
    assert t.snapshot() == []
    assert t.get_request_trace("r") is None
    # queue protocol: absent trace_ctx = absent key (no envelope growth)
    req = RemotePrefillRequest(request_id="r", token_ids=[1],
                               trace_ctx=t.current_trace_ctx())
    assert "trace_ctx" not in req.to_dict()


def test_unsampled_root_suppresses_descendants():
    t = tracing.configure(sample=0.0)
    with t.start_span("root"):
        # even if sampling were re-enabled, a noop ambient parent wins
        t.sample = 1.0
        with t.start_span("child") as child:
            assert not child.recording
    assert t.spans_recorded == 0


def test_ring_is_bounded():
    t = tracing.configure(sample=1.0, ring=8)
    for i in range(50):
        with t.start_span(f"s{i}"):
            pass
    assert len(t.snapshot()) == 8
    assert t.spans_recorded == 50


def test_jsonl_export(tmp_path):
    path = tmp_path / "trace.jsonl"
    t = tracing.configure(sample=1.0, jsonl=str(path))
    with t.start_span("op", request_id="rx") as sp:
        sp.set_attribute("n", 3)
    lines = [json.loads(line) for line in
             path.read_text().strip().splitlines()]
    assert len(lines) == 1
    rec = lines[0]
    assert rec["name"] == "op" and rec["trace_id"] == sp.trace_id
    assert rec["attributes"] == {"request_id": "rx", "n": 3}
    assert rec["duration_ms"] is not None


def test_traceparent_roundtrip(fresh_tracer):
    with fresh_tracer.start_span("root") as sp:
        hdr = tracing.format_traceparent(sp)
    ctx = tracing.parse_traceparent(hdr)
    assert ctx == {"trace_id": sp.trace_id, "span_id": sp.span_id}
    # malformed / unsampled headers are rejected
    assert tracing.parse_traceparent(None) is None
    assert tracing.parse_traceparent("not-a-header") is None
    assert tracing.parse_traceparent(
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
    assert tracing.parse_traceparent(
        "00-" + "a" * 32 + "-" + "1" * 16 + "-00") is None  # not sampled


# --------------------------------------------------- envelope wire compat


def test_codec_roundtrip_with_and_without_trace_ctx():
    """The two-part frame and msgpack envelope carry the trace field
    transparently; peers without it still interoperate (absent = None)."""
    ctx = {"trace_id": "a" * 32, "span_id": "b" * 16}
    chunk = {"kind": "chunk", "request_id": "r", "chunk_idx": 0,
             "n_chunks": 1, "page_ids": [1], "shape": [1], "dtype": "f",
             "k_len": 1}  # full registered frame: DYN_WIRE_VALIDATE-safe
    with_trace = codec.encode(codec.TwoPartMessage(
        {**chunk, "trace": ctx}, b"kv"))
    without = codec.encode(codec.TwoPartMessage(dict(chunk), b"kv"))
    msg1, rest1 = codec.decode_buffer(with_trace)
    msg2, rest2 = codec.decode_buffer(without)
    assert rest1 == b"" and rest2 == b""
    assert msg1.header["trace"] == ctx and msg1.body == b"kv"
    assert msg2.header.get("trace") is None  # old peer: no parent
    # DCP request envelope (component.Client.generate shape)
    env = {"req_id": "r", "conn": {"address": "h:1", "subject": "s"},
           "payload": b"p"}
    assert msgpack.unpackb(msgpack.packb(env, use_bin_type=True),
                           raw=False).get("trace") is None
    env["trace"] = ctx
    assert msgpack.unpackb(msgpack.packb(env, use_bin_type=True),
                           raw=False)["trace"] == ctx


# ------------------------------------------------------- end-to-end disagg


def _greedy_chat_body(stream=False):
    return {"model": "m", "stream": stream, "max_tokens": 6,
            "temperature": 0.0,
            "messages": [{"role": "user", "content": "hi there"}]}


async def _build_disagg_http(params, drt):
    """HTTP frontend → LocalChatChain → DisaggDecodeEngine (+ remote
    prefill worker), all in-process over real DCP/TCP planes."""
    from dynamo_tpu.llm.engines import LocalChatChain
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    decode_eng = make_engine(params)
    prefill_eng = make_engine(params)
    router = DisaggRouter(max_local_prefill_length=4)  # force remote
    disagg = await build_disagg_decode(drt, decode_eng, namespace="trace",
                                       router=router, watch_config=False)
    pw = PrefillWorker(drt, prefill_eng, namespace="trace")
    pw.start()
    mdc = ModelDeploymentCard(name="m", tokenizer_kind="byte",
                              context_length=256)
    service = HttpService()
    service.manager.add_chat_model("m", LocalChatChain(mdc, disagg))
    await service.start(host="127.0.0.1", port=0)
    return service, disagg, pw, decode_eng, prefill_eng


async def _teardown(service, disagg, pw, decode_eng, prefill_eng):
    await service.stop()
    await pw.stop()
    await disagg.transfer.stop()
    await prefill_eng.stop()
    await decode_eng.stop()


def test_disagg_trace_end_to_end(run_async):
    """One chat completion through the remote-prefill path yields ONE
    trace covering http → route → prefill → kv_transfer stages → decode,
    with consistent trace_id across the queue/transfer envelopes, all
    retrievable from /v1/traces/{request_id}."""

    async def main():
        import aiohttp
        import jax

        params = init_params(tiny_cfg(), jax.random.PRNGKey(5))
        drt = await DistributedRuntime.detached()
        handles = await _build_disagg_http(params, drt)
        service, disagg, pw = handles[0], handles[1], handles[2]
        base = f"http://127.0.0.1:{service.port}"
        rid = "trace-e2e-1"
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(f"{base}/v1/chat/completions",
                                     json=_greedy_chat_body(),
                                     headers={"X-Request-Id": rid}) as r:
                    assert r.status == 200
                    # X-Request-Id echoed; traceparent emitted
                    assert r.headers["X-Request-Id"] == rid
                    assert "traceparent" in r.headers
                    full = await r.json()
                assert full["choices"][0]["message"]["content"] is not None
                assert disagg.remote_prefills == 1
                assert disagg.remote_fallbacks == 0

                async with http.get(f"{base}/v1/traces/{rid}") as r:
                    assert r.status == 200
                    tr = await r.json()
        finally:
            await _teardown(*handles)
            await drt.shutdown()

        spans = tr["spans"]
        names = {s["name"] for s in spans}
        # the full disagg request path in ONE trace
        for expected in ("http.request", "preprocess", "route.disagg",
                         "prefill.remote", "prefill.forward",
                         "kv_transfer.send", "kv_transfer.extract",
                         "kv_transfer.wire", "kv_transfer.inject", "decode"):
            assert expected in names, f"missing span {expected}: {names}"
        assert len({s["trace_id"] for s in spans}) == 1
        by_name = {s["name"]: s for s in spans}
        ids = {s["span_id"] for s in spans}
        # parent/child links: every non-root span's parent is in the trace
        root = by_name["http.request"]
        assert root["parent_id"] is None
        for s in spans:
            if s is not root:
                assert s["parent_id"] in ids, s
        # the cross-process hops hang off the decode-side request spans
        assert by_name["prefill.forward"]["parent_id"] == \
            by_name["prefill.remote"]["span_id"]
        assert by_name["kv_transfer.send"]["parent_id"] == \
            by_name["prefill.remote"]["span_id"]
        assert by_name["kv_transfer.inject"]["parent_id"] == \
            by_name["kv_transfer.send"]["span_id"]
        assert by_name["preprocess"]["parent_id"] == root["span_id"]
        # stage rollup is serviceable for a breakdown
        assert tr["stages"]["http.request"] >= tr["stages"]["decode"]

    run_async(main())


def test_traces_listing_and_engine_timeline(run_async):
    """/v1/traces lists recent traces and exposes the engine step
    timeline (admit queue-wait, prefill/decode dispatches)."""

    async def main():
        import aiohttp
        import jax

        params = init_params(tiny_cfg(), jax.random.PRNGKey(6))
        drt = await DistributedRuntime.detached()
        handles = await _build_disagg_http(params, drt)
        service = handles[0]
        base = f"http://127.0.0.1:{service.port}"
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(f"{base}/v1/chat/completions",
                                     json=_greedy_chat_body(),
                                     headers={"X-Request-Id": "list-1"}) as r:
                    assert r.status == 200
                    await r.json()
                async with http.get(f"{base}/v1/traces") as r:
                    assert r.status == 200
                    listing = await r.json()
                # unknown request id → 404 with the id echoed
                async with http.get(f"{base}/v1/traces/nope") as r:
                    assert r.status == 404
                # ITL + stage histograms in the exposition
                async with http.get(f"{base}/metrics") as r:
                    metrics = await r.text()
        finally:
            await _teardown(*handles)
            await drt.shutdown()

        assert any(t["request_id"] == "list-1" for t in listing["traces"])
        # both engines registered a step timeline; events carry the fields
        timelines = listing["engine_steps"]
        assert timelines, "no engine step timelines registered"
        events = [e for tl in timelines.values() for e in tl]
        kinds = {e["kind"] for e in events}
        assert "admit" in kinds and "prefill" in kinds
        admits = [e for e in events if e["kind"] == "admit"]
        assert all("queue_wait_ms" in e and "occupancy" in e
                   for e in admits)
        assert "dyn_llm_http_service_stage_duration_seconds_bucket" in metrics
        assert 'stage="prefill.remote"' in metrics

    run_async(main())


def test_sampling_zero_end_to_end(run_async):
    """DYN_TRACE_SAMPLE=0: the full disagg path serves identically with
    zero spans recorded and zero trace fields on any envelope."""

    async def main():
        import aiohttp
        import jax

        tracer = tracing.configure(sample=0.0)
        params = init_params(tiny_cfg(), jax.random.PRNGKey(7))
        drt = await DistributedRuntime.detached()
        handles = await _build_disagg_http(params, drt)
        service, disagg = handles[0], handles[1]
        base = f"http://127.0.0.1:{service.port}"
        rid = "unsampled-1"
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(f"{base}/v1/chat/completions",
                                     json=_greedy_chat_body(stream=True),
                                     headers={"X-Request-Id": rid}) as r:
                    assert r.status == 200
                    # the request id still echoes on the SSE response...
                    assert r.headers["X-Request-Id"] == rid
                    # ...but no traceparent: nothing was sampled
                    assert "traceparent" not in r.headers
                    async for line in r.content:
                        if line.decode().strip() == "data: [DONE]":
                            break
                assert disagg.remote_prefills == 1
                # no SPANS at sample=0 — but dynaprof cost attribution is
                # always-on, so /v1/traces/{rid} serves a cost-only
                # payload with an empty span list instead of a 404
                async with http.get(f"{base}/v1/traces/{rid}") as r:
                    assert r.status == 200
                    body = await r.json()
                    assert body["spans"] == []
                    assert body["cost"]["decode_tokens"] >= 1
                async with http.get(f"{base}/v1/traces/never-seen") as r:
                    assert r.status == 404
        finally:
            await _teardown(*handles)
            await drt.shutdown()

        # zero overhead: no span ever touched the ring
        assert tracer.spans_recorded == 0
        assert tracer.snapshot() == []

    run_async(main())


def test_itl_recorded_for_streams(run_async):
    """Streaming responses feed the ITL histogram next to TTFT."""

    async def main():
        import aiohttp

        from dynamo_tpu.engine.echo import EchoEngineCore
        from dynamo_tpu.llm.engines import LocalChatChain
        from dynamo_tpu.llm.http.service import HttpService
        from dynamo_tpu.llm.model_card import ModelDeploymentCard

        mdc = ModelDeploymentCard(name="m", tokenizer_kind="byte",
                                  context_length=256)
        service = HttpService()
        service.manager.add_chat_model(
            "m", LocalChatChain(mdc, EchoEngineCore(delay_ms=0)))
        await service.start(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(f"{base}/v1/chat/completions",
                                     json=_greedy_chat_body(stream=True)) as r:
                    assert r.status == 200
                    async for line in r.content:
                        if line.decode().strip() == "data: [DONE]":
                            break
                async with http.get(f"{base}/metrics") as r:
                    metrics = await r.text()
        finally:
            await service.stop()

        assert "# TYPE dyn_llm_http_service_itl_seconds histogram" in metrics
        assert 'dyn_llm_http_service_itl_seconds_count{model="m"}' in metrics
        assert 'dyn_llm_http_service_time_to_first_token_seconds_count' \
            in metrics

    run_async(main())


def test_request_id_logging_filter():
    """Log records carry the bound request id (JSONL joinable with
    traces), independent of sampling."""
    import logging as _logging

    from dynamo_tpu.runtime.logging import JsonlFormatter, RequestIdFilter

    tracing.configure(sample=0.0)  # sampling off: logs still join
    tracing.bind_request_id("log-join-1")
    rec = _logging.LogRecord("dynamo_tpu.test", _logging.INFO, __file__, 1,
                             "served", None, None)
    assert RequestIdFilter().filter(rec)
    out = json.loads(JsonlFormatter().format(rec))
    assert out["request_id"] == "log-join-1"
    tracing.bind_request_id(None)


def test_prefill_queue_carries_trace_ctx(run_async):
    """RemotePrefillRequest round-trips trace_ctx over the real queue;
    absent field stays absent."""

    async def main():
        from dynamo_tpu.llm.disagg import PrefillQueue

        drt = await DistributedRuntime.detached()
        try:
            q = PrefillQueue(drt.dcp, "tq")
            ctx = {"trace_id": "c" * 32, "span_id": "d" * 16}
            await q.put(RemotePrefillRequest(request_id="a", token_ids=[1],
                                             trace_ctx=ctx))
            await q.put(RemotePrefillRequest(request_id="b", token_ids=[2]))
            got_a = await q.pull(timeout=1.0)
            got_b = await q.pull(timeout=1.0)
            assert got_a.trace_ctx == ctx
            assert got_b.trace_ctx is None
        finally:
            await drt.shutdown()

    run_async(main())


# ------------------------------------------- the engine's request phases

REQUEST_STAGES = {"http.request", "preprocess", "engine.queue",
                  "engine.prefill_wait", "engine.prefill", "engine.decode",
                  "http.first_chunk"}


def test_record_span_takes_its_own_start(fresh_tracer):
    """With ``start`` the span lies where it happened, not "ends now"."""
    import time

    t = fresh_tracer
    began = time.monotonic() - 5.0
    with t.start_span("parent") as p:
        t.record_span("phase", 0.5, start=began, parent=p)
        t.record_span("ends-now", 0.5, parent=p)
    phase = [s for s in t.snapshot() if s.name == "phase"][0]
    assert phase.start == began and phase.end_time == began + 0.5
    assert phase.duration_s == pytest.approx(0.5)
    assert phase.wall_start == pytest.approx(time.time() - 5.0, abs=0.5)
    late = [s for s in t.snapshot() if s.name == "ends-now"][0]
    assert late.end_time == pytest.approx(time.monotonic(), abs=0.5)
    assert phase.parent_id == late.parent_id == p.span_id


async def _serve_one_stream(rid: str):
    """One streamed chat request through HttpService -> LocalChatChain
    -> a tiny JaxEngine; returns (/v1/traces/{rid} body, engine stats)."""
    import aiohttp

    from dynamo_tpu.llm.engines import LocalChatChain
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    engine = make_engine()
    mdc = ModelDeploymentCard(name="tiny-jax", tokenizer_kind="byte",
                              context_length=256)
    service = HttpService()
    service.manager.add_chat_model("tiny-jax", LocalChatChain(mdc, engine))
    await service.start(host="127.0.0.1", port=0)
    base = f"http://127.0.0.1:{service.port}"
    try:
        async with aiohttp.ClientSession() as http:
            body = {"model": "tiny-jax", "stream": True, "max_tokens": 8,
                    "messages": [{"role": "user", "content": "hello"}]}
            async with http.post(f"{base}/v1/chat/completions", json=body,
                                 headers={"X-Request-Id": rid}) as r:
                assert r.status == 200
                async for line in r.content:
                    if line.decode().strip() == "data: [DONE]":
                        break
            # the stream can end a moment before the engine emits its
            # finish (the page release waits for the window in flight),
            # and the engine's spans are recorded with that finish
            for _ in range(100):
                async with http.get(f"{base}/v1/traces/{rid}") as r:
                    # 404 until a span or the cost block exists
                    trace_body = await r.json() if r.status == 200 else {}
                if (trace_body.get("cost") or {}).get("finish_reason"):
                    break
                await asyncio.sleep(0.05)
    finally:
        await service.stop()
        stats = engine.stats()
        await engine.stop()
    return trace_body, stats


def test_engine_spans_join_the_http_request_trace(run_async):
    """/v1/traces/{request_id} shows the whole path under one trace_id:
    the frontend's spans, the engine's four request phases (recorded at
    finish with their own start times) and http.first_chunk."""
    body, stats = run_async(_serve_one_stream("phases-1"))
    spans = {s["name"]: s for s in body["spans"]}
    assert REQUEST_STAGES <= set(spans) and REQUEST_STAGES <= set(
        body["stages"])
    assert {s["trace_id"] for s in body["spans"]} == {body["trace_id"]}
    root = spans["http.request"]
    assert spans["http.first_chunk"]["parent_id"] == root["span_id"]
    # the engine's spans hang from whatever was ambient in generate()
    parents = {spans[n]["parent_id"] for n in REQUEST_STAGES
               if n.startswith("engine.")}
    assert len(parents) == 1 and parents <= {s["span_id"]
                                             for s in body["spans"]}
    # in order, touching end to start, inside the request's span
    order = ["engine.queue", "engine.prefill_wait", "engine.prefill",
             "engine.decode"]
    for a, b in zip(order, order[1:]):
        assert spans[a]["start_ms"] + spans[a]["duration_ms"] == \
            pytest.approx(spans[b]["start_ms"], abs=1.0)
    assert spans["engine.queue"]["start_ms"] >= root["start_ms"] - 1.0
    first = spans["http.first_chunk"]
    assert first["start_ms"] == pytest.approx(root["start_ms"], abs=1.0)
    engine_ttft = sum(spans[n]["duration_ms"] for n in order[:3])
    assert engine_ttft <= first["duration_ms"] + 1.0
    assert body["cost"]["first_token_ms"] == pytest.approx(
        spans["engine.prefill"]["duration_ms"], abs=0.01)
    assert stats["first_tokens_total"] == 1


def test_sample_zero_records_no_engine_span_and_counts_the_same(run_async):
    tracer = tracing.configure(sample=0.0)
    body, stats = run_async(_serve_one_stream("phases-0"))
    assert body["spans"] == [] and tracer.spans_recorded == 0
    assert stats["first_tokens_total"] == 1
    assert stats["engine_ttft_seconds_total"] > 0
    split = (stats["queue_wait_seconds_total"]
             + stats["prefill_wait_seconds_total"]
             + stats["first_token_seconds_total"])
    assert split == pytest.approx(stats["engine_ttft_seconds_total"],
                                  abs=1e-3)
    for key in ("prefill_wait_ms", "first_token_ms", "decode_ms"):
        assert body["cost"][key] > 0
