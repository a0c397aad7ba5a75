"""The engine accounts for its own time: the step thread's phase ledger
closes on the wall clock, the per-request TTFT split adds up (and counts
a preempted request once), the slot-fill counters match a hand count,
the dyn.* annotations land in a profiler trace, and the named scopes
change no program."""

import asyncio
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine import profiler as engine_profiler
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context

SPLIT = ("queue_wait_seconds_total", "prefill_wait_seconds_total",
         "first_token_seconds_total")


def _req(tokens, mt=6):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=mt, ignore_eos=True),
        eos_token_ids=[])


def _engine(**overrides) -> JaxEngine:
    kw = dict(page_size=8, num_pages=64, max_batch=4, prefill_chunk=32,
              batch_buckets=(1, 2, 4), prefill_buckets=(16, 32),
              page_buckets=(8,), max_prefill_batch=2, decode_steps=2)
    kw.update(overrides)
    return JaxEngine(ModelConfig.tiny(), EngineConfig(**kw), seed=0)


async def _one(eng, req):
    """(tokens, finish cost block, arrival -> first emission seconds as
    the caller's own clock saw it)."""
    toks, cost, first = [], None, None
    t0 = time.monotonic()
    async for out in eng.generate(req, Context()):
        if out.token_ids and first is None:
            first = time.monotonic() - t0
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            assert out.finish_reason != "error"
            cost = out.cost
    return toks, cost, first


def _phases(stats) -> dict:
    return stats["step_phase_seconds_total"]


def test_phases_close_on_the_wall_clock(run_async):
    """Between two stats() calls the phases' deltas add up to the wall
    time, busy or idle, and every phase whose code ran is > 0."""
    eng = _engine()
    eng.warmup()

    async def main():
        s0, t0 = eng.stats(), time.perf_counter()
        await asyncio.gather(*(_one(eng, _req(range(1 + i, 20 + i), mt=9))
                               for i in range(3)))
        await asyncio.sleep(0.3)            # the loop sleeps: idle
        s1, t1 = eng.stats(), time.perf_counter()
        await eng.stop()
        return s0, s1, t1 - t0

    s0, s1, wall = run_async(main())
    assert set(_phases(s1)) == set(engine_profiler.PHASES)
    d = {k: _phases(s1)[k] - _phases(s0)[k] for k in _phases(s1)}
    assert sum(d.values()) == pytest.approx(wall, rel=0.02)
    assert all(v >= 0.0 for v in d.values())
    for ran in ("admit", "dispatch_prefill", "readback_prefill",
                "process_prefill", "dispatch_window", "readback_window",
                "process_window", "between_steps", "idle", "other"):
        assert d[ran] > 0.0, ran
    assert d["kv_tier"] == 0.0              # no host tier configured
    assert d["idle"] >= 0.25
    assert s1["step_iterations_total"] > s0["step_iterations_total"]
    assert s1["warmup_seconds"] > 0
    eng.fence.disarm()


def test_a_nested_phase_takes_the_clock_and_gives_it_back(monkeypatch):
    """A flush inside a dispatch (readback inside dispatch_window) must
    not be counted twice: the ledger hands the clock to the inner phase
    and back. The test hands the ledger its clock (``PhaseLedger.clock``,
    read when the ledger is made), so the account is exact whatever else
    the machine is doing."""
    from dynamo_tpu.runtime.profiling import PhaseLedger

    now = [100.0]
    monkeypatch.setattr(PhaseLedger, "clock", staticmethod(lambda: now[0]))

    def sleep(seconds):
        now[0] += seconds

    prof = engine_profiler.EngineProfiler("ledger-test")
    prof.step_begin()
    with prof.phase("dispatch_window"):
        sleep(0.02)
        with prof.phase("readback_window"):
            sleep(0.03)
        sleep(0.01)
    prof.step_end()
    snap = prof.phase_snapshot()
    assert snap["readback_window"] == pytest.approx(0.03)
    assert snap["dispatch_window"] == pytest.approx(0.03)
    assert snap["other"] == 0.0
    assert sum(snap.values()) == pytest.approx(0.06)
    prof.slept = True
    sleep(0.02)
    # the open gap, settled
    assert prof.phase_snapshot()["idle"] == pytest.approx(0.02)
    prof.step_begin()
    prof.step_end()
    assert prof.phase_snapshot()["idle"] == pytest.approx(0.02)
    assert not prof.slept
    assert prof.step_iterations == 2


def test_snapshot_closes_while_the_step_thread_switches():
    """stats() reads the ledger from another thread. Whatever the step
    thread is doing, a snapshot's phases add up to the time since the
    ledger opened: a read torn across one switch would be off by a
    whole 2 ms phase."""
    import sys
    import threading

    prof = engine_profiler.EngineProfiler("ledger-stress")
    opened = prof._t
    stop = threading.Event()

    def step_thread():
        while not stop.is_set():
            prof.step_begin()
            with prof.phase("dispatch_window"):
                time.sleep(0.002)
                with prof.phase("readback_window"):
                    time.sleep(0.002)
            prof.step_end()

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    worker = threading.Thread(target=step_thread, daemon=True)
    try:
        worker.start()
        reads = 0
        deadline = time.perf_counter() + 1.0
        while time.perf_counter() < deadline:
            before = time.perf_counter()
            total = sum(prof.phase_snapshot().values())
            after = time.perf_counter()
            assert before - opened - 5e-4 <= total <= after - opened + 5e-4
            reads += 1
        assert reads > 100
    finally:
        stop.set()
        worker.join(5)
        sys.setswitchinterval(was)
    assert not worker.is_alive()
    assert prof.step_iterations > 50


def test_ttft_split_adds_up_per_request_and_in_the_counters(run_async):
    eng = _engine()
    eng.warmup()

    async def main():
        s0 = eng.stats()
        out = await asyncio.gather(
            _one(eng, _req(range(1, 20), mt=7)),
            _one(eng, _req([7] * 24, mt=5)),
            _one(eng, _req(range(40, 45), mt=4)))
        s1 = eng.stats()
        await eng.stop()
        return s0, s1, out

    s0, s1, out = run_async(main())
    for toks, cost, first_s in out:
        parts = (cost["queue_wait_ms"] + cost["prefill_wait_ms"]
                 + cost["first_token_ms"])
        # arrival -> first emission as the engine stamped it; the
        # caller's clock starts a little earlier and reads a hop later
        assert 0 < parts <= first_s * 1000.0 + 0.5
        assert parts == pytest.approx(first_s * 1000.0, abs=50.0)
        assert cost["decode_ms"] > 0 and len(toks) >= 4
    d = {k: s1[k] - s0[k] for k in SPLIT + ("engine_ttft_seconds_total",
                                            "first_tokens_total")}
    assert d["first_tokens_total"] == 3
    assert sum(d[k] for k in SPLIT) == pytest.approx(
        d["engine_ttft_seconds_total"], abs=1e-3)   # queue_wait is rounded
    per_request = sum(c["queue_wait_ms"] + c["prefill_wait_ms"]
                      + c["first_token_ms"] for _, c, _ in out)
    assert per_request == pytest.approx(
        1000.0 * d["engine_ttft_seconds_total"], abs=0.1)
    eng.fence.disarm()


def test_a_preempted_and_resumed_sequence_is_counted_once(run_async):
    """Pool pressure preempts running rows (they re-enter waiting and
    prefill again); each request still adds one first token, one queue
    wait and the stamps of its first pass."""
    eng = _engine(num_pages=16, watermark_pages=1, max_batch=4,
                  prefill_buckets=(16, 32))
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s): s for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)

    eng._grow_or_preempt = spy

    async def main():
        out = await asyncio.wait_for(asyncio.gather(*(
            _one(eng, _req(range(i * 16, i * 16 + 16), mt=16))
            for i in range(4))), 120)
        stats = eng.stats()
        await eng.stop()
        return out, stats

    out, stats = run_async(main())
    assert preempted, "the pool was meant to run out"
    assert all(len(toks) == 16 for toks, _, _ in out)
    assert stats["first_tokens_total"] == 4
    assert sum(stats[k] for k in SPLIT) == pytest.approx(
        stats["engine_ttft_seconds_total"], abs=1e-3)
    for seq in preempted:
        assert seq.t_admit is not None and seq.t_first_dispatch is not None
        assert seq.arrival <= seq.t_admit <= seq.t_first_dispatch \
            <= seq.t_first_token <= seq.last_emit_t


def test_fill_counters_match_a_hand_count(run_async):
    """One request, 19 prompt tokens, 5 output tokens, K = 2: one
    prefill of 19 tokens in a PB 1 x T 32 program; the first token comes
    from prefill and the other 4 from decode windows of one live row in
    a 1-row bucket. Windows are dispatched ahead of their readback, so
    there are at least 2 and rows == slots == 2 per window."""
    eng = _engine()
    eng.warmup()
    # the hand count is of the small bucket: at this size warmup()'s own
    # timing of PB 1 against PB 2 is a coin's (choose_prefill_bucket)
    eng._prefill_costs = {}

    async def main():
        s0 = eng.stats()
        toks, _, _ = await _one(eng, _req(range(1, 20), mt=5))
        s1 = eng.stats()
        await eng.stop()
        return toks, {k: s1[k] - s0[k] for k in s1
                      if k.endswith("_total") and isinstance(s1[k], int)}

    toks, d = run_async(main())
    assert len(toks) == 5
    assert d["prefill_tokens_total"] == 19
    assert d["prefill_slots_total"] == 32 and d["prefill_dispatches_total"] == 1
    windows = d["decode_windows_total"]
    assert windows >= 2
    assert d["decode_rows_total"] == d["decode_slots_total"] == 2 * windows
    eng.fence.disarm()


def test_rows_never_exceed_slots_in_a_padded_bucket(run_async):
    """Three live rows run in the 4-row bucket: 3 of 4 slots filled."""
    eng = _engine()
    eng.warmup()

    async def main():
        await asyncio.gather(*(_one(eng, _req(range(1 + i, 12 + i), mt=8))
                               for i in range(3)))
        stats = eng.stats()
        await eng.stop()
        return stats

    s = run_async(main())
    assert 0 < s["decode_rows_total"] < s["decode_slots_total"]
    assert s["decode_slots_total"] % eng.ecfg.decode_steps == 0
    assert s["prefill_tokens_total"] < s["prefill_slots_total"]
    eng.fence.disarm()


def test_annotations_land_in_a_profiler_trace(run_async, tmp_path):
    """A jax.profiler trace of a few steps on the CPU holds the step
    thread's dyn.* events, and benchmark/harness/host_trace.py finds
    them."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.harness import host_trace

    eng = _engine()
    eng.warmup()

    async def main():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            await _one(eng, _req(range(1, 20), mt=6))
        finally:
            jax.profiler.stop_trace()
        await eng.stop()

    run_async(main())
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert files
    loaded = host_trace.load(files[-1])
    names = {name for name, _, _ in loaded["phases"]}
    assert {"dyn.step", "dyn.dispatch_window", "dyn.readback_window",
            "dyn.dispatch_prefill", "dyn.process_window"} <= names
    assert all(d >= 0 for _, _, d in loaded["phases"])
    assert loaded["ops"] == {}              # no device plane on the CPU
    eng.fence.disarm()


def _lowered(monkeypatch, scoped: bool):
    """The prefill step and the decode window of a tiny MoE, lowered."""
    import contextlib

    jax.clear_caches()      # sample_tokens is jitted: trace it afresh
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2)
    B, T, P, ps, pages = 2, 16, 4, 8, 16
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    kv_k, kv_v = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, llama.KVCacheSpec(pages, ps)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)    # noqa: E731
    prefill, _ = llama.make_step_fns(cfg, allow_pallas=False)
    window = llama.make_decode_window_fn(cfg, allow_pallas=False)
    return (
        prefill.lower(params, i32(B, T), i32(B, T), kv_k, kv_v, i32(B, P),
                      i32(B, T), i32(B), i32(B, T // ps)),
        window.lower(params, i32(B), i32(B),
                     jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B), i32(B),
                     kv_k, kv_v, i32(B, P), f32(B), i32(B), f32(B),
                     jax.ShapeDtypeStruct((B,), jnp.uint32), i32(B, 4),
                     None, k_steps=2, logprobs_topn=0))


@pytest.mark.parametrize("program", [0, 1], ids=["prefill", "window"])
def test_scopes_change_no_program(monkeypatch, program):
    """Lowered with and without the named scopes, a step program is the
    same once the locations (where a scope lives) are left out; with
    them, only the scoped one names the layers."""
    scoped = _lowered(monkeypatch, scoped=True)[program]
    plain = _lowered(monkeypatch, scoped=False)[program]
    assert scoped.as_text() == plain.as_text()
    named = scoped.as_text(debug_info=True)
    want = ["attn", "moe/moe.router", "moe/moe.experts", "lm_head"]
    if program == 1:
        want += ["sample", "kv_carry"]
    unnamed = plain.as_text(debug_info=True)
    for scope in want:
        at = re.compile(r'["/]' + re.escape(scope) + "/")
        assert at.search(named), scope
        assert not at.search(unnamed), scope
