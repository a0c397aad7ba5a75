"""The host's other threads account for their time as the step thread
does: the event loop's ledger (flat brackets that add up to its wall
time), the detokeniser workers' bracket, CPU time beside wall time on the
step thread, per-thread CPU / run-queue time and GC pauses in stats(),
the frontend's two legs of a request, all cumulative; and what the
brackets cost, held by a count of clock reads, not by a stopwatch."""

import asyncio
import gc
import glob
import json
import sys
import threading
import time

import pytest

from dynamo_tpu.engine import profiler as engine_profiler
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.engines import LocalChatChain
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.protocols.common import (EngineOutput,
                                             PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context, profiling
from dynamo_tpu.runtime.profiling import LOOP_PHASES, LoopLedger, PhaseLedger

# every key stats() gained in PR 35; all cumulative
FLAT = ("loop_lag_seconds_total", "loop_lag_samples_total",
        "intake_seconds_total", "intake_total",
        "emit_to_wire_seconds_total", "emit_to_wire_total",
        "first_emit_to_wire_seconds_total", "first_emit_to_wire_total",
        "gc_pause_seconds_total")
NESTED = ("step_phase_cpu_seconds_total", "loop_phase_seconds_total",
          "loop_phase_calls_total", "thread_cpu_seconds_total",
          "gc_collections_total")
RUNQ = "thread_runq_wait_seconds_total"     # only a kernel with schedstat


def _engine(**overrides) -> JaxEngine:
    kw = dict(page_size=8, num_pages=64, max_batch=4, prefill_chunk=32,
              batch_buckets=(1, 2, 4), prefill_buckets=(16, 32),
              page_buckets=(8,), max_prefill_batch=2, decode_steps=2)
    kw.update(overrides)
    return JaxEngine(ModelConfig.tiny(), EngineConfig(**kw), seed=0)


class _Clock:
    """A clock a test owns: counts its reads, moves only when told."""

    def __init__(self, now: float = 100.0):
        self.now = now
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return self.now


# ------------------------------------------------------- the flat ledger

def test_flat_brackets_are_disjoint_and_add_up_to_the_wall_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(PhaseLedger, "clock", clock)
    led = LoopLedger()
    opened = clock.now

    def spend(seconds):
        clock.now += seconds

    spend(0.5)                      # other
    led.enter("intake")
    spend(0.25)
    led.leave("intake")
    spend(0.125)                    # other
    led.enter("deliver")
    spend(1.0)
    led.enter("encode_write")       # takes the clock over: no leave between
    spend(2.0)
    led.leave("deliver")            # not the holder: nothing happens
    spend(0.5)
    led.leave("encode_write")
    spend(4.0)                      # other
    seconds, cpu, calls = led.snapshot()
    assert cpu is None              # the loop's ledger reads no CPU clock
    assert seconds == {"intake": 0.25, "deliver": 1.0, "encode_write": 2.5,
                       "engine_loop": 0.0, "other": 4.625}
    assert sum(seconds.values()) == clock.now - opened
    assert calls == {"intake": 1, "deliver": 1, "encode_write": 1,
                     "engine_loop": 0, "other": 0}


def test_a_suspended_brackets_clock_goes_to_the_bracket_that_takes_over(
        monkeypatch):
    """resp.write suspends (the client stopped reading): encode_write's
    interval ends where another task's bracket begins, and the late
    leave of the suspended task changes nothing."""
    clock = _Clock()
    monkeypatch.setattr(PhaseLedger, "clock", clock)
    led = LoopLedger()
    led.enter("encode_write")       # task A
    clock.now += 1.0
    led.enter("deliver")            # task B, while A is suspended
    clock.now += 2.0
    led.leave("deliver")
    clock.now += 4.0                # the selector's wait: other
    led.leave("encode_write")       # A resumes and leaves: too late
    clock.now += 8.0
    seconds = led.snapshot()[0]
    assert seconds["encode_write"] == 1.0 and seconds["deliver"] == 2.0
    assert seconds["other"] == 12.0


def test_a_flat_snapshot_is_consistent_while_its_thread_switches():
    led = LoopLedger()
    opened = led._t
    stop = threading.Event()

    def loop_thread():
        while not stop.is_set():
            led.enter("deliver")
            led.leave("deliver")
            led.enter("encode_write")
            time.sleep(0.001)
            led.leave("encode_write")

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    worker = threading.Thread(target=loop_thread, daemon=True)
    try:
        worker.start()
        reads = 0
        deadline = time.perf_counter() + 0.6
        while time.perf_counter() < deadline:
            before = time.perf_counter()
            total = sum(led.snapshot()[0].values())
            after = time.perf_counter()
            assert before - opened - 5e-4 <= total <= after - opened + 5e-4
            reads += 1
        assert reads > 100
    finally:
        stop.set()
        worker.join(5)
        sys.setswitchinterval(was)
    assert led.snapshot()[2]["deliver"] > 50


def test_the_null_ledger_takes_every_call():
    led = profiling.NULL_LEDGER
    led.enter("deliver")
    led.leave("deliver")
    led.add("intake", 1.0)
    assert profiling.loop_ledger() is led       # no running loop here


# --------------------------------------------- CPU beside wall, by phase

def test_step_phases_read_the_cpu_clock_beside_the_wall_clock(monkeypatch):
    wall, cpu = _Clock(100.0), _Clock(7.0)
    monkeypatch.setattr(PhaseLedger, "clock", wall)
    monkeypatch.setattr(PhaseLedger, "cpu_clock", cpu)
    prof = engine_profiler.EngineProfiler("cpu-test")

    def spend(wall_s, cpu_s):
        wall.now += wall_s
        cpu.now += cpu_s

    cpu.now = 50.0                  # another thread's clock made the ledger
    prof.step_begin()               # binds the step thread: CPU restamped
    with prof.phase("process_window"):
        spend(0.5, 0.25)            # half of it waiting for the GIL
        with prof.phase("readback_window"):
            spend(2.0, 0.0)         # blocked on the device
        spend(0.5, 0.5)
    prof.step_end()
    spend(1.0, 0.125)               # the gap: between_steps
    seconds, cpus = prof.phase_snapshot(), prof.cpu_snapshot()
    assert set(cpus) == set(seconds) == set(engine_profiler.PHASES)
    assert seconds["process_window"] == 1.0 and cpus["process_window"] == 0.75
    assert seconds["readback_window"] == 2.0
    assert cpus["readback_window"] == 0.0
    assert seconds["between_steps"] == 1.0
    assert cpus["between_steps"] == 0.0     # settled at the next switch
    prof.step_begin()
    assert prof.cpu_snapshot()["between_steps"] == 0.125
    assert prof.native_id == threading.get_native_id()
    for name in engine_profiler.PHASES:
        assert prof.cpu_snapshot()[name] <= prof.phase_snapshot()[name]
    prof.step_end()


# ------------------------------------- absent, not zero, where there is none

def test_schedstat_and_gc_counters_are_absent_where_the_platform_has_none(
        monkeypatch, run_async):
    real_open = open

    def no_proc(path, *a, **kw):
        if str(path).startswith("/proc/self/task/"):
            raise FileNotFoundError(path)
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", no_proc)
    monkeypatch.setattr(profiling, "_gc_clock", None)
    monkeypatch.delattr(gc, "callbacks")

    async def main():
        profiling.acquire_loop_profiler()
        try:
            return profiling.host_stats(threading.get_native_id())
        finally:
            await profiling.release_loop_profiler()

    stats = run_async(main())
    for key in ("thread_cpu_seconds_total", "thread_runq_wait_seconds_total",
                "gc_pause_seconds_total", "gc_collections_total"):
        assert key not in stats
    assert set(stats["loop_phase_seconds_total"]) == set(LOOP_PHASES) | {
        "detok"}
    assert profiling._thread_times(None) is None
    assert profiling._thread_times(threading.get_native_id()) is None


def test_a_kernel_without_schedstat_still_gives_cpu_ticks_and_no_runqueue(
        monkeypatch, run_async):
    """gVisor, which the benchmark's machines run: no schedstat, and
    utime + stime in ticks in ``stat`` (a command name may hold a
    parenthesis and a space)."""
    import io

    real_open = open

    def gvisor(path, *a, **kw):
        path = str(path)
        if path.endswith("/schedstat"):
            raise FileNotFoundError(path)
        if path.startswith("/proc/self/task/") and path.endswith("/stat"):
            return io.StringIO("299 (py (thon) 3) R 297 294 1 0 0 0 0 0 0 0 "
                               "84 16 0 0 20 0 2 0 2568 103260160 5298\n")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", gvisor)
    monkeypatch.setattr(profiling, "_CLK_TCK", 100.0)
    assert profiling._thread_times(299) == (1.0, None)

    async def main():
        profiling.acquire_loop_profiler()
        try:
            return profiling.host_stats(299)
        finally:
            await profiling.release_loop_profiler()

    stats = run_async(main())
    assert stats["thread_cpu_seconds_total"]["step"] == 1.0
    assert stats["thread_cpu_seconds_total"]["loop"] == 1.0
    assert "thread_runq_wait_seconds_total" not in stats


def test_this_threads_cpu_and_runqueue_seconds_grow():
    got = profiling._thread_times(threading.get_native_id())
    if got is None:
        pytest.skip("no /proc/self/task/<tid>/ on this platform")
    t0 = time.thread_time()         # this thread's CPU, not the wall:
    while time.thread_time() - t0 < 0.06:   # a loaded machine cannot starve it
        pass
    again = profiling._thread_times(threading.get_native_id())
    assert again[0] >= got[0] + 0.03
    if got[1] is not None:
        assert again[1] >= got[1] >= 0.0


def test_a_collection_is_counted_by_generation_and_timed(run_async):
    async def main():
        profiling.acquire_loop_profiler()
        try:
            s0 = profiling.host_stats()
            gc.collect(0)
            gc.collect(2)
            return s0, profiling.host_stats()
        finally:
            await profiling.release_loop_profiler()

    s0, s1 = run_async(main())
    c0, c1 = s0["gc_collections_total"], s1["gc_collections_total"]
    assert c1["0"] >= c0["0"] + 1 and c1["2"] >= c0["2"] + 1
    assert s1["gc_pause_seconds_total"] > s0["gc_pause_seconds_total"]


# ------------------------------- real requests through the whole frontend

async def _stream(http, base, i, max_tokens=10):
    body = {"model": "m", "stream": True, "max_tokens": max_tokens,
            "messages": [{"role": "user", "content": f"hello {i}"}]}
    content = 0
    async with http.post(f"{base}/v1/chat/completions", json=body) as r:
        assert r.status == 200
        async for line in r.content:
            if line.startswith(b"data: {"):
                delta = json.loads(line[6:])["choices"][0]["delta"]
                content += bool(delta.get("content"))
    return content


def _flatten(stats):
    out = {k: stats[k] for k in FLAT}
    for k in NESTED + ((RUNQ,) if RUNQ in stats else ()):
        out.update({f"{k}/{n}": v for n, v in stats[k].items()})
    return out


def test_every_new_key_is_cumulative_and_the_loops_slots_add_up(run_async):
    """Real requests through HttpService -> LocalChatChain -> Backend ->
    JaxEngine on the CPU. Between two stats() reads with requests in
    flight at both, the loop thread's five slots add up to the wall
    time within 1 ms (``detok`` is the workers' time, beside them)."""
    eng = _engine()
    eng.warmup()

    async def main():
        import aiohttp

        svc = HttpService()
        svc.manager.add_chat_model("m", LocalChatChain(
            ModelDeploymentCard(name="m", tokenizer_kind="byte"), eng))
        await svc.start(host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{svc.port}"
        async with aiohttp.ClientSession() as http:
            await _stream(http, base, 0)
            background = asyncio.gather(*(
                _stream(http, base, 10 + i, max_tokens=40)
                for i in range(3)))
            await asyncio.sleep(0.01)
            # the three are taken in before the first read, however busy
            # the machine is (under six workers 10 ms was not always
            # enough: the delta below read 6 intakes for 4, PR 50)
            while eng.stats()["intake_total"] < 4:
                await asyncio.sleep(0.005)
            # the ledger is read somewhere inside a stats() call: the
            # wall time between two reads lies between these two
            before0 = time.perf_counter()
            s0, after0 = eng.stats(), time.perf_counter()
            content = await asyncio.gather(*(
                _stream(http, base, i) for i in range(4)))
            mid, before1 = eng.stats(), time.perf_counter()
            s1, after1 = eng.stats(), time.perf_counter()
            content += await background
        await svc.stop()
        await eng.stop()
        return (s0, mid, s1, before1 - after0, after1 - before0, content)

    s0, mid, s1, least, most, content = run_async(main())
    wall = most
    a, m, b = _flatten(s0), _flatten(mid), _flatten(s1)
    assert set(a) == set(b)
    for key in b:
        assert a[key] <= m[key] <= b[key], key
    d = {k: b[k] - a[k] for k in b}
    loop = {n: d[f"loop_phase_seconds_total/{n}"] for n in LOOP_PHASES}
    assert least - 1e-3 <= sum(loop.values()) <= most + 1e-3
    for name in ("intake", "deliver", "encode_write", "engine_loop", "other"):
        assert loop[name] > 0.0, name
    assert d["loop_phase_seconds_total/detok"] > 0.0
    assert d["loop_phase_calls_total/intake"] == 4
    assert d["intake_total"] == 4 and d["intake_seconds_total"] > 0.0
    assert d["first_emit_to_wire_total"] >= 4
    assert d["emit_to_wire_total"] >= sum(content[:4])
    assert d["emit_to_wire_total"] <= sum(content) + 7
    assert 0.0 < d["emit_to_wire_seconds_total"] < wall * 7
    assert d["loop_phase_calls_total/detok"] >= d["emit_to_wire_total"] - 7
    for name in engine_profiler.PHASES:
        cpu = d[f"step_phase_cpu_seconds_total/{name}"]
        assert 0.0 <= cpu <= (s1["step_phase_seconds_total"][name]
                              - s0["step_phase_seconds_total"][name]) + 1e-3
    assert d["step_phase_cpu_seconds_total/process_window"] > 0.0
    if "thread_cpu_seconds_total/step" in d:    # a kernel with schedstat
        for thread in ("step", "loop", "detok"):
            assert d[f"thread_cpu_seconds_total/{thread}"] > 0.0
    eng.fence.disarm()


def test_the_brackets_land_in_a_trace_each_on_its_own_thread(
        run_async, tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = _engine()
    eng.warmup()
    backend = Backend(eng, ByteTokenizer())

    async def one(i):
        req = PreprocessedRequest(
            token_ids=list(range(1 + i, 12 + i)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
            eos_token_ids=[])
        return [o async for o in backend.generate(req, Context())]

    async def main():
        await one(0)
        jax.profiler.start_trace(str(tmp_path))
        try:
            await asyncio.gather(one(1), one(2))
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        await eng.stop()

    run_async(main())
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names = {e.name for e in line.events
                         if e.name.startswith("dyn.")}
                if names:
                    lines.append(names)
    step = [n for n in lines if "dyn.step" in n]
    loop = [n for n in lines if any(x.startswith("dyn.loop.") for x in n)]
    detok = [n for n in lines if "dyn.detok" in n]
    assert len(step) == 1 and len(loop) == 1 and 1 <= len(detok) <= 2
    assert {"dyn.loop.deliver", "dyn.loop.encode_write",
            "dyn.loop.engine_loop"} <= loop[0]
    assert "dyn.gc" in loop[0]                  # collected on this thread
    for names in step + detok:
        assert not any(x.startswith("dyn.loop.") for x in names)
    assert "dyn.step" not in loop[0] and "dyn.detok" not in loop[0]
    eng.fence.disarm()


# ------------------------------------------- the cost, by clock reads

# a _step's phase switches, each one read of either clock: step_begin and
# step_end, and in and out of kv_tier, two dispatches, admit and the two
# halves of a window's and a prefill's readback
STEP_SWITCHES = 20


def test_a_step_reads_its_clocks_a_bounded_number_of_times(
        monkeypatch, run_async):
    """Every phase switch reads perf_counter once and thread_time once
    and nothing else does: at most STEP_SWITCHES of each a ``_step``,
    on average over a run of them, whatever the machine's speed."""
    wall, cpu = _Clock(), _Clock()
    monkeypatch.setattr(PhaseLedger, "clock", wall)
    monkeypatch.setattr(PhaseLedger, "cpu_clock", cpu)
    eng = _engine()
    eng.warmup()

    async def main():
        async def one(i):
            req = PreprocessedRequest(
                token_ids=list(range(1 + i, 20 + i)),
                sampling=SamplingOptions(),
                stop=StopConditions(max_tokens=9, ignore_eos=True),
                eos_token_ids=[])
            async for _ in eng.generate(req, Context()):
                pass

        await one(0)
        w0, c0, n0 = wall.reads, cpu.reads, eng.profiler.step_iterations
        await asyncio.gather(*(one(i) for i in range(1, 4)))
        steps = eng.profiler.step_iterations - n0
        per_step = ((wall.reads - w0) / steps, (cpu.reads - c0) / steps)
        await eng.stop()
        return steps, per_step

    steps, (wall_reads, cpu_reads) = run_async(main())
    assert steps >= 5
    # the loop ledger shares the patched wall clock: its engine_loop
    # bracket is two of the reads a step
    assert cpu_reads <= STEP_SWITCHES and wall_reads <= STEP_SWITCHES + 2
    assert cpu_reads >= 4
    eng.fence.disarm()


class _ScriptedEngine:
    """A token-level engine that yields what it is told to."""

    def __init__(self, outputs):
        self.outputs = outputs

    async def generate(self, request, context):
        for out in self.outputs:
            await asyncio.sleep(0)
            yield out


def test_an_emission_costs_a_fixed_number_of_clock_reads(monkeypatch,
                                                         run_async):
    """On the loop thread: ``deliver`` in and out, ``encode_write`` in
    and out = four reads of the loop ledger's clock for an output with
    tokens to decode, three for one without (``encode_write`` takes the
    clock straight from ``deliver``); on the worker two (``dyn.detok``
    in and out). The finish adds one leave that finds nothing to do."""
    loop_clock, worker_clock = _Clock(), _Clock()
    n = 5

    async def main():
        monkeypatch.setattr(PhaseLedger, "clock", loop_clock)
        prof = profiling.acquire_loop_profiler()
        assert prof.ledger._clock is loop_clock
        for led in profiling._workers.get("detok", ()):
            monkeypatch.setattr(led, "_clock", worker_clock)
        monkeypatch.setattr(PhaseLedger, "clock", worker_clock)
        outs = [EngineOutput(token_ids=[65 + i]) for i in range(n)]
        outs += [EngineOutput(token_ids=[])]            # nothing to decode
        outs += [EngineOutput(token_ids=[66], finish_reason="stop")]
        backend = Backend(_ScriptedEngine(outs), ByteTokenizer())
        req = PreprocessedRequest(
            token_ids=[1, 2, 3], sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=64, ignore_eos=True),
            eos_token_ids=[])
        r0, w0 = loop_clock.reads, worker_clock.reads
        got = [o async for o in backend.generate(req, Context())]
        reads = loop_clock.reads - r0, worker_clock.reads - w0
        await profiling.release_loop_profiler()
        return got, reads

    got, (loop_reads, worker_reads) = run_async(main())
    assert len(got) == n + 2 and got[-1].finish_reason == "stop"
    assert loop_reads == 4 * (n + 1) + 3
    assert worker_reads == 2 * (n + 1)
