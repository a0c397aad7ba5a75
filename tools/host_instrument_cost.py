#!/usr/bin/env python3
"""What each always-on instrument of the hot path costs a call, on this
host: the ledgers' brackets and every span, counter, ring write and
histogram observation that runs once a step, once an emission or once a
chunk (the audit table of docs/profiling.md is this tool's output on the
chip machine's host; PERF.md, PR 35).

    python3 tools/host_instrument_cost.py [--calls 200000]

One JSON line an instrument: microseconds a call (the best of five
repeats of a tight loop, loop overhead subtracted), with a profiler
session closed, as in serving. Touches no device; imports jax only for
``TraceAnnotation``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def best_us(fn, calls: int) -> float:
    def loop(f):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(calls):
                f()
            best = min(best, time.perf_counter() - t0)
        return best

    return 1e6 * (loop(fn) - loop(lambda: None)) / calls


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=200000)
    calls = ap.parse_args().calls

    from jax.profiler import TraceAnnotation

    from dynamo_tpu.engine.profiler import EngineProfiler
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.runtime import profiling, slo, tracing

    rows = {}
    rows["perf_counter"] = time.perf_counter
    rows["thread_time"] = time.thread_time
    rows["monotonic"] = time.monotonic

    def annotation():
        TraceAnnotation("dyn.x").__exit__(None, None, None)

    rows["TraceAnnotation open+close, no session"] = annotation

    step = EngineProfiler("cost")
    step.step_begin()
    phase = step.phase("process_window")

    def nested():
        with phase:
            pass

    rows["step bracket (with phase: 2 switches, CPU clock, annotation)"] = \
        nested

    plain = profiling.PhaseLedger(("a", "b"), "a", annotation=TraceAnnotation)
    plain_phase = plain.phase("b")

    def nested_plain():
        with plain_phase:
            pass

    rows["the same bracket without the CPU clock (as before PR 35)"] = \
        nested_plain

    def step_pair():
        step.step_end()
        step.step_begin()

    rows["step_end + step_begin (dyn.step)"] = step_pair

    loop_led = profiling.LoopLedger()

    def flat():
        loop_led.enter("deliver")
        loop_led.leave("deliver")

    rows["loop bracket (enter + leave, annotation)"] = flat
    rows["loop sum (ledger.add)"] = lambda: loop_led.add("emit_to_wire", 1e-3)
    rows["worker_ledger lookup"] = lambda: profiling.worker_ledger("detok")

    timeline = tracing.StepTimeline(512)
    rows["StepTimeline.add (ring 512, 5 fields)"] = lambda: timeline.add(
        "decode", batch=64, tokens=256, occupancy=64, waiting=0)
    recorder = slo.LatencyRecorder("aggregated")
    rows["LatencyRecorder.observe('itl', x, 4)"] = lambda: recorder.observe(
        "itl", 0.0125, 4)
    metrics = Metrics()
    rows["Metrics.observe_itl"] = lambda: metrics.observe_itl("m", 0.05)
    cost = {"queue_wait_ms": 1.0, "device_step_share": 2.0}
    n = [0]

    def attribution():
        n[0] += 1
        profiling.record_attribution(f"r{n[0]}", cost)

    rows["record_attribution (ring 2048), a request"] = attribution
    tracer = tracing.get_tracer()
    rows["tracer.record_span, a span (4 a request)"] = \
        lambda: tracer.record_span("engine.decode", 0.5, start=1.0)

    def span():
        with tracer.start_span("preprocess"):
            pass

    rows["tracer.start_span + end, a span"] = span
    rows["host_stats() (4 schedstat reads), a stats() call"] = \
        lambda: profiling.host_stats(step.native_id)
    for name, fn in rows.items():
        few = calls // 50 if "request" in name or "span" in name \
            or "stats()" in name else calls
        print(json.dumps({"instrument": name,
                          "us_per_call": round(best_us(fn, few), 4)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
