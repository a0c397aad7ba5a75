"""dynaprof: always-on, low-overhead profiling for the serving runtime.

dyntrace (runtime/tracing.py) answers *how long* each stage of a request
took in wall-clock; this module answers *where the time went* — the
measurement gap that kept "scheduler overhead, not FLOPs" an inference.
Planes, all stdlib-only (the step thread's ledger lives in
``engine/profiler.py`` because it needs jax; it is an instance of the
``PhaseLedger`` here):

- **Phase ledgers** — ``PhaseLedger``: every second of one thread in
  exactly one named slot, each bracket an event on a profiler trace's
  clock. One instance per hot-path thread: the step thread's
  (``engine/profiler.py``), the event loop's (``LoopLedger``, five
  slots) and one per ``dyn-detok`` worker. Beside them what the kernel
  and the interpreter already count: per-thread CPU and run-queue time
  (``/proc/self/task/<tid>/schedstat`` or ``stat``, read at ``stats()``
  time only) and garbage-collection pauses (``gc.callbacks``).
- **Set-up ledger** — ``SetupLedger``: process start to readiness as
  named spans (``dyn.setup.<name>``; they nest, across threads), and the
  jit pipeline's stages (trace, lower, cache read, backend compile) from
  ``jax.monitoring``'s events, by span and by program. The process's one
  duration listener lives here; ``engine/jit_fence.py`` subscribes to it.
- **Event-loop lag monitor** — an asyncio task sleeps a fixed interval
  and records how late it woke (sampled sleep-drift, the classic
  continuous-profiling signal for a starved event loop). Bounded ring;
  p50/p99 exported as ``dyn_runtime_loop_lag_seconds`` and folded into
  every engine's ``stats()`` → ForwardPassMetrics.
- **Stall watchdog** — a daemon thread watching the monitor's heartbeat.
  When a single loop callback overruns ``DYN_PROF_STALL_MS``, it
  captures the event-loop thread's Python stack via
  ``sys._current_frames()`` and accumulates it into a bounded
  folded-stack table exportable as flamegraph-ready collapsed-stack
  text (``GET /debug/profile/stacks`` → ``flamegraph.pl``). Sampling
  only happens *during* a stall, so the steady-state cost is one
  ``monotonic()`` read per poll.
- **Per-request cost attribution** — a bounded ring of attribution
  dicts (queue wait, occupancy-weighted device-step share, KV bytes,
  prefill/decode split) recorded by the engine at finish and surfaced
  through ``/v1/traces/{request_id}`` and the optional usage extension
  block.

Overhead budget and knobs: docs/profiling.md.
"""

from __future__ import annotations

import asyncio
import functools
import gc
import logging
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from .config import env_float, env_int

log = logging.getLogger("dynamo_tpu.profiling")

# --------------------------------------------------------- loop lag monitor


def _pct(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (0 when empty)."""
    if not sorted_vals:
        return 0.0
    rank = max(int(len(sorted_vals) * q / 100.0), 0)
    return sorted_vals[min(rank, len(sorted_vals) - 1)]


class LoopLagMonitor:
    """Sampled sleep-drift: sleep ``interval``, record how late the wakeup
    was. Lag ≈ the sum of callback overruns during the sleep — exactly
    the stall every other request on this loop also experienced."""

    def __init__(self, interval_s: Optional[float] = None, ring: int = 2048):
        if interval_s is None:
            interval_s = (env_float("DYN_PROF_LOOP_INTERVAL_MS")
                          or 100.0) / 1000.0
        self.interval = max(float(interval_s), 0.001)
        self.samples: deque = deque(maxlen=ring)
        # heartbeat read by the stall watchdog thread (single-word
        # read/write — atomic under the GIL)
        self.last_beat = time.monotonic()
        self.loop_thread_id: Optional[int] = None
        self.beats = 0
        # what the ring cannot give: a delta between two stats() reads
        self.lag_seconds_total = 0.0
        self._task: Optional[asyncio.Task] = None

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        self.loop_thread_id = threading.get_ident()
        while True:
            t0 = loop.time()
            self.last_beat = time.monotonic()
            await asyncio.sleep(self.interval)
            lag = max(loop.time() - t0 - self.interval, 0.0)
            self.lag_seconds_total += lag
            self.beats += 1
            self.samples.append(lag)

    def start(self) -> None:
        if self._task is None or self._task.done():
            from .tasks import spawn_tracked

            self._task = spawn_tracked(self._run(), name="dynaprof-loop-lag")

    async def stop(self) -> None:
        from .tasks import cancel_join

        task, self._task = self._task, None
        await cancel_join(task)

    def snapshot(self) -> dict:
        vals = sorted(self.samples)
        return {
            "interval_s": self.interval,
            "samples": len(vals),
            "p50_s": round(_pct(vals, 50), 6),
            "p99_s": round(_pct(vals, 99), 6),
            "max_s": round(vals[-1], 6) if vals else 0.0,
        }


# ------------------------------------------------------------ stall watchdog


def fold_stack(frame) -> str:
    """Collapsed-stack line (outermost;...;innermost) for one Python
    frame chain — the flamegraph.pl input format, module.function units."""
    parts: List[str] = []
    f = frame
    while f is not None:
        name = f.f_code.co_name
        mod = f.f_globals.get("__name__", "?")
        parts.append(f"{mod}.{name}")
        f = f.f_back
    return ";".join(reversed(parts))


class StallWatchdog(threading.Thread):
    """Samples the event-loop thread's stack while a callback overruns.

    The monitor task stamps ``last_beat`` before every sleep; if *now*
    exceeds ``last_beat + interval + threshold`` the loop has been stuck
    inside one callback for at least ``threshold`` — capture the stack.
    Repeated captures during one long stall accumulate like a sampling
    profiler: tall bars in the flamegraph = long/frequent stalls."""

    def __init__(self, monitor: LoopLagMonitor,
                 threshold_s: Optional[float] = None,
                 max_stacks: Optional[int] = None,
                 poll_s: Optional[float] = None):
        super().__init__(name="dynaprof-watchdog", daemon=True)
        if threshold_s is None:
            threshold_s = (env_float("DYN_PROF_STALL_MS") or 250.0) / 1000.0
        self.threshold = float(threshold_s)
        self.max_stacks = (max_stacks if max_stacks is not None
                           else (env_int("DYN_PROF_STACKS") or 256))
        self.poll = poll_s if poll_s is not None else max(
            self.threshold / 4.0, 0.01)
        self.monitor = monitor
        self._stacks: "OrderedDict[str, int]" = OrderedDict()
        self._last_seen: Dict[str, float] = {}  # bounded-by: same cap as _stacks (popped together)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.captures = 0
        self.dropped = 0

    def run(self) -> None:
        while not self._stop.wait(self.poll):
            overdue = (time.monotonic() - self.monitor.last_beat
                       - self.monitor.interval)
            if overdue >= self.threshold:
                self.capture()

    def stop(self) -> None:
        self._stop.set()

    def capture(self) -> Optional[str]:
        """Capture the loop thread's current stack into the folded table
        (also callable directly from tests)."""
        tid = self.monitor.loop_thread_id
        if tid is None:
            return None
        frame = sys._current_frames().get(tid)
        if frame is None:
            return None
        folded = fold_stack(frame)
        with self._lock:
            self.captures += 1
            if folded in self._stacks:
                self._stacks[folded] += 1
                self._last_seen[folded] = time.time()
            elif len(self._stacks) < self.max_stacks:
                self._stacks[folded] = 1
                self._last_seen[folded] = time.time()
            else:
                self.dropped += 1  # bounded: new shapes past cap are counted
        # a stall long enough to sample IS an anomaly; already off-loop
        from . import blackbox
        blackbox.notify_trigger("watchdog_stall", {
            "stack": folded, "threshold_ms": self.threshold * 1000.0})
        return folded

    def folded(self, limit: Optional[int] = None,
               since: Optional[float] = None) -> str:
        """Flamegraph-ready collapsed-stack text: ``stack count`` lines.

        ``limit`` keeps only the top-N hottest stacks; ``since`` (wall
        seconds) drops stacks not sampled since that time — both exist so
        /debug/profile/stacks can bound its response at production ring
        sizes (satellite of dynablack)."""
        with self._lock:
            items = sorted(self._stacks.items(),
                           key=lambda kv: (-kv[1], kv[0]))
            if since is not None:
                items = [(s, c) for s, c in items
                         if self._last_seen.get(s, 0.0) >= since]
        if limit is not None and limit >= 0:
            items = items[:limit]
        return "".join(f"{stack} {count}\n" for stack, count in items)

    def snapshot(self) -> dict:
        with self._lock:
            distinct = len(self._stacks)
        return {"captures": self.captures, "distinct_stacks": distinct,
                "dropped": self.dropped,
                "threshold_ms": round(self.threshold * 1000.0, 3)}


# ------------------------------------------------------------ phase ledgers


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where this process has jax
    loaded, else None: a frontend-only process holds no device to trace
    and never imports jax for a bracket's sake."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class _Phase:
    """One phase's re-usable nesting bracket (``with ledger.phase(name):``).
    The ledger lives on its owner; a bracket only parks, on the ledger's
    stack, the phase to hand the clock back to and its open trace
    annotation."""

    __slots__ = ("prof", "name", "label")

    def __init__(self, prof: "PhaseLedger", name: str):
        self.prof = prof
        self.name = name
        self.label = prof.prefix + name

    def __enter__(self) -> None:
        prof = self.prof
        stack = prof._open
        stack.append(prof._switch(self.name))
        stack.append(prof.annotation(self.label))

    def __exit__(self, *exc) -> None:
        stack = self.prof._open
        stack.pop().__exit__(*exc)
        self.prof._switch(stack.pop())


class PhaseLedger:
    """One thread's time as a ledger: the clock always runs for exactly
    one of ``phases``, entering one closes the interval of whichever ran
    before, so the slots add up to the thread's wall time by
    construction. All mutation happens on the owning thread; a snapshot
    may be read from any.

    Two bracket styles over the one ``_switch``, one style a ledger:

    - **nesting** (``with ledger.phase(name):``, the step thread): the
      exit hands the clock back to the phase around it;
    - **flat** (``enter(name)`` ... ``leave(name)``, the event loop and
      the pool workers, where brackets of interleaved tasks cannot
      nest): ``enter`` takes the clock from whoever holds it, ``leave``
      hands it to ``base``, and does nothing when the clock was taken
      over meanwhile (the bracket's task was suspended: its interval,
      and its trace event, ended where the other bracket began).

    A bracket is one ``perf_counter`` read each way (with ``cpu`` one
    ``thread_time`` read beside it) and one ``TraceAnnotation(prefix +
    name)``: an atomic load while no profiler session is open, an event
    on this thread's line of ``/host:CPU`` on the device trace's clock
    while one is."""

    # the two clocks, read into the instance when it is made (a test
    # hands a ledger its clocks here)
    clock = staticmethod(time.perf_counter)
    cpu_clock = staticmethod(time.thread_time)

    def __init__(self, phases: Tuple[str, ...], base: str,
                 prefix: str = "dyn.", cpu: bool = False,
                 annotation: Any = None):
        self.prefix = prefix
        self.base = base
        # seconds per phase, the phase the clock is running for, and
        # when it started running
        self.phase_seconds: Dict[str, float] = dict.fromkeys(phases, 0.0)
        # CPU seconds of the owning thread per phase, settled at each
        # switch (thread_time is the CALLING thread's clock, so a
        # snapshot from elsewhere cannot add the running interval)
        self.phase_cpu_seconds: Optional[Dict[str, float]] = (
            dict.fromkeys(phases, 0.0) if cpu else None)
        self.phase_calls: Dict[str, int] = dict.fromkeys(phases, 0)
        self.annotation = annotation or _trace_annotation()
        self._phases = {n: _Phase(self, n) for n in phases}
        self._labels = {n: prefix + n for n in phases}
        self._open: list = []   # nesting: outer phase, annotation a bracket
        self._ann = None        # flat: the open bracket's annotation
        self._clock = self.clock
        self._cpu_clock = self.cpu_clock if cpu else None
        self._cur = base
        self._t = self._clock()
        self._c = 0.0
        self._ver = 0           # odd while _switch is mid-update
        self._tid: Optional[int] = None
        self.native_id: Optional[int] = None

    def bind_thread(self) -> None:
        """Called on the owning thread before its first bracket (again
        if the owner changes): the kernel's id of it, for schedstat, and
        the start of its CPU clock."""
        self._tid = threading.get_ident()
        self.native_id = threading.get_native_id()
        if self._cpu_clock is not None:
            self._c = self._cpu_clock()

    def phase(self, name: str) -> _Phase:
        return self._phases[name]

    def _switch(self, name: str) -> str:
        """Close the running phase's interval and start ``name``'s;
        returns the phase that was running."""
        now = self._clock()
        prev = self._cur
        if self._cpu_clock is not None:
            cpu = self._cpu_clock()
            self._ver += 1
            self.phase_cpu_seconds[prev] += cpu - self._c
            self._c = cpu
        else:
            self._ver += 1
        self.phase_seconds[prev] += now - self._t
        self._cur = name
        self._t = now
        self._ver += 1
        return prev

    def enter(self, name: str) -> None:
        ann = self._ann
        if ann is not None:     # taken over from a suspended bracket
            ann.__exit__(None, None, None)
        self._switch(name)
        self.phase_calls[name] += 1
        cls = self.annotation
        self._ann = cls(self._labels[name]) if cls is not None else None

    def leave(self, name: str) -> None:
        if self._cur != name:
            return
        ann = self._ann
        if ann is not None:
            ann.__exit__(None, None, None)
            self._ann = None
        self._switch(self.base)

    def snapshot(self) -> Tuple[Dict[str, float], Optional[Dict[str, float]],
                                Dict[str, int]]:
        """({phase: wall seconds}, {phase: CPU seconds} or None, {phase:
        flat-bracket entries}), cumulative. The wall seconds include the
        running interval, so two snapshots differ by the wall time
        between them. Read from any thread: retried while the owner is
        inside ``_switch``."""
        for _ in range(16):
            ver = self._ver
            seconds = dict(self.phase_seconds)
            cpu = (dict(self.phase_cpu_seconds)
                   if self.phase_cpu_seconds is not None else None)
            calls = dict(self.phase_calls)
            cur, t = self._cur, self._t
            if ver == self._ver and not ver & 1:
                break
        seconds[cur] += self._clock() - t
        return seconds, cpu, calls


class _NullLedger:
    """What ``loop_ledger()`` hands out on a loop nobody profiles."""

    def enter(self, name: str) -> None:
        pass

    leave = enter

    def add(self, name: str, seconds: float) -> None:
        pass


NULL_LEDGER = _NullLedger()

# the event loop's slots (docs/profiling.md has the table of what each
# covers and which file opens it); "other" is asyncio's and aiohttp's own
# machinery and the selector's wait: how BUSY the loop thread is comes
# from its CPU clock (thread_cpu_seconds_total), not from this ledger
LOOP_PHASES = ("intake", "deliver", "encode_write", "engine_loop", "other")
# intervals the frontend sums on the loop thread: each a [seconds, count]
LOOP_SUMS = ("intake", "emit_to_wire", "first_emit_to_wire")


class LoopLedger(PhaseLedger):
    """The event-loop thread's ledger (flat brackets ``dyn.loop.<name>``)
    and, on the same thread, the frontend's two legs of a request:
    handler entry -> ``Sequence.arrival`` and ``_emit`` -> the chunk's
    ``resp.write`` returned."""

    def __init__(self):
        super().__init__(LOOP_PHASES, "other", prefix="dyn.loop.")
        self.sums: Dict[str, List[float]] = {n: [0.0, 0] for n in LOOP_SUMS}
        self.bind_thread()

    def add(self, name: str, seconds: float) -> None:
        s = self.sums[name]
        s[0] += seconds
        s[1] += 1


# one flat ledger per pool worker thread, by pool: the dyn-detok workers
# bracket each decode as ``dyn.detok`` (llm/backend.py)
_workers: Dict[str, List[PhaseLedger]] = {}
_worker_local = threading.local()
_workers_lock = threading.Lock()


def worker_ledger(pool: str) -> PhaseLedger:
    """The calling pool thread's own ledger (slots ``<pool>`` and
    ``other``: waiting for work), made on its first call."""
    led = getattr(_worker_local, "ledger", None)
    if led is None:
        led = PhaseLedger((pool, "other"), "other")
        led.bind_thread()
        _worker_local.ledger = led
        with _workers_lock:
            _workers.setdefault(pool, []).append(led)
    return led


_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def _thread_times(native_id: Optional[int]
                  ) -> Optional[Tuple[float, Optional[float]]]:
    """(seconds on a CPU, seconds runnable but waiting for one) of one
    thread of this process, as the kernel counts them in
    ``/proc/self/task/<tid>/schedstat`` (nanoseconds). A kernel without
    that file (gVisor, which the benchmark's machines run) still keeps
    the thread's CPU ticks in ``.../stat`` (utime + stime, 10 ms each)
    and no run-queue time: then the second is None. None where neither
    file is there (or the thread is gone)."""
    if native_id is None:
        return None
    task = f"/proc/self/task/{native_id}/"
    try:
        with open(task + "schedstat") as f:
            run_ns, wait_ns = f.read().split()[:2]
        return int(run_ns) * 1e-9, int(wait_ns) * 1e-9
    except (OSError, ValueError):
        pass
    try:
        with open(task + "stat") as f:
            # fields after the command's closing parenthesis: state is
            # the 3rd of the line, utime and stime the 14th and 15th
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / _CLK_TCK, None
    except (OSError, ValueError, IndexError):
        return None


class GcClock:
    """Garbage-collection pauses as the interpreter reports them
    (``gc.callbacks``): seconds and collections by generation, and a
    ``dyn.gc`` annotation on whichever thread collects. A collection
    holds the GIL, so no two overlap."""

    def __init__(self):
        self.pause_seconds = 0.0
        self.collections = {"0": 0, "1": 0, "2": 0}
        self._t = 0.0
        self._ann = None
        self._cls = None

    def install(self) -> bool:
        callbacks = getattr(gc, "callbacks", None)
        if callbacks is None:
            return False
        if self not in callbacks:
            callbacks.append(self)
        self._cls = _trace_annotation()
        return True

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            if self._cls is not None:
                self._ann = self._cls("dyn.gc")
        else:
            self.pause_seconds += time.perf_counter() - self._t
            gen = str(info.get("generation", 2))
            self.collections[gen] = self.collections.get(gen, 0) + 1
            ann, self._ann = self._ann, None
            if ann is not None:
                ann.__exit__(None, None, None)


_gc_clock: Optional[GcClock] = None     # None until a loop profiler starts


# ------------------------------------------------------------ set-up ledger

# the jit pipeline's timed stages, by the jax.monitoring event of each
# (jax/_src/dispatch.py log_elapsed_time: a scalar of the same name when
# the stage begins, a duration with fun_name when it ends)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JIT_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    BACKEND_COMPILE_EVENT: "backend_compile",
}
# the persistent cache's own events (jax/_src/compiler.py): on a hit the
# retrieval's duration, without fun_name, just before the
# backend_compile_duration that holds it
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
JIT_STAGES = ("trace", "lower", "cache_read", "backend_compile")
# jit events with no set-up span open (the benchmark's agreement check, a
# program built after readiness) are charged to this name
OUTSIDE = "outside"


class _SetupSpan:
    """One bracket of the set-up ledger (``with ledger.span(name) as s:``):
    ``start`` / ``end`` on the ledger's clock, ``depth`` spans open around
    it, ``jit`` the stage seconds of what was traced and compiled while it
    was the innermost, ``jit_below`` those of the spans inside it."""

    __slots__ = ("ledger", "name", "start", "end", "depth", "jit",
                 "jit_below", "_ann")

    def __init__(self, ledger: "SetupLedger", name: str):
        self.ledger = ledger
        self.name = name
        self.start = self.end = 0.0
        self.depth = 0
        self.jit: Dict[str, float] = {}
        self.jit_below: Dict[str, float] = {}
        self._ann = None

    @property
    def seconds(self) -> float:
        """Of a closed span its length, of an open one the time so far."""
        return (self.end or self.ledger.clock()) - self.start

    def __enter__(self) -> "_SetupSpan":
        cls = _trace_annotation()
        if cls is not None:
            self._ann = cls("dyn.setup." + self.name)
        self.ledger._enter(self)
        return self

    def __exit__(self, *exc) -> None:
        self.ledger._exit(self)
        if self._ann is not None:
            self._ann.__exit__(*exc)


def _add(into: Dict[str, float], stages: Dict[str, float]) -> None:
    for stage, seconds in stages.items():
        into[stage] = into.get(stage, 0.0) + seconds


class SetupLedger:
    """Process start to readiness as named spans, and what the jit
    pipeline did meanwhile, by stage, by span and by program.

    One instance a process (``setup_ledger()``), always on. Spans nest
    and may open on any thread (set-up crosses ``asyncio.to_thread``): one
    stack for the process under one lock, ``time.monotonic()`` both ways
    (the clock of a load generator's ``open`` stamp, so a reader can
    subtract), one ``TraceAnnotation("dyn.setup.<name>")`` a span. The
    stage seconds come from JAX's own monitoring events
    (``install_jit_listeners``): each top-level trace, lowering, cache read
    and backend compile is added to the innermost open span (else
    ``OUTSIDE``) and to its program's row. Nothing here is reachable from
    a step or a request: spans open in set-up code only, the listeners
    fire only while something is traced or compiled, and ``stats()`` after
    readiness compares one integer and hands out the tables it built."""

    MAX_SPANS = 256         # of the ordered list; the sums take every span
    MAX_PROGRAMS = 32       # rows of jit_program_seconds, the costliest
    MAX_WARM_PROGRAMS = 512

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self.clock = clock
        self.origin = clock()
        self._lock = threading.Lock()
        self._open: List[_SetupSpan] = []
        self.span_seconds: Dict[str, float] = {}
        self.span_calls: Dict[str, int] = {}
        self.spans: List[list] = []     # [name, start, end, depth], closed
        self.span_jit: Dict[str, Dict[str, float]] = {}
        # of each depth-0 span name, the stages of everything inside it
        self.top_jit: Dict[str, Dict[str, float]] = {}
        self.stage_calls: Dict[str, int] = dict.fromkeys(JIT_STAGES, 0)
        self.programs: Dict[str, dict] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        self.warm_programs: List[dict] = []
        self._version = 0
        self._built: Tuple[int, dict] = (-1, {})

    def span(self, name: str) -> _SetupSpan:
        return _SetupSpan(self, name)

    def _enter(self, span: _SetupSpan) -> None:
        with self._lock:
            span.depth = len(self._open)
            self._open.append(span)
            span.start = self.clock()

    def _exit(self, span: _SetupSpan) -> None:
        with self._lock:
            span.end = self.clock()
            # not a pop: a span opened on another thread may outlive the
            # one it was opened inside
            self._open.remove(span)
            name = span.name
            self.span_seconds[name] = (self.span_seconds.get(name, 0.0)
                                       + span.end - span.start)
            self.span_calls[name] = self.span_calls.get(name, 0) + 1
            if len(self.spans) < self.MAX_SPANS:
                self.spans.append([name, span.start, span.end, span.depth])
            _add(self.span_jit.setdefault(name, {}), span.jit)
            _add(span.jit_below, span.jit)
            if span.depth and self._open:
                around = self._open[min(span.depth, len(self._open)) - 1]
                _add(around.jit_below, span.jit_below)
            else:
                _add(self.top_jit.setdefault(name, {}), span.jit_below)
            self._version += 1

    def add_stage(self, stage: str, program: str, seconds: float,
                  calls: int = 1) -> None:
        """One top-level stage of the jit pipeline, as the listener saw it
        end."""
        with self._lock:
            jit = (self._open[-1].jit if self._open
                   else self.span_jit.setdefault(OUTSIDE, {}))
            jit[stage] = jit.get(stage, 0.0) + seconds
            row = self.programs.get(program)
            if row is None:
                row = self.programs[program] = dict.fromkeys(JIT_STAGES, 0.0)
                row["calls"] = 0
            row[stage] += seconds
            self.stage_calls[stage] += calls
            if stage in ("cache_read", "backend_compile"):
                row["calls"] += calls   # programs built, however
            self._version += 1

    def count_cache(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
            self._version += 1

    def add_warm_program(self, form: str, span: _SetupSpan) -> None:
        """A row of the warm grid: the call form a ``warmup.*`` span ran
        (``CompileFence.last_dispatch_form()``), the span's seconds and
        the stages charged to it; seconds less the four stages is the
        eager arguments and the dispatch of the first execution."""
        row = {"program": form.split("(", 1)[0], "form": form,
               "seconds": span.end - span.start,
               **{s: span.jit.get(s, 0.0) for s in JIT_STAGES}}
        with self._lock:
            if len(self.warm_programs) < self.MAX_WARM_PROGRAMS:
                self.warm_programs.append(row)
                self._version += 1

    def _program_table(self) -> Dict[str, dict]:
        """``programs`` as stats() shows it: the eager one-op programs
        (``jnp.zeros`` is a ``broadcast_in_dim`` of its own, traced,
        lowered and read from the cache like any other) as one row
        ``eager``, then the costliest names, the rest as ``other``."""
        table: Dict[str, dict] = {}
        for name, row in self.programs.items():
            into = table.setdefault("eager" if _is_eager(name) else name,
                                    dict.fromkeys(row, 0))
            for k, v in row.items():
                into[k] += v

        def cost(name: str) -> Tuple[bool, float]:
            # the eager row first, whatever it cost: it is never "other"
            return name != "eager", -sum(table[name][s] for s in JIT_STAGES)

        names = sorted(table, key=cost)
        out = {n: table[n] for n in names[:self.MAX_PROGRAMS]}
        for name in names[self.MAX_PROGRAMS:]:
            into = out.setdefault("other", dict.fromkeys(table[name], 0))
            for k, v in table[name].items():
                into[k] += v
        return out

    def stats(self) -> dict:
        """The ledger's keys of engine ``stats()``. Built when something
        changed since the last call, which after readiness nothing does:
        then the same tables again, by reference (a reader must not write
        to them)."""
        version, built = self._built
        if version == self._version:
            return built
        with self._lock:
            version = self._version
            totals = dict.fromkeys(JIT_STAGES, 0.0)
            for stages in self.span_jit.values():
                _add(totals, stages)
            built = {
                "setup_span_seconds_total": dict(self.span_seconds),
                "setup_span_calls_total": dict(self.span_calls),
                "setup_spans": sorted(
                    (list(s) for s in self.spans), key=lambda s: s[1]),
                "setup_span_jit_seconds": {
                    n: dict(st) for n, st in self.span_jit.items() if st},
                "jit_stage_seconds_total": totals,
                "jit_stage_calls_total": dict(self.stage_calls),
                "compile_cache_hits_total": self.cache_hits,
                "compile_cache_misses_total": self.cache_misses,
                "jit_program_seconds": self._program_table(),
                "warmup_programs": [dict(r) for r in self.warm_programs],
            }
            self._built = (version, built)
        return built

    def ready_line(self) -> str:
        """``ready in 36.9 s: jax_import 3.1, ..., warmup 10.9 (trace 2.0
        lower 3.1 cache 1.2 compile 0.0), http_start 0.8, outside 19.5``:
        the depth-0 spans in order with the stages inside each, and what
        of the time since the ledger was made no span covers."""
        now = self.clock()
        with self._lock:
            top = sorted((s for s in self.spans if s[3] == 0),
                         key=lambda s: s[1])
            top_jit = {n: dict(st) for n, st in self.top_jit.items()}
        seconds: Dict[str, float] = {}
        for name, start, end, _ in top:
            seconds[name] = seconds.get(name, 0.0) + end - start
        parts = []
        for name, s in seconds.items():
            part = f"{name} {s:.1f}"
            jit = top_jit.get(name, {})
            if any(jit.values()):
                part += (" (trace {trace:.1f} lower {lower:.1f} cache "
                         "{cache_read:.1f} compile {backend_compile:.1f})"
                         .format(**{st: jit.get(st, 0.0)
                                    for st in JIT_STAGES}))
            parts.append(part)
        covered = _union_seconds([(s[1], s[2]) for s in top])
        parts.append(f"{OUTSIDE} {now - self.origin - covered:.1f}")
        return f"ready in {now - self.origin:.1f} s: " + ", ".join(parts)


def _union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total, edge = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > edge:
            total += end - max(start, edge)
            edge = end
    return total


def _is_eager(name: str) -> bool:
    """Whether a program's name is one of JAX's own: a primitive that an
    eager call dispatched (``convert_element_type``, ``broadcast_in_dim``)
    or a jitted ``jax.numpy`` function (``subtract``, ``_where``). The
    names the program's jitted entry points are not."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    return (hasattr(jax.lax, name + "_p")
            or hasattr(jax.numpy, name.lstrip("_")))


_setup = SetupLedger()
# what runs after the ledger on every backend_compile_duration event,
# nested or not: fn(seconds, cache_hit) (engine/jit_fence.py)
_compile_subscribers: List[Callable[[float, bool], None]] = []
_jit_local = threading.local()      # depth of open stages, a pending read
_listeners_lock = threading.Lock()
_listeners_installed = False


def setup_ledger() -> SetupLedger:
    """The process's set-up ledger, made when this module is first
    imported: its ``origin`` is as near the process's start as the program
    can see."""
    return _setup


def setup_span(name: str):
    """Decorator: every call of the function is one span of the process's
    set-up ledger."""
    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with _setup.span(name):
                return fn(*args, **kwargs)

        return run

    return deco


def _on_jit_begin(event: str, _value, **_kw) -> None:
    if event in JIT_STAGE_EVENTS:
        _jit_local.depth = getattr(_jit_local, "depth", 0) + 1


def _on_jit_duration(event: str, seconds: float, fun_name: str = "",
                     **_kw) -> None:
    stage = JIT_STAGE_EVENTS.get(event)
    if stage is None:
        if event == CACHE_READ_EVENT:
            _jit_local.cache_read = seconds
        return
    # a stage that ran inside another on this thread (a jitted jnp
    # function traced inside a program's trace, an eager constant inside
    # a lowering) is part of the outer one's duration: not added again
    depth = _jit_local.depth = max(getattr(_jit_local, "depth", 1) - 1, 0)
    program = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    compiled = stage == "backend_compile"
    # pxla wraps compile_or_get_cached whole: on a persistent-cache hit
    # the backend's duration is the retrieval just reported plus a
    # remainder
    read = _jit_local.__dict__.pop("cache_read", None) if compiled else None
    if not depth:
        if read is not None:
            _setup.add_stage("cache_read", program, read)
            _setup.add_stage(stage, program, max(seconds - read, 0.0), 0)
        else:
            _setup.add_stage(stage, program, seconds)
    if compiled:
        for fn in list(_compile_subscribers):
            fn(seconds, read is not None)


def _on_jit_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _setup.count_cache(True)
    elif event == CACHE_MISS_EVENT:
        _setup.count_cache(False)


def install_jit_listeners() -> None:
    """Register the process's ``jax.monitoring`` listeners, once: ONE
    duration listener, which feeds the set-up ledger and then the compile
    fences (``subscribe_backend_compiles``), a scalar listener for the
    stages' beginnings and a plain one for the cache's hits and misses.
    Called by
    ``enable_compile_cache()`` and ``JaxEngine.__init__``: before the
    first jit of any process that serves."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_jit_begin)
        jax.monitoring.register_event_duration_secs_listener(
            _on_jit_duration)
        jax.monitoring.register_event_listener(_on_jit_event)
        _listeners_installed = True


def subscribe_backend_compiles(fn: Callable[[float, bool], None]) -> None:
    """``fn(seconds, cache_hit)`` on every backend_compile_duration event
    from now on, on the compiling thread, after the ledger took it; what
    ``fn`` raises reaches the jit call that compiled."""
    install_jit_listeners()
    if fn not in _compile_subscribers:
        _compile_subscribers.append(fn)


# ------------------------------------------------------------- loop profiler


class LoopProfiler:
    """Monitor + watchdog pair for one event loop."""

    def __init__(self, interval_s: Optional[float] = None,
                 stall_threshold_s: Optional[float] = None):
        self.monitor = LoopLagMonitor(interval_s)
        if stall_threshold_s is None:
            stall_threshold_s = (env_float("DYN_PROF_STALL_MS")
                                 or 250.0) / 1000.0
        self.watchdog = (StallWatchdog(self.monitor, stall_threshold_s)
                         if stall_threshold_s > 0 else None)
        # made on the loop's own thread (acquire_loop_profiler runs there)
        self.ledger = LoopLedger()
        self._started = False

    def start(self) -> None:
        self.monitor.start()
        if self.watchdog is not None and not self._started:
            self.watchdog.start()
        self._started = True

    async def stop(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        await self.monitor.stop()

    def snapshot(self) -> dict:
        out = {"loop_lag": self.monitor.snapshot()}
        if self.watchdog is not None:
            out["stalls"] = self.watchdog.snapshot()
        return out


# one refcounted profiler per event loop: every acquirer (HTTP service,
# engine, bench) shares it; the last release cancels the monitor task so
# no task outlives its loop
_loop_profilers: Dict[int, List] = {}  # id(loop) -> [LoopProfiler, refcount]
_lp_lock = threading.Lock()
_latest: Optional[LoopProfiler] = None  # last started (stats() fallback)


def acquire_loop_profiler() -> LoopProfiler:
    """Start (or join) the running loop's profiler. Must be called from
    the event loop; pair with :func:`release_loop_profiler`."""
    global _latest, _gc_clock
    loop = asyncio.get_running_loop()
    key = id(loop)
    with _lp_lock:
        ent = _loop_profilers.get(key)
        if ent is None:
            ent = [LoopProfiler(), 0]
            _loop_profilers[key] = ent
        ent[1] += 1
        prof = ent[0]
        # an engine may have brought jax in since the ledger was made
        prof.ledger.annotation = _trace_annotation()
        clock = _gc_clock or GcClock()
        if clock.install():
            _gc_clock = clock
    prof.start()
    _latest = prof
    return prof


async def release_loop_profiler() -> None:
    loop = asyncio.get_running_loop()
    key = id(loop)
    with _lp_lock:
        ent = _loop_profilers.get(key)
        if ent is None:
            return
        ent[1] -= 1
        if ent[1] > 0:
            return
        # claim before the await: a concurrent release must not double-stop
        del _loop_profilers[key]
        prof = ent[0]
    await prof.stop()


def current_loop_profiler() -> Optional[LoopProfiler]:
    try:
        loop = asyncio.get_running_loop()
    except RuntimeError:
        loop = None
    if loop is not None:
        with _lp_lock:
            ent = _loop_profilers.get(id(loop))
        if ent is not None:
            return ent[0]
    return _latest


def loop_ledger():
    """The running loop's ledger, for a caller on that loop's thread
    (looked up once a request, not once a bracket); a ledger whose
    brackets do nothing where no profiler was acquired on this loop."""
    try:
        ent = _loop_profilers.get(id(asyncio.get_running_loop()))
    except RuntimeError:
        return NULL_LEDGER
    return ent[0].ledger if ent is not None else NULL_LEDGER


def host_stats(step_native_id: Optional[int] = None) -> dict:
    """What engine ``stats()`` carries of the host beside the step
    thread's own ledger, process-wide like ``loop_lag_*`` (the serving
    loop, found as ``loop_lag_snapshot`` finds it): cumulative, so a
    reader takes deltas. A key is ABSENT, not zero, where its source is:
    no profiled loop, no per-thread file under ``/proc`` (or, for the
    run-queue time, no ``schedstat``), no ``gc.callbacks``."""
    out: dict = {}
    with _workers_lock:
        detok = list(_workers.get("detok", ()))
    prof = current_loop_profiler()
    if prof is not None:
        seconds, _, calls = prof.ledger.snapshot()
        # the workers' brackets beside the loop's slots: another
        # thread's time, so NOT part of the sum that equals wall time
        snaps = [w.snapshot() for w in detok]
        seconds["detok"] = sum(s[0]["detok"] for s in snaps)
        calls["detok"] = sum(s[2]["detok"] for s in snaps)
        out["loop_phase_seconds_total"] = seconds
        out["loop_phase_calls_total"] = calls
        out["loop_lag_seconds_total"] = prof.monitor.lag_seconds_total
        out["loop_lag_samples_total"] = prof.monitor.beats
        for name, (total, n) in prof.ledger.sums.items():
            out[f"{name}_seconds_total"] = total
            out[f"{name}_total"] = n
    threads = {"step": [step_native_id],
               "loop": [prof.ledger.native_id] if prof is not None else [],
               "detok": [w.native_id for w in detok]}
    cpu: Dict[str, float] = {}
    runq: Dict[str, float] = {}
    for name, ids in threads.items():
        got = [s for s in map(_thread_times, ids) if s is not None]
        if got:
            cpu[name] = sum(s[0] for s in got)
            if all(s[1] is not None for s in got):
                runq[name] = sum(s[1] for s in got)
    if cpu:
        out["thread_cpu_seconds_total"] = cpu
    if runq:
        out["thread_runq_wait_seconds_total"] = runq
    if _gc_clock is not None:
        out["gc_pause_seconds_total"] = _gc_clock.pause_seconds
        out["gc_collections_total"] = dict(_gc_clock.collections)
    # process start to readiness, and the jit pipeline's stages
    out.update(_setup.stats())
    return out


def loop_lag_snapshot() -> dict:
    """The running loop's lag percentiles (zeros when no profiler is up).
    Falls back to the most recently started profiler so engine ``stats()``
    called off-loop (executor thread) still reports the serving loop."""
    prof = current_loop_profiler()
    if prof is None:
        return {"interval_s": 0.0, "samples": 0, "p50_s": 0.0,
                "p99_s": 0.0, "max_s": 0.0}
    return prof.monitor.snapshot()


def stall_stacks_folded(limit: Optional[int] = None,
                        since_ms: Optional[float] = None) -> str:
    prof = current_loop_profiler()
    if prof is None or prof.watchdog is None:
        return ""
    since = since_ms / 1000.0 if since_ms is not None else None
    return prof.watchdog.folded(limit=limit, since=since)


def _setup_prom_lines() -> List[str]:
    st = _setup.stats()
    if not st["setup_span_seconds_total"]:
        return []       # a process that set nothing up (a bare frontend)
    lines = ["# HELP dyn_engine_setup_span_seconds seconds of process "
             "set-up inside each named span (a span holds its children's)",
             "# TYPE dyn_engine_setup_span_seconds gauge"]
    lines += [f'dyn_engine_setup_span_seconds{{span="{name}"}} {s:.6f}'
              for name, s in st["setup_span_seconds_total"].items()]
    lines += ["# HELP dyn_engine_jit_stage_seconds_total seconds the jit "
              "pipeline spent in each stage, process-wide",
              "# TYPE dyn_engine_jit_stage_seconds_total counter"]
    lines += [f'dyn_engine_jit_stage_seconds_total{{stage="{stage}"}} {s:.6f}'
              for stage, s in st["jit_stage_seconds_total"].items()]
    for name in ("hits", "misses"):
        lines += [f"# TYPE dyn_engine_compile_cache_{name}_total counter",
                  f"dyn_engine_compile_cache_{name}_total "
                  f"{st[f'compile_cache_{name}_total']}"]
    return lines


def render_prom_lines() -> List[str]:
    """Loop-lag/stall gauges for the local process's /metrics exposition
    (the aggregator re-exports per-worker figures from ForwardPassMetrics
    instead), and the set-up ledger's spans and jit stages."""
    prof = current_loop_profiler()
    if prof is None:
        return _setup_prom_lines()
    snap = prof.monitor.snapshot()
    lines = _setup_prom_lines() + [
        "# HELP dyn_runtime_loop_lag_seconds event-loop sleep-drift "
        "(sampled callback overrun seen by every task on this loop)",
        "# TYPE dyn_runtime_loop_lag_seconds gauge",
        f'dyn_runtime_loop_lag_seconds{{quantile="p50"}} {snap["p50_s"]}',
        f'dyn_runtime_loop_lag_seconds{{quantile="p99"}} {snap["p99_s"]}',
    ]
    if prof.watchdog is not None:
        w = prof.watchdog.snapshot()
        lines += [
            "# HELP dyn_runtime_loop_stall_captures_total stack samples "
            "taken while a loop callback overran the stall threshold",
            "# TYPE dyn_runtime_loop_stall_captures_total counter",
            f"dyn_runtime_loop_stall_captures_total {w['captures']}",
        ]
    return lines


# -------------------------------------------------- per-request attribution

_attr_lock = threading.Lock()
_attributions: "OrderedDict[str, dict]" = OrderedDict()


def _attr_cap() -> int:
    return max(env_int("DYN_PROF_ATTR_RING") or 2048, 1)


# attribution listeners: called on EVERY record (engine-side finish AND
# the Backend's re-register of a remote cost block) with (request_id,
# cost). Called OUTSIDE the ring lock, and a listener MAY mutate the cost
# dict in place — that is how the KvRouter merges router_overlap_blocks
# into the same dict /v1/traces serves (dynacache calibration).
_attr_listeners: List[Callable[[str, dict], None]] = []


def add_attribution_listener(fn: Callable[[str, dict], None]) -> None:
    if fn not in _attr_listeners:
        _attr_listeners.append(fn)


def remove_attribution_listener(fn: Callable[[str, dict], None]) -> None:
    try:
        _attr_listeners.remove(fn)
    except ValueError:
        pass


def record_attribution(request_id: Optional[str], cost: dict) -> None:
    """Record one finished request's cost-attribution dict (bounded ring,
    newest wins). Called by the engine at finish and by the Backend when
    a remote worker's finish chunk carries a ``cost`` block — so the
    frontend process can serve ``/v1/traces/{rid}`` attribution for
    requests whose engine ran elsewhere."""
    if not request_id:
        return
    cap = _attr_cap()
    with _attr_lock:
        _attributions[request_id] = cost
        _attributions.move_to_end(request_id)
        while len(_attributions) > cap:
            _attributions.popitem(last=False)
    for fn in list(_attr_listeners):
        try:
            fn(request_id, cost)
        except Exception:  # noqa: BLE001 — observability must not break serving
            log.exception("attribution listener failed")


def request_attribution(request_id: str) -> Optional[dict]:
    with _attr_lock:
        return _attributions.get(request_id)


def attributions_snapshot(limit: int = 100) -> List[Tuple[str, dict]]:
    with _attr_lock:
        items = list(_attributions.items())
    return items[-limit:]


# --------------------------------------------------- engine profile registry
# Engine-side profilers (engine/profiler.py) register here so the HTTP
# /debug/profile endpoint can render every live engine's phase ledger —
# same weakref pattern as tracing.register_timeline.

_profiles: Dict[str, "weakref.ref"] = {}
_profiles_lock = threading.Lock()


def register_profile(name: str, profile: Any) -> None:
    with _profiles_lock:
        _profiles[name] = weakref.ref(profile)


def profiles_snapshot() -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    with _profiles_lock:
        for name, ref in list(_profiles.items()):
            p = ref()
            if p is None:
                del _profiles[name]
            else:
                out[name] = p.summary()
    return out


# ----------------------------------------------------- cache-view registry
# dynacache: anything with a ``cache_snapshot()`` (the JaxEngine's
# pool/host-tier/hot-prefix view) registers here so GET /debug/cache can
# render every live cache in the process — same weakref hygiene as the
# engine-profile registry above.

_caches: Dict[str, "weakref.ref"] = {}
_caches_lock = threading.Lock()


def register_cache(name: str, owner: Any) -> None:
    with _caches_lock:
        _caches[name] = weakref.ref(owner)


def caches_snapshot() -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    with _caches_lock:
        for name, ref in list(_caches.items()):
            c = ref()
            if c is None:
                del _caches[name]
            else:
                try:
                    out[name] = c.cache_snapshot()
                except Exception:  # noqa: BLE001 — a dying engine must not 500 the debug page
                    log.debug("cache snapshot for %s failed", name,
                              exc_info=True)
    return out
