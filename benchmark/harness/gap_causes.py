"""What the host's threads were doing while the device idled, while the
step thread was between two iterations, and around each prefill program:
the run's own trace, split by overlap.

``host_trace.py`` (PR 24) files a device idle gap whole under the one
step-thread phase that covered most of it and reads the ``dyn.*`` events
of every host line as one thread's. Since PR 35 the program brackets
three kinds of thread (``python -m benchmark.harness.gap_causes <file>``
prints the view):

- the **step thread**: the line of ``/host:CPU`` that holds ``dyn.step``
  (``engine/profiler.py``: every ``_step`` a ``dyn.step``, every phase a
  ``dyn.<phase>`` inside it, nested brackets nest);
- the **loop thread**: the line that holds ``dyn.loop.*``
  (``runtime/profiling.py LoopLedger``: ``intake``, ``deliver``,
  ``encode_write``, ``engine_loop``; flat, never nested);
- the **detokeniser workers**: the lines that hold ``dyn.detok``.

A ``dyn.gc`` (a garbage collection) lies on whichever thread collected.
Lines are named after the process, so a thread is told by its events.
On a trace of a program without the loop's brackets (the parent of
PR 35) the readers built on this file return None.

Three reductions, each by OVERLAP (a gap that straddles two states is
split between them):

(a) ``idle_split``: every device idle gap of 50 us or more among three
    states of the step thread: a work phase (inside ``dyn.step``, any
    phase but a readback), a ``dyn.readback_*`` phase, and outside
    ``dyn.step`` (``idle`` / ``between_steps``: no work, or the hop
    through the event loop); the time outside is split again by whether
    a stream bracket (``dyn.loop.deliver`` / ``encode_write`` /
    ``dyn.detok``) ran on another thread. With the gaps under 50 us and
    the slice's two edges the three add up to the device's idle time.
(b) ``step_gap_split``: the time between two ``dyn.step`` events, by
    whether a stream bracket ran.
(c) ``prefill_lives``: for each ``prefill_step`` execution of the slice,
    the ``dyn.dispatch_prefill`` that enqueued it (the last one, of
    those that enqueued anything, to start before the program did) and
    the ``dyn.readback_prefill`` that fetched it (of the executions
    ended when a readback returns, the last is its own): enqueue ->
    device start, device end -> readback returned. What the slice's
    edges cut finds no partner and is dropped.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.harness import host_trace, trace

LOOP_PREFIX = "dyn.loop."
DETOK_EVENT = "dyn.detok"
STREAM_EVENTS = (LOOP_PREFIX + "deliver", LOOP_PREFIX + "encode_write",
                 DETOK_EVENT)
READBACK_PREFIX = "dyn.readback_"
DISPATCH_PREFILL = "dyn.dispatch_prefill"
READBACK_PREFILL = "dyn.readback_prefill"
# _dispatch_prefill runs every iteration and returns at once where no
# prompt waits: such a bracket is a few microseconds, one that built a
# batch and called the program is a millisecond or more
DISPATCH_FLOOR_S = 100e-6

Event = Tuple[str, float, float]        # name, start_s, duration_s
Span = Tuple[float, float]              # start_s, end_s


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"ops": {device plane: [(name, start_s, duration_s)]}, "modules":
    {device plane: [...]}, "threads": [[dyn.* events of one host line]]},
    seconds on the trace's one clock."""
    from jax.profiler import ProfileData

    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    threads: List[List[Event]] = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {trace.OPS_LINE: ops,
                        trace.MODULES_LINE: modules}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name == host_trace.HOST_PLANE:
            for line in plane.lines:
                own = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events
                       if e.name.startswith(host_trace.PHASE_PREFIX)]
                if own:
                    threads.append(sorted(own, key=lambda e: (e[1], -e[2])))
    return {"ops": {k: v for k, v in ops.items() if v},
            "modules": modules, "threads": threads}


# ------------------------------------------------------------ intervals

def union(spans: List[Span]) -> List[Span]:
    out: List[Span] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        elif b > a:
            out.append((a, b))
    return out


class _Spans:
    """Disjoint spans in order, with the starts kept for bisection."""

    def __init__(self, spans: List[Span]):
        self.spans = union(spans)
        self.starts = [s[0] for s in self.spans]

    def overlap(self, a: float, b: float) -> float:
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        total = 0.0
        while i < len(self.spans) and self.spans[i][0] < b:
            s, e = self.spans[i]
            total += max(0.0, min(e, b) - max(s, a))
            i += 1
        return total

    def minus(self, a: float, b: float) -> List[Span]:
        """The parts of [a, b] no span covers."""
        out, cur = [], a
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        while i < len(self.spans) and self.spans[i][0] < b:
            s, e = self.spans[i]
            if e > cur:
                if s > cur:
                    out.append((cur, s))
                cur = max(cur, e)
            i += 1
        if cur < b:
            out.append((cur, b))
        return out


# -------------------------------------------------------------- threads

def step_thread(loaded: dict) -> Optional[List[Event]]:
    for events in loaded["threads"]:
        if any(e[0] == host_trace.STEP_EVENT for e in events):
            return events
    return None


def stream_spans(loaded: dict) -> Optional[_Spans]:
    """When a stream bracket ran on the loop or a detokeniser thread;
    None where the trace has no ``dyn.loop.*`` event at all (a program
    without the loop's ledger)."""
    found = False
    spans: List[Span] = []
    for events in loaded["threads"]:
        for name, s, d in events:
            found = found or name.startswith(LOOP_PREFIX)
            if name in STREAM_EVENTS:
                spans.append((s, s + d))
    return _Spans(spans) if found else None


def step_states(events: List[Event]) -> Dict[str, _Spans]:
    """The step thread's ``work`` and ``readback`` spans and its
    ``step`` spans (each ``dyn.step`` whole); what lies outside every
    ``dyn.step`` is the third state."""
    work, readback = [], []
    for name, a, b in host_trace.exclusive_phases(events):
        (readback if name.startswith(READBACK_PREFIX) else work).append(
            (a, b))
    steps = [(s, s + d) for name, s, d in events
             if name == host_trace.STEP_EVENT]
    return {"work": _Spans(work), "readback": _Spans(readback),
            "step": _Spans(steps)}


# ----------------------------------------------------------- reductions

def idle_split(loaded: dict, window_s: float = 0.0) -> Optional[dict]:
    """Seconds of the device's idle time by cause, averaged over the
    chips: ``host_work`` / ``readback`` / ``no_work`` over the gaps of
    50 us or more (``no_work_streams`` is the part of ``no_work`` in
    which a stream bracket ran; None without the loop's brackets), and
    the remainder no cause is given for: ``small_gaps`` (under 50 us:
    launch spacing) and ``edges`` (the traced span before the first and
    after the last op). ``span_s`` is the span they are shares of:
    ``window_s`` or the ops' own span, whichever is longer, as
    ``trace.reduce`` takes it. None without a device op or a step
    thread."""
    events = step_thread(loaded)
    if not loaded["ops"] or events is None:
        return None
    states = step_states(events)
    streams = stream_spans(loaded)
    n = len(loaded["ops"])
    out = dict.fromkeys(("host_work", "readback", "no_work",
                         "no_work_streams", "small_gaps", "busy"), 0.0)
    span = 0.0
    for ops in loaded["ops"].values():
        busy = trace._union(ops)
        out["busy"] += sum(b - a for a, b in busy)
        span = max(span, busy[-1][1] - busy[0][0])
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b - a < trace.GAP_FLOOR_S:
                out["small_gaps"] += b - a
                continue
            work = states["work"].overlap(a, b)
            read = states["readback"].overlap(a, b)
            out["host_work"] += work
            out["readback"] += read
            out["no_work"] += (b - a) - work - read
            if streams is not None:
                out["no_work_streams"] += sum(
                    streams.overlap(s, e)
                    for s, e in states["step"].minus(a, b))
    out = {k: v / n for k, v in out.items()}
    if streams is None:
        out["no_work_streams"] = None
    out["span_s"] = max(window_s, span)
    out["edges"] = out["span_s"] - out["busy"] - out["small_gaps"] - sum(
        out[k] for k in ("host_work", "readback", "no_work"))
    return out


def step_gap_split(loaded: dict) -> Optional[dict]:
    """Seconds between two ``dyn.step`` events of the slice, and how
    much of them a stream bracket ran in. None without a step thread,
    two steps, or the loop's brackets."""
    events = step_thread(loaded)
    streams = stream_spans(loaded)
    if events is None or streams is None:
        return None
    steps = sorted((s, s + d) for name, s, d in events
                   if name == host_trace.STEP_EVENT)
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(steps, steps[1:]) if b_start > a_end]
    if not gaps:
        return None
    return {"gaps": len(gaps), "gap_s": sum(b - a for a, b in gaps),
            "stream_s": sum(streams.overlap(a, b) for a, b in gaps)}


def prefill_lives(loaded: dict) -> Optional[dict]:
    """{"device_wait_s": [...], "readback_lag_s": [...], "executions",
    "dispatches", "empty_dispatches", "readbacks"} over the slice's
    ``prefill_step`` executions (module docstring, (c)). None without a
    step thread or without such a program."""
    events = step_thread(loaded)
    if events is None:
        return None
    execs = sorted((s, s + d) for mods in loaded["modules"].values()
                   for name, s, d in mods
                   if trace.PREFILL_MODULE.search(trace._module(name)))
    if not execs:
        return None
    every = [(s, s + d) for name, s, d in events
             if name == DISPATCH_PREFILL]
    dispatches = sorted(x for x in every if x[1] - x[0] >= DISPATCH_FLOOR_S)
    readbacks = sorted(((s, s + d) for name, s, d in events
                        if name == READBACK_PREFILL), key=lambda x: x[1])
    # an execution's dispatch: the last to start before it; where two
    # executions claim one (the first was enqueued before the slice),
    # the later keeps it
    d_starts = [x[0] for x in dispatches]
    claimed: Dict[int, Span] = {}
    for ex in execs:
        i = bisect.bisect_right(d_starts, ex[0]) - 1
        if i >= 0:
            claimed[i] = ex
    waits = [max(0.0, ex[0] - dispatches[i][1])
             for i, ex in sorted(claimed.items())]
    # a readback's execution: the last that had ended when it returned
    ends = [x[1] for x in execs]
    lags: Dict[int, float] = {}
    for _, r_end in readbacks:
        i = bisect.bisect_right(ends, r_end) - 1
        if i >= 0 and i not in lags:
            lags[i] = r_end - ends[i]
    return {"device_wait_s": waits,
            "readback_lag_s": [v for _, v in sorted(lags.items())],
            "executions": len(execs), "dispatches": len(dispatches),
            "empty_dispatches": len(every) - len(dispatches),
            "readbacks": len(readbacks)}


# -------------------------------------------------------------- readers

def of_run(raw: dict, reader_file: str) -> Optional[dict]:
    """``load`` of the trace of the run ``raw`` came from, found and
    checked as ``host_trace._run_trace`` does; None where the run was
    not traced, the file is not that run's, or the program wrote no
    ``dyn.loop.*`` event (the parent of PR 35: its trace could be split
    too, but these are metrics of the program that has the brackets)."""
    if host_trace._run_trace(raw, reader_file) is None:
        return None
    root = os.path.abspath(reader_file)
    for _ in range(3):
        root = os.path.dirname(root)
    loaded = load(host_trace.find_xplane(root))
    return loaded if stream_spans(loaded) is not None else None


def idle_share(raw: dict, cause: str, reader_file: str) -> Optional[float]:
    """100 x the device's idle seconds filed under ``cause`` / the
    traced span (``device_idle_share``'s own denominator)."""
    loaded = of_run(raw, reader_file)
    got = idle_split(loaded, raw["trace"]["window_s"]) if loaded else None
    return 100.0 * got[cause] / got["span_s"] if got else None


def prefill_ms_mean(raw: dict, key: str, reader_file: str
                    ) -> Optional[float]:
    loaded = of_run(raw, reader_file)
    got = prefill_lives(loaded) if loaded else None
    if not got or not got[key]:
        return None
    return 1000.0 * sum(got[key]) / len(got[key])


def summarize(path: str, window_s: float = 0.0) -> dict:
    loaded = load(path)
    idle = idle_split(loaded, window_s)
    lives = prefill_lives(loaded)
    if lives:
        for key in ("device_wait_s", "readback_lag_s"):
            xs = lives.pop(key)
            lives[key.replace("_s", "_ms_mean")] = (
                1000.0 * sum(xs) / len(xs) if xs else None)
            lives[key.replace("_s", "_matched")] = len(xs)
    return {"file": path, "host_lines_with_dyn_events":
            len(loaded["threads"]),
            "idle_s_by_cause": idle,
            "idle_share_by_cause": idle and {
                k: 100.0 * idle[k] / idle["span_s"]
                for k in ("host_work", "readback", "no_work",
                          "small_gaps", "edges")},
            "step_gaps": step_gap_split(loaded),
            "prefills": lives}


if __name__ == "__main__":
    json.dump(summarize(sys.argv[1]), sys.stdout, indent=1)
