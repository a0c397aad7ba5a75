"""The program's own names in a profiler trace: the scope of each device
op and the step thread's ``dyn.*`` phases, on one clock.

Where they are in a trace of this program on a v5e (looked at by hand,
PR 24; ``python -m benchmark.harness.host_trace <file>`` prints the
view):

- **Scopes.** The ``jax.named_scope`` path of an ``XLA Ops`` event
  (``jit(decode_window)/while/body/closed_call/moe/moe.experts/btd,edi->
  btei/dot_general:``) is the stat ``tf_op`` of the event's
  *XEventMetadata*, not of the event. ``jax.profiler.ProfileData`` shows
  an event's own stats only (``device_offset_ps``, ``device_duration_ps``),
  so ``op_scopes`` reads the plane's metadata table straight from the
  file's protobuf wire format (four message types of xplane.proto, no
  generated code) and joins it to ProfileData's events by name. An op the
  compiler made itself (a copy of the pool, a layout change) has no
  ``tf_op`` past its program's ``jit(...)/while``: the unscoped remainder.
- **Phases.** ``engine/profiler.py`` wraps every phase of the step thread
  in ``jax.profiler.TraceAnnotation("dyn.<phase>")`` and each ``_step`` in
  ``dyn.step``. They are events of the step thread's line of
  ``/host:CPU`` (named after the process, ``python3``; found here as the
  line that holds ``dyn.`` events), on the device trace's clock: a
  ``dyn.readback_window`` ends 2-4 ms after its window's last op.
  Nested brackets nest as events; the innermost owns the time.

``trace.py`` (PR 23) keeps the numbers every accepted metric reads; this
file adds to it and changes nothing there.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.harness import counters, trace

# the jax.named_scope names of dynamo_tpu/models/llama.py, ops/ and
# engine/sampling.py; a nested one is reported under its parent too
SCOPES = ("attn", "moe", "moe.router", "moe.experts", "mlp", "lm_head",
          "sample", "kv_carry")
PHASE_PREFIX = "dyn."
STEP_EVENT = "dyn.step"
HOST_PLANE = "/host:CPU"

ScopedOp = Tuple[str, float, float, str]    # name, start_s, duration_s, tf_op
Event = Tuple[str, float, float]


def find_xplane(root: str) -> Optional[str]:
    """The newest trace under ``<root>/.bench_trace/*/``: run.py clears
    its cell's directory before it traces, so after a traced window the
    newest file is that window's."""
    files = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


# ------------------------------------------------- xplane.proto, by hand
#
#   XSpace          1: repeated XPlane
#   XPlane          2: name   4: map<int64, XEventMetadata>
#                   5: map<int64, XStatMetadata>
#   XEventMetadata  2: name   5: repeated XStat
#   XStatMetadata   2: name
#   XStat           1: metadata_id   5: str_value   7: ref_value
#   (a map entry is a message: 1 = key, 2 = value)

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, a
    bytes slice for length-delimited fields; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _first(buf: bytes, want: int, default=None):
    for num, val in _fields(buf):
        if num == want:
            return val
    return default


def op_scopes(path: str) -> Dict[str, str]:
    """{event name: tf_op} over the device planes of an xplane.pb file;
    {} where no event metadata carries one (a CPU trace)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name = _first(plane, 2, b"").decode()
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        events: List[bytes] = []
        for pnum, entry in _fields(plane):
            if pnum == 5:
                meta = _first(entry, 2, b"")
                stat_names[_first(entry, 1, 0)] = _first(
                    meta, 2, b"").decode()
            elif pnum == 4:
                events.append(_first(entry, 2, b""))
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for meta in events:
            ev_name = scope = None
            for mnum, val in _fields(meta):
                if mnum == 2:
                    ev_name = val.decode()
                elif mnum == 5 and _first(val, 1) in tf_op:
                    ref = _first(val, 7)
                    scope = (_first(val, 5, b"").decode() if ref is None
                             else stat_names.get(ref, ""))
            if ev_name and scope:
                out[ev_name] = scope
    return out


# ------------------------------------------------------------- loading

@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"ops": {device plane: [(name, start_s, duration_s, tf_op)]},
    "phases": [(dyn.<phase>, start_s, duration_s)] of the step thread},
    times in seconds on the trace's one clock."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    ops: Dict[str, List[ScopedOp]] = {}
    phases: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         scopes.get(e.name, "")) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                phases += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events
                           if e.name.startswith(PHASE_PREFIX)]
    return {"ops": {k: v for k, v in ops.items() if v},
            "phases": sorted(phases, key=lambda e: (e[1], -e[2]))}


def scopes_of(tf_op: str) -> List[str]:
    """The program's scopes on an op's name-stack path, outermost
    first: ``.../moe/moe.experts/dot_general:`` -> [moe, moe.experts]."""
    return [s for s in tf_op.rstrip(":").split("/") if s in SCOPES]


# ------------------------------------------------------------ reduction

def scope_seconds(loaded: dict) -> Optional[dict]:
    """Device seconds per scope (an op under ``moe/moe.experts`` counts
    in both), the unscoped remainder, and the busy time they are shares
    of, averaged over the chips; None without a device op."""
    planes = loaded["ops"]
    if not planes:
        return None
    n = len(planes)
    per: Dict[str, float] = defaultdict(float)
    busy = total = 0.0
    for ops in planes.values():
        busy += sum(b - a for a, b in trace._union(
            [(name, s, d) for name, s, d, _ in ops]))
        for name, _, d, tf_op in ops:
            if trace.CONTAINER_OP.match(trace._op(name)[0]):
                continue
            total += d
            found = scopes_of(tf_op)
            for s in found:
                per[s] += d
            if not found:
                per["unscoped"] += d
    return {"busy_s": busy / n, "op_s": total / n,
            "scopes": {k: v / n for k, v in sorted(
                per.items(), key=lambda kv: -kv[1])}}


def _run_trace(raw: dict, reader_file: str) -> Optional[Tuple[dict, dict]]:
    """(``load``, ``scope_seconds``) of the trace of the run ``raw`` came
    from; ``reader_file`` is the calling reader's ``__file__``
    (``<root>/benchmark/metrics/x.py``: the run's trace lies under that
    root). None where the run was not traced, no file is there, no
    operation ran on a device, or the file is not that run's (its busy
    time differs from the one ``trace.reduce`` read)."""
    if not raw.get("trace"):
        return None
    root = os.path.abspath(reader_file)
    for _ in range(3):
        root = os.path.dirname(root)
    path = find_xplane(root)
    if path is None:
        return None
    loaded = load(path)
    got = scope_seconds(loaded)
    if not got or got["busy_s"] <= 0:
        return None
    if abs(got["busy_s"] - raw["trace"]["busy_s"]) > 0.01 * got["busy_s"]:
        return None
    return loaded, got


def scope_share(raw: dict, scope: str, reader_file: str) -> Optional[float]:
    """100 x device time of the ops under ``scope`` / busy time, in the
    traced slice of the run ``raw`` came from (``_run_trace``). None
    also where the program is one without the scopes (told by its
    ``stats()``, which then lacks the phases of the same PR: op names
    cannot tell, because a compile cache shared with a scoped program
    hands its executables, names included, to an unscoped one with the
    same HLO), or no op carries a scope at all."""
    if counters.PHASES_KEY not in raw.get("stats1", {}):
        return None
    found = _run_trace(raw, reader_file)
    if found is None:
        return None
    got = found[1]
    if set(got["scopes"]) <= {"unscoped"}:
        return None
    return 100.0 * got["scopes"].get(scope, 0.0) / got["busy_s"]


def op_seconds(raw: dict, pattern: str, reader_file: str) -> Optional[float]:
    """Device seconds of the ops whose kind (``trace._op``: the
    instruction's name without its number, ``paged_attention_decode_
    layered``, ``fusion``) matches the regular expression ``pattern``
    from its start, averaged over the chips, in the traced slice of the
    run ``raw`` came from (``_run_trace``): what ``raw["trace"]
    ["kernel_s"]`` is for the one kernel ``trace.reduce`` knows. Ops
    that only contain other ops are left out. 0.0 where none matches."""
    found = _run_trace(raw, reader_file)
    if found is None:
        return None
    planes = found[0]["ops"]
    hit = re.compile(pattern)
    total = 0.0
    for ops in planes.values():
        for name, _, d, _ in ops:
            kind = trace._op(name)[0]
            if hit.match(kind) and not trace.CONTAINER_OP.match(kind):
                total += d
    return total / len(planes)


def exclusive_phases(phases: List[Event]) -> List[Event]:
    """Nested ``dyn.*`` events cut into disjoint pieces, the innermost
    owning its time: (name, start_s, end_s), in time order. What of a
    ``dyn.step`` no phase covers comes out as ``dyn.other``."""
    out: List[Event] = []
    stack: List[List] = []      # [name, cursor, end]

    def close(upto: float) -> None:
        while stack and stack[-1][2] <= upto:
            name, cur, end = stack.pop()
            if end > cur:
                out.append((name, cur, end))
            if stack:
                stack[-1][1] = max(stack[-1][1], end)

    for name, start, dur in phases:
        close(start)
        if stack and start > stack[-1][1]:
            out.append((stack[-1][0], stack[-1][1], start))
        name = "dyn.other" if name == STEP_EVENT else name
        stack.append([name, start, start + dur])
    close(float("inf"))
    return sorted(out, key=lambda e: e[1])


def gap_phases(loaded: dict) -> List[list]:
    """For every device idle gap of 50 us or more: the phase of the step
    thread that covered most of it (``outside dyn.step`` between two
    iterations). [[phase, seconds, gaps]], longest first."""
    pieces = exclusive_phases(loaded["phases"])
    starts = [p[1] for p in pieces]
    tally: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for ops in loaded["ops"].values():
        spans = trace._union([(name, s, d) for name, s, d, _ in ops])
        for (_, a), (b, _) in zip(spans, spans[1:]):
            if b - a < trace.GAP_FLOOR_S:
                continue
            cover: Dict[str, float] = defaultdict(float)
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(pieces) and pieces[i][1] < b:
                name, s, e = pieces[i]
                cover[name] += max(0.0, min(e, b) - max(s, a))
                i += 1
            cover["outside dyn.step"] += (b - a) - sum(cover.values())
            owner = max(cover, key=cover.get)
            tally[owner][0] += b - a
            tally[owner][1] += 1
    return [[k, s, n] for k, (s, n) in sorted(
        tally.items(), key=lambda kv: -kv[1][0])]


def summarize(path: str) -> dict:
    """The by-hand view: device time per scope with the unscoped
    remainder, idle gaps by phase, and the step thread's seconds per
    phase inside the trace."""
    loaded = load(path)
    per_phase: Dict[str, float] = defaultdict(float)
    for name, a, b in exclusive_phases(loaded["phases"]):
        per_phase[name] += b - a
    scoped = scope_seconds(loaded)
    return {"file": path,
            "device": scoped,
            "unscoped_top": _unscoped_top(loaded),
            "idle_gaps_by_phase": gap_phases(loaded),
            "step_thread_s": dict(sorted(per_phase.items(),
                                         key=lambda kv: -kv[1])),
            "steps": sum(1 for e in loaded["phases"]
                         if e[0] == STEP_EVENT)}


def _unscoped_top(loaded: dict, top_n: int = 8) -> List[list]:
    """What the unscoped remainder is made of, by op kind + output."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in loaded["ops"].values():
        for name, _, d, tf_op in ops:
            kind, out = trace._op(name)
            if not scopes_of(tf_op) and not trace.CONTAINER_OP.match(kind):
                tot[f"{kind} {out}".strip()] += d
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:top_n]]


if __name__ == "__main__":
    json.dump(summarize(sys.argv[1]), sys.stdout, indent=1)
