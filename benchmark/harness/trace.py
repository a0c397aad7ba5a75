"""From a profiler trace (``*.xplane.pb``) to device numbers: busy and
idle time, time per program, the decode kernel's share, the breakdown.

Where things are in a trace of this program on a v5e (looked at by hand,
PR 23; ``python -m benchmark.harness.trace <file>`` prints the same
view): one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` has
one event per executed HLO op, named by the whole instruction text
(``%fusion.750 = f32[32,8,14336]{...} fusion(...)``; a ``%while.N``
event spans the ops of its body; the Pallas decode kernel is
``%paged_attention_decode_layered.N``, after the jitted function around
the pallas_call), ``XLA Modules`` one event per executed program, named
``jit_<function>(<fingerprint>)`` (``jit_decode_window``,
``jit_prefill_step``, and the small ``jit__merge_carry``,
``jit_sample_tokens``, ...). ``Async XLA Ops`` holds copy-start/done
pairs and is not read. Host threads are lines of ``/host:CPU`` (PJRT
execute/transfer TraceMe events; the program writes none of its own).

Two stages, so the arithmetic can be tested on a small recorded
fixture without a profiler: ``load`` reads the file into plain lists,
``reduce`` turns those into numbers.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# what the program's step programs and kernels are called in a trace
PREFILL_MODULE = re.compile(r"prefill_step")
WINDOW_MODULE = re.compile(r"decode_window")
DECODE_KERNEL_OP = re.compile(r"^paged_attention_decode")
# ops that only contain other ops: their time is their children's
CONTAINER_OP = re.compile(r"^(while|conditional|call)$")
GAP_FLOOR_S = 50e-6     # shorter gaps are launch spacing, not waiting

Event = Tuple[str, float, float]    # name, start_s, duration_s


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{device plane: {"ops": [...], "modules": [...]}}, times in
    seconds on the device's clock; {} where the trace has no device
    plane (a CPU run)."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            lines[key] = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                          for e in line.events]
        out[plane.name] = lines
    return out


def _union(events: List[Event]) -> List[Tuple[float, float]]:
    spans: List[Tuple[float, float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if spans and s <= spans[-1][1]:
            if s + d > spans[-1][1]:
                spans[-1] = (spans[-1][0], s + d)
        else:
            spans.append((s, s + d))
    return spans


def _module(name: str) -> str:
    """``jit_decode_window(123)`` -> ``decode_window``."""
    return re.sub(r"^jit_", "", name.split("(")[0])


def _op(name: str) -> Tuple[str, str]:
    """An op event's (kind, output type): ``%fusion.750 = f32[32,8]{1,0}
    fusion(...)`` -> (``fusion``, ``f32[32,8]``). The number is dropped
    so that the copies of one op in an unrolled loop add up."""
    own, _, rest = name.partition(" = ")
    kind = re.sub(r"[.\d]+$", "", own.lstrip("%")) or own
    out = re.match(r"\(?[a-z0-9]+\[[\d,]*\]", rest)
    return kind, out.group(0).lstrip("(") if out else ""


def reduce(planes: Dict[str, Dict[str, List[Event]]],
           window_s: float) -> Optional[dict]:
    """Numbers of one traced slice, averaged over the chips used; None
    where no operation ran on a device."""
    planes = {k: v for k, v in planes.items() if v["ops"]}
    if not planes:
        return None
    n = len(planes)
    busy = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    kernel = 0.0
    modules: Dict[str, List[float]] = defaultdict(list)
    for lines in planes.values():
        spans = _union(lines["ops"])
        busy += sum(b - a for a, b in spans)
        for name, _, d in lines["ops"]:
            kind, out = _op(name)
            if DECODE_KERNEL_OP.match(kind):
                kernel += d
            if not CONTAINER_OP.match(kind):
                op_time[f"{kind} {out}".strip()] += d
        mods = sorted(lines["modules"], key=lambda e: e[1])
        for name, _, d in mods:
            modules[_module(name)].append(d)
        # an idle gap is named by the programs on either side of it: all
        # that today's trace can say about what the host was doing
        starts = [m[1] for m in mods]
        for (_, a_end), (b_start, _) in zip(spans, spans[1:]):
            gap = b_start - a_end
            if gap < GAP_FLOOR_S:
                continue
            before = _module_at(mods, starts, a_end - 1e-9)
            after = _module_at(mods, starts, b_start + 1e-9)
            gap_time[f"{before} -> {after}"] += gap
    span = max(max(s + d for _, s, d in v["ops"])
               - min(s for _, s, _ in v["ops"]) for v in planes.values())
    window_s = max(window_s, span)

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": busy / n, "window_s": window_s, "chips": n,
            "kernel_s": kernel / n,
            "modules": {k: {"count": len(v), "mean_s": sum(v) / len(v),
                            "total_s": sum(v) / n}
                        for k, v in modules.items()},
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}


def _module_at(mods: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1] + mods[i][2] >= t:
        return _module(mods[i][0])
    return "none"


def module_stats(reduced: dict, pattern: re.Pattern) -> Optional[dict]:
    """Executions of the programs whose name matches, merged."""
    hit = [v for k, v in reduced["modules"].items() if pattern.search(k)]
    if not hit:
        return None
    count = sum(v["count"] for v in hit)
    return {"count": count,
            "mean_s": sum(v["mean_s"] * v["count"] for v in hit) / count}


def summarize(path: str, top_n: int = 15) -> dict:
    """Every plane and line of a trace with its heaviest event names:
    the by-hand look that the constants above were written from."""
    from jax.profiler import ProfileData

    view = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            tot: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
            n = 0
            for e in line.events:
                n += 1
                tot[e.name][0] += 1
                tot[e.name][1] += e.duration_ns * 1e-9
            lines[line.name] = {"events": n, "top": [
                [k, c, round(s, 6)] for k, (c, s) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:top_n]]}
        view[plane.name] = lines
    return view


if __name__ == "__main__":
    json.dump(summarize(sys.argv[1]), sys.stdout, indent=1)
