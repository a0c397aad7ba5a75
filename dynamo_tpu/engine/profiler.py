"""dynaprof engine layer: the step thread's phase ledger.

Every second of the engine's step thread belongs to exactly one named
phase (``PHASES``), kept as a ledger — entering a phase closes the
interval of the one around it, so nested brackets (a pipeline flush
inside a window dispatch) stay disjoint and the phases sum to the
thread's wall time by construction. Each bracket costs two
``perf_counter`` reads and one ``jax.profiler.TraceAnnotation
("dyn.<phase>")``, which is an atomic load while no profiler session is
open and, while one is, puts the phase on the step thread's line of
``/host:CPU`` on the device trace's clock.
``stats()["step_phase_seconds_total"]`` carries the ledger.

Nothing here touches the device: device time is read from a profiler
trace (benchmark/harness/trace.py, host_trace.py), never from a host
clock around a sync (dynalint DL018 holds profiler code to that).
"""

from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation

from ..runtime import profiling


# exhaustive and disjoint on the step thread (docs/profiling.md has the
# table of what each covers); "other" is what of _step no bracket names
PHASES = ("admit", "kv_tier", "dispatch_window", "dispatch_prefill",
          "readback_window", "process_window", "readback_prefill",
          "process_prefill", "between_steps", "idle", "other")
# ledger slot of the time between two _step calls, settled into
# between_steps or idle when the next _step (or a stats() read) arrives
_GAP = "_gap"


def _settle_gap(seconds: Dict[str, float], slept: bool) -> None:
    seconds["idle" if slept else "between_steps"] += seconds[_GAP]
    seconds[_GAP] = 0.0


class _Phase:
    """One phase's re-usable bracket (``with profiler.phase(name):``).
    The ledger lives on the profiler; a bracket only parks, on the
    profiler's stack, the phase to hand the clock back to and its open
    trace annotation."""

    __slots__ = ("prof", "name", "label")

    def __init__(self, prof: "EngineProfiler", name: str):
        self.prof = prof
        self.name = name
        self.label = "dyn." + name

    def __enter__(self) -> None:
        stack = self.prof._open
        stack.append(self.prof._switch(self.name))
        stack.append(TraceAnnotation(self.label))

    def __exit__(self, *exc) -> None:
        stack = self.prof._open
        stack.pop().__exit__(*exc)
        self.prof._switch(stack.pop())


class EngineProfiler:
    """Per-engine step-phase ledger. All mutation happens on the
    engine's single-worker executor thread (the same serialization the
    scheduler itself relies on); ``summary()`` and ``phase_snapshot()``
    reads are snapshot-style dict builds."""

    def __init__(self, name: str):
        self.name = name
        # seconds per phase, the phase the clock is running for, and when
        # it started running
        self.phase_seconds: Dict[str, float] = dict.fromkeys(
            PHASES + (_GAP,), 0.0)
        self.step_iterations = 0
        self.slept = False      # _loop waited on _wake since the last step
        self._phases = {n: _Phase(self, n) for n in PHASES}
        self._open: list = []   # outer phase, annotation per open bracket
        self._step_ann = None
        self._cur = _GAP
        self._t = time.perf_counter()
        self._ver = 0           # odd while _switch is mid-update
        profiling.register_profile(name, self)

    def phase(self, name: str) -> _Phase:
        return self._phases[name]

    def _switch(self, name: str) -> str:
        """Close the running phase's interval and start ``name``'s;
        returns the phase that was running."""
        now = time.perf_counter()
        prev = self._cur
        self._ver += 1
        self.phase_seconds[prev] += now - self._t
        self._cur = name
        self._t = now
        self._ver += 1
        return prev

    def step_begin(self) -> None:
        """Entry of one ``_step``: the time since the last one ended was
        ``idle`` if the loop slept on its wake event in between, else
        ``between_steps`` (executor hop, reap)."""
        self._switch("other")
        _settle_gap(self.phase_seconds, self.slept)
        self.slept = False
        self.step_iterations += 1
        # a TraceAnnotation opens when it is made and closes in __exit__
        self._step_ann = TraceAnnotation("dyn.step")

    def step_end(self) -> None:
        self._step_ann.__exit__(None, None, None)
        self._switch(_GAP)

    def phase_snapshot(self) -> Dict[str, float]:
        """{phase: cumulative seconds} up to now, the running interval
        included, so two snapshots differ by the wall time between them.
        Read from any thread: retried while the step thread is inside
        ``_switch``."""
        for _ in range(16):
            ver = self._ver
            seconds = dict(self.phase_seconds)
            cur, t = self._cur, self._t
            if ver == self._ver and not ver & 1:
                break
        seconds[cur] += time.perf_counter() - t
        _settle_gap(seconds, self.slept)
        del seconds[_GAP]
        return seconds

    def summary(self) -> dict:
        """What /debug/profile and a blackbox dump carry per engine."""
        return {"step_iterations": self.step_iterations,
                "phase_seconds": self.phase_snapshot()}


def memory_snapshot(pm, page_bytes: int) -> dict:
    """HBM/page occupancy accounting from a PageManager: live (allocated,
    refcounted), cached (reusable prefix pages), free — in pages and KV
    bytes — plus the host tier when configured. Host-side reads only."""
    free = len(pm.free)
    cached = len(pm.reusable)
    live = pm.num_pages - 1 - free - cached
    out = {
        "page_bytes": page_bytes,
        "hbm": {
            "live_pages": live, "cached_pages": cached, "free_pages": free,
            "live_bytes": live * page_bytes,
            "cached_bytes": cached * page_bytes,
            "free_bytes": free * page_bytes,
        },
    }
    if pm.host_pages > 0:
        host_free = len(pm.host_free)
        host_used = pm.host_pages - host_free
        out["host"] = {
            "used_pages": host_used, "free_pages": host_free,
            "used_bytes": host_used * page_bytes,
        }
    return out
