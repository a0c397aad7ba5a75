"""dynaprof engine layer: the step thread's phase ledger.

Every second of the engine's step thread belongs to exactly one named
phase (``PHASES``), kept as a ledger (``runtime/profiling.py
PhaseLedger``, the class the event loop's and the detokeniser workers'
ledgers are instances of too): entering a phase closes the interval of
the one around it, so nested brackets (a pipeline flush inside a window
dispatch) stay disjoint and the phases sum to the thread's wall time by
construction. Each bracket costs two ``perf_counter`` reads, two
``thread_time`` reads (the thread's CPU clock: ~0.3 us each, a real
system call) and one ``jax.profiler.TraceAnnotation("dyn.<phase>")``,
which is an atomic load while no profiler session is open and, while
one is, puts the phase on the step thread's line of ``/host:CPU`` on the
device trace's clock. ``stats()["step_phase_seconds_total"]`` carries
the ledger and ``step_phase_cpu_seconds_total`` the CPU time beside it:
in a phase that does host work, wall less CPU is time the thread wanted
to run and did not (the GIL, or the kernel's run queue).

Nothing here touches the device: device time is read from a profiler
trace (benchmark/harness/trace.py, host_trace.py), never from a host
clock around a sync (dynalint DL018 holds profiler code to that).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

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


class EngineProfiler(profiling.PhaseLedger):
    """Per-engine step-phase ledger: the step thread's instance of the
    one ledger class, with nesting brackets. All mutation happens on the
    engine's single-worker executor thread (the same serialization the
    scheduler itself relies on); ``summary()`` and ``phase_snapshot()``
    reads are snapshot-style dict builds."""

    def __init__(self, name: str):
        super().__init__(PHASES + (_GAP,), _GAP, cpu=True,
                         annotation=TraceAnnotation)
        self.name = name
        self.step_iterations = 0
        self.slept = False      # _loop waited on _wake since the last step
        self._step_ann = None
        profiling.register_profile(name, self)

    def step_begin(self) -> None:
        """Entry of one ``_step``: the time since the last one ended was
        ``idle`` if the loop slept on its wake event in between, else
        ``between_steps`` (executor hop, reap)."""
        if threading.get_ident() != self._tid:
            self.bind_thread()  # the executor's worker, on its first step
        self._switch("other")
        _settle_gap(self.phase_seconds, self.slept)
        _settle_gap(self.phase_cpu_seconds, self.slept)
        self.slept = False
        self.step_iterations += 1
        # a TraceAnnotation opens when it is made and closes in __exit__
        self._step_ann = TraceAnnotation("dyn.step")

    def step_end(self) -> None:
        self._step_ann.__exit__(None, None, None)
        self._switch(_GAP)

    def _settled(self, seconds: Dict[str, float]) -> Dict[str, float]:
        _settle_gap(seconds, self.slept)
        del seconds[_GAP]
        return seconds

    def phase_snapshot(self) -> Dict[str, float]:
        """{phase: cumulative seconds} up to now, the running interval
        included, so two snapshots differ by the wall time between them.
        Read from any thread."""
        return self._settled(self.snapshot()[0])

    def cpu_snapshot(self) -> Dict[str, float]:
        """{phase: cumulative CPU seconds of the step thread}, settled at
        each switch: without the running interval, which only the step
        thread itself could read."""
        return self._settled(self.snapshot()[1])

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
