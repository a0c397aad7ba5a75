"""dynaprof engine layer: always-on step-thread phases, plus the sampled
device/host split + per-bucket cost.

Two halves. The HOST half is always on: every second of the engine's
step thread belongs to exactly one named phase (``PHASES``), kept as a
ledger — entering a phase closes the interval of the one around it, so
nested brackets (a pipeline flush inside a window dispatch) stay
disjoint and the phases sum to the thread's wall time by construction.
Each bracket costs two ``perf_counter`` reads and one
``jax.profiler.TraceAnnotation("dyn.<phase>")``, which is an atomic load
while no profiler session is open and, while one is, puts the phase on
the step thread's line of ``/host:CPU`` on the device trace's clock.
``stats()["step_phase_seconds_total"]`` carries the ledger.

The DEVICE half is the sampled sync below, off by default. Under
pipelining its "device time" is a host-clock drain of everything queued
before the sampled dispatch; device time proper is read from a profiler
trace (benchmark/harness/trace.py).

The serving loop's time goes three places: device compute, host dispatch
(Python building arrays + enqueueing the jitted call), and event-loop /
queue stalls. The runtime layer (runtime/profiling.py) measures the
third; this module measures the first two — *per compiled program* — so
"383 vs 1129 tok/s is scheduler overhead, not FLOPs" becomes a table,
not an inference.

Mechanism: every ``DYN_PROF_SAMPLE``-th scheduler iteration is a
*sampled* iteration. On a sampled iteration each dispatch is bracketed —
``t0 → dispatch returns (host cost) → block_until_ready (device
queue+compute drain)`` — and the figures accumulate into a per-bucket
cost table keyed by ``kind:B..xP..[xT/K..]``, i.e. exactly the compiled
program the warmed grid provides. The ``block_until_ready`` is a
DELIBERATE host sync: it serializes that one iteration's pipeline (the
documented sampling overhead), which is why it is

- gated behind ``self.sampling`` (dynalint DL018 fails an unguarded
  sync in profiler code paths), and
- completely absent at ``DYN_PROF_SAMPLE=0`` (default): the per-dispatch
  cost is one integer compare — the compile fence + step timeline stay
  byte-identical (tests/test_profiling.py pins this).

The table exposes which ``(bucket_len, bucket_batch)`` programs the
ROADMAP item-3 hot-path overhaul must attack: dispatch-µs per program is
the scheduler-overhead term, tokens/s per program the FLOPs term.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from ..runtime import profiling
from ..runtime.config import env_int


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
    """Per-engine step-phase ledger + sampled dispatch timer + cost
    table. All mutation happens on the engine's single-worker executor
    thread (the same serialization the scheduler itself relies on);
    ``summary()`` and ``phase_snapshot()`` reads are snapshot-style dict
    builds."""

    def __init__(self, name: str, timeline=None,
                 sample: Optional[int] = None):
        if sample is None:
            sample = env_int("DYN_PROF_SAMPLE") or 0
        self.name = name
        self.sample = max(int(sample), 0)
        self.timeline = timeline
        self.sampling = False      # True while the CURRENT iteration samples
        self._iter = 0
        self.profiled_steps = 0
        self.device_seconds_total = 0.0
        self.dispatch_seconds_total = 0.0
        # "kind:B8xP64[xT512|xK4]" -> {samples, device_us, dispatch_us, tokens}
        self.buckets: Dict[str, dict] = {}
        # the always-on phase ledger: seconds per phase, the phase the
        # clock is running for, and when it started running
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

    # -------------------------------------------------------------- phases

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
        ``between_steps`` (executor hop, reap, loop-thread admission)."""
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

    # ------------------------------------------------------------ sampling

    def tick(self) -> None:
        """Once per scheduler iteration. At sample=0 this is the whole
        hot-path cost: one compare, no syncs, no timeline writes."""
        if self.sample <= 0:
            self.sampling = False
            return
        self._iter += 1
        self.sampling = (self._iter % self.sample) == 0

    def begin(self) -> Optional[float]:
        """Dispatch-bracket start, or None when this iteration is not
        sampled (so ``end`` is a no-op and not even perf_counter runs)."""
        return time.perf_counter() if self.sampling else None

    def end(self, t0: Optional[float], kind: str, key: Tuple[int, ...],
            tokens: int = 0, sync_ref=None) -> None:
        """Dispatch-bracket end: host cost = return-from-dispatch − t0;
        device cost = the drain until ``sync_ref`` is ready (queue +
        compute — under pipelining this includes previously enqueued
        work, which is the honest figure for "what the device is doing
        while the host dispatches")."""
        if self.sampling and t0 is not None:
            t1 = time.perf_counter()
            # the deliberate sampled sync (see module docstring)
            jax.block_until_ready(sync_ref)
            t2 = time.perf_counter()
            self._record(kind, key, t1 - t0, t2 - t1, tokens)

    def _record(self, kind: str, key: Tuple[int, ...], dispatch_s: float,
                device_s: float, tokens: int) -> None:
        label = f"{kind}:" + "x".join(str(k) for k in key)
        # bounded-by: labels are pow2-padded bucket shapes (fixed vocab)
        row = self.buckets.setdefault(label, {
            "samples": 0, "device_us": 0.0, "dispatch_us": 0.0,
            "tokens": 0})
        row["samples"] += 1
        row["device_us"] += device_s * 1e6
        row["dispatch_us"] += dispatch_s * 1e6
        row["tokens"] += int(tokens)
        self.profiled_steps += 1
        self.device_seconds_total += device_s
        self.dispatch_seconds_total += dispatch_s
        if self.timeline is not None:
            # bounded-by: StepTimeline is a deque(maxlen=) ring
            self.timeline.add(
                "prof_sample", bucket=label,
                dispatch_us=round(dispatch_s * 1e6, 1),
                device_us=round(device_s * 1e6, 1), tokens=int(tokens))

    # ------------------------------------------------------------- exports

    def device_time_fraction(self) -> float:
        total = self.device_seconds_total + self.dispatch_seconds_total
        return self.device_seconds_total / total if total > 0 else 0.0

    def mean_device_ms_per_step(self) -> Optional[float]:
        """Mean sampled device-drain per dispatch — the scale factor the
        per-request attribution uses to turn occupancy-weighted step
        shares into an estimated device-ms figure. None when nothing has
        been sampled (sample=0)."""
        if self.profiled_steps == 0:
            return None
        return self.device_seconds_total / self.profiled_steps * 1000.0

    def cost_table(self) -> Dict[str, dict]:
        """Per-bucket means: dispatch/device µs per dispatch plus
        device-side tokens/s — the regression surface for scheduler
        overhead per compiled program."""
        out: Dict[str, dict] = {}
        for label, row in sorted(self.buckets.items()):
            n = max(row["samples"], 1)
            dev_s = row["device_us"] / 1e6
            out[label] = {
                "samples": row["samples"],
                "dispatch_us": round(row["dispatch_us"] / n, 1),
                "device_us": round(row["device_us"] / n, 1),
                "tokens_per_s": (round(row["tokens"] / dev_s, 1)
                                 if dev_s > 0 and row["tokens"] else 0.0),
            }
        return out

    def summary(self) -> dict:
        return {
            "sample_every": self.sample,
            "profiled_steps": self.profiled_steps,
            "device_time_fraction": round(self.device_time_fraction(), 4),
            "device_seconds_total": round(self.device_seconds_total, 6),
            "dispatch_seconds_total": round(self.dispatch_seconds_total, 6),
            "buckets": self.cost_table(),
        }


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
