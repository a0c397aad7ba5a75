"""Runtime compile fence: detect XLA compilation after warmup.

``JaxEngine.warmup()`` pre-compiles the full bucket grid so no compile
ever happens mid-serving — a mid-flight compile stalls every in-flight
request for the compile latency (seconds on TPU). The static side of
that invariant is dynajit (tools/dynalint, DL015-DL017); this module is
the runtime side: a fence armed at the end of ``warmup()`` that counts
every program built afterwards via JAX's monitoring hook. In JAX 0.9
``/jax/core/compile/backend_compile_duration`` wraps
``compile_or_get_cached`` whole: it fires once per program that reaches
the backend, on a real compile AND on a persistent-cache hit (then it
holds the retrieval, milliseconds to a second). The fence counts both:
either way a program was traced, lowered and loaded mid-serving, and
every in-flight request waited for it. The trigger, the timeline event
and the warn/raise messages say which it was (``cache_hit``: the cache's
own ``cache_hits`` / ``cache_retrieval_time_sec`` events tell).

``DYN_JIT_FENCE`` picks the reaction:

- unset/empty — count only: the counter is exported through
  ``stats()`` → ``ForwardPassMetrics`` →
  ``dyn_engine_post_warmup_compiles_total`` so the fleet metrics
  aggregator sees a mid-serving compile on any worker;
- ``warn`` — additionally log a warning with the compile duration;
- ``raise`` — raise ``PostWarmupCompileError`` from the compile path
  (the CI/test mode: the offending jit call fails loudly).

Every compile also lands a ``compile`` event in the engine's dyntrace
step timeline, so ``/v1/traces`` shows exactly where in the serving
schedule the stall happened.

The monitoring event carries only a duration — no call info — so the
engine stamps every fenced jit dispatch via ``note_dispatch`` (one
attribute store of raw refs, no formatting on the hot path). When the
fence trips, warn/raise messages and the blackbox trigger render that
note lazily into a call-form key (jit name + per-operand dtype[shape]
and static kwarg values): the runtime twin of dynaform's DL026
warmup-form-drift key.

ONE ``jax.monitoring`` duration listener serves the process: the set-up
ledger's (``runtime/profiling.py install_jit_listeners``, installed
before the first jit), to which this module subscribes; it dispatches
to live fences (weakly referenced — a dropped engine stops counting).
Compiles are process-global: with two
engines in one process (disagg smoke tests) a compile triggered by
either increments both armed fences, which is the honest reading — the
process stalled.
"""

from __future__ import annotations

import logging
import weakref
from typing import Optional

from ..runtime import profiling
from ..runtime.config import env_str

log = logging.getLogger("dynamo_tpu.engine.fence")

# the per-program duration event (a real backend compile or a
# persistent-cache hit; device_put does not record it)
COMPILE_EVENT = profiling.BACKEND_COMPILE_EVENT

_fences: "weakref.WeakSet[CompileFence]" = weakref.WeakSet()


class PostWarmupCompileError(RuntimeError):
    """A program was built after warmup with DYN_JIT_FENCE=raise."""


def _dispatch(duration_secs: float, cache_hit: bool) -> None:
    for fence in list(_fences):
        fence.on_compile(duration_secs, cache_hit)


class CompileFence:
    """Per-engine post-warmup compile counter + warn/raise tripwire."""

    def __init__(self, name: str, timeline=None,
                 mode: Optional[str] = None):
        self.name = name
        self.timeline = timeline
        self._mode_override = mode
        self.armed = False
        self.post_warmup_compiles = 0
        # (jit name, args, kwargs) of the most recent fenced dispatch —
        # raw refs only; the call-form summary is rendered lazily when a
        # fence trips (never on the dispatch hot path)
        self._last_dispatch: Optional[tuple] = None

    def note_dispatch(self, name: str, args: tuple = (),
                      kwargs: Optional[dict] = None) -> None:
        """Stamp the jitted call about to run. The compile monitoring
        event carries only a duration, so when the fence trips this note
        is the only way to name the offending call form. Cheap by
        design: one attribute store, no formatting."""
        self._last_dispatch = (name, args, kwargs)

    @staticmethod
    def _summ(x, depth: int = 0) -> str:
        dt = getattr(x, "dtype", None)
        sh = getattr(x, "shape", None)
        if dt is not None and sh is not None:
            return f"{dt}[{','.join(str(d) for d in sh)}]"
        if x is None or isinstance(x, (bool, int, float, str)):
            return repr(x)
        if isinstance(x, (tuple, list)) and depth < 2:
            inner = ", ".join(
                CompileFence._summ(e, depth + 1) for e in x[:4])
            if len(x) > 4:
                inner += f", ...{len(x)} items"
            return f"({inner})"
        return type(x).__name__

    def last_dispatch_form(self) -> str:
        """Render the most recent dispatch as a call-form key: jit name
        plus per-operand dtype[shape] / static-value summary."""
        if self._last_dispatch is None:
            return "<no dispatch recorded>"
        name, args, kwargs = self._last_dispatch
        try:
            parts = [self._summ(a) for a in args]
            for k, v in (kwargs or {}).items():
                parts.append(f"{k}={self._summ(v)}")
            return f"{name}({', '.join(parts)})"
        except Exception:  # never let diagnostics mask the real trip
            return f"{name}(<unprintable args>)"

    @property
    def mode(self) -> str:
        if self._mode_override is not None:
            return self._mode_override
        return (env_str("DYN_JIT_FENCE") or "").strip().lower()

    def arm(self) -> None:
        """Called at the end of warmup(): from here on, every backend
        compile counts against the zero-compile serving invariant."""
        profiling.subscribe_backend_compiles(_dispatch)
        _fences.add(self)
        self.armed = True
        # end of warmup = steady state begins: snapshot the pre-incident
        # phase-ledger/cache baseline dynablack postmortems are read against
        from ..runtime import blackbox
        rec = blackbox.get_recorder()
        if rec.enabled:
            rec.refresh_baseline()

    def disarm(self) -> None:
        self.armed = False

    def on_compile(self, duration_secs: float,
                   cache_hit: bool = False) -> None:
        if not self.armed:
            return
        self.post_warmup_compiles += 1
        if self.timeline is not None:
            self.timeline.add("compile",
                              duration_ms=round(duration_secs * 1e3, 3),
                              cache_hit=cache_hit,
                              post_warmup_total=self.post_warmup_compiles)
        # a post-warmup compile is an incident by definition (the
        # zero-compile invariant broke); already on the cold compile path
        from ..runtime import blackbox
        blackbox.notify_trigger("post_warmup_compile", {
            "fence": self.name,
            "duration_ms": round(duration_secs * 1e3, 3),
            "cache_hit": cache_hit,
            "post_warmup_total": self.post_warmup_compiles,
            "last_dispatch_form": self.last_dispatch_form(),
        })
        mode = self.mode
        what = ("program read from the compile cache" if cache_hit
                else "XLA compile")
        if mode == "raise":
            raise PostWarmupCompileError(
                f"{what} after warmup on {self.name} "
                f"({duration_secs * 1e3:.1f} ms, "
                f"{self.post_warmup_compiles} total): an unbucketed "
                f"shape or request-varying static arg reached a jitted "
                f"call — last dispatched form: "
                f"{self.last_dispatch_form()} — see dynajit/dynaform "
                f"(docs/static_analysis.md)")
        if mode == "warn":
            log.warning(
                "%s after warmup on %s (%.1f ms, %d total): "
                "an unbucketed shape or request-varying static arg "
                "reached a jitted call — last dispatched form: %s",
                what, self.name, duration_secs * 1e3,
                self.post_warmup_compiles, self.last_dispatch_form())
