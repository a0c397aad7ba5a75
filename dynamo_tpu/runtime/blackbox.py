"""dynablack: the incident flight recorder.

Every telemetry plane in this tree is sampled, windowed, or ring-bounded
(DYN_TRACE_SAMPLE, DYN_STEP_TIMELINE, the bounded stall table) — correct
for steady-state overhead, useless at 3 a.m. when the evidence of *why*
a burn-rate alert fired or a breaker opened has already rotated out.
The standard production answer (Dapper's always-on sampling plus
Canopy-style trigger-driven retroactive capture) is what this module
implements:

- :class:`ShadowRing` — a bounded, lock-free per-worker event ring with
  the dyntrace anchor-pair discipline (``anchor_wall`` +
  ``anchor_monotonic`` stamped once; every event carries a ``mono_ms``
  offset) so rings from different workers align on one timeline.
- :class:`FlightRecorder` — holds the rings, a trigger registry, and a
  bounded incident table. On :meth:`trip` it freezes the rings,
  assembles a JSON **incident bundle** folding the last
  ``DYN_BLACKBOX_WINDOW_S`` seconds of *existing* telemetry (tracer
  spans, step timelines, profiler/cache/memory snapshots, loop lag,
  stall stacks, request attributions, guard counters, breaker and chaos
  state, engine stats), persists it under ``DYN_BLACKBOX_DIR``, and
  debounces with ``DYN_BLACKBOX_COOLDOWN_S``.
- Trigger notifications (:func:`notify_trigger`, :func:`note_deadline`)
  wired from the events that already exist: SLO burn-rate trips
  (slo.py), breaker ``closed→open`` (guard.py), post-warmup compiles
  (jit_fence.py), watchdog stall captures (profiling.py), failover
  resumes (revive.py), and deadline storms (N timeouts in W seconds).
- DCP fan-out (:func:`attach_dcp` / :func:`broadcast_capture`) over the
  optional ``blackbox.capture`` wire frame so sibling workers
  contribute their rings to the same incident id.

Hot-path contract (the A/B acceptance criterion): an armed-but-untripped
recorder costs one global read + a ``None``/bool check per
:func:`note` call and *nothing* anywhere else — every fold of real
telemetry happens at capture time, on the cold path. No host syncs
(DL005), no eager formatting (DL023), every container bounded (DL024).

Trigger sources lazy-import this module inside their cold event paths;
this module lazy-imports tracing/profiling/guard at capture time, so no
import cycle exists at module load.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional

from .config import env_float, env_str
from .tracing import json_safe

log = logging.getLogger("dynamo_tpu.blackbox")

#: every trigger the registry knows; DYN_BLACKBOX_TRIGGERS filters this.
TRIGGERS = ("slo_burn_rate", "breaker_open", "post_warmup_compile",
            "watchdog_stall", "failover_resume", "deadline_storm", "manual")

# deadline storm: this many DeadlineExceeded within this window = trip
STORM_N = 8
STORM_WINDOW_S = 5.0

#: DCP subject the capture fan-out rides on (namespaced by the caller)
BLACKBOX_SUBJECT = "blackbox.capture"


# ------------------------------------------------------------- shadow ring


class ShadowRing:
    """Bounded per-worker event ring, lock-free on the append path.

    ``deque.append`` on a ``maxlen`` deque is a single GIL-atomic
    operation, so writers from any thread never contend and never grow
    the ring (the dynaprof ring idiom). Anchors follow the StepTimeline
    pair discipline: stamped once at construction (and on
    :meth:`restamp` after a restart), events carry only the monotonic
    offset, wall time is derived at export."""

    __slots__ = ("label", "anchor_wall", "anchor_monotonic",
                 "_events", "_clock", "_wall")

    def __init__(self, label: str, maxlen: int = 512,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time):
        self.label = label
        self._clock = clock
        self._wall = wall
        self._events: deque = deque(maxlen=maxlen)  # bounded ring
        self.anchor_wall = 0.0
        self.anchor_monotonic = 0.0
        self.restamp()

    def restamp(self) -> None:
        """Re-stamp the anchor pair (worker restart): events recorded
        after a restamp must never alias pre-restart ``mono_ms`` values,
        so the ring is cleared with the anchors."""
        self._events.clear()
        self.anchor_monotonic = self._clock()
        self.anchor_wall = self._wall()

    def note(self, kind: str, **fields: Any) -> None:
        """Append one event. Hot-path safe: no formatting, no locks —
        fields are stored raw and coerced JSON-safe only at capture."""
        fields["kind"] = kind
        fields["mono_ms"] = round(
            (self._clock() - self.anchor_monotonic) * 1000.0, 3)
        self._events.append(fields)

    def __len__(self) -> int:
        return len(self._events)

    def anchors(self) -> dict:
        return {"anchor_wall": round(self.anchor_wall, 6),
                "anchor_monotonic": round(self.anchor_monotonic, 6)}

    def snapshot(self, window_s: Optional[float] = None) -> List[dict]:
        """Events (oldest first), optionally only the last ``window_s``
        seconds, as JSON-safe dicts with derived ``ts_ms`` wall stamps."""
        items = [dict(e) for e in self._events]
        if window_s is not None and window_s > 0:
            cutoff = ((self._clock() - self.anchor_monotonic)
                      - window_s) * 1000.0
            items = [e for e in items if e.get("mono_ms", 0.0) >= cutoff]
        base_ms = self.anchor_wall * 1000.0
        for e in items:
            e["ts_ms"] = round(base_ms + e.get("mono_ms", 0.0), 3)
        return [json_safe(e) for e in items]

    def export(self, window_s: Optional[float] = None) -> dict:
        return {"anchors": self.anchors(),
                "events": self.snapshot(window_s)}


# --------------------------------------------------------- flight recorder


class FlightRecorder:
    """Shadow rings + trigger registry + bounded incident table.

    Everything time-related is injectable (``clock``/``wall``/
    ``id_factory``) so the fleet simulator can run the recorder on its
    virtual clock and produce byte-identical bundles per seed.
    ``include_process_state=False`` skips the live-process telemetry
    fold (tracer/profiler/guard globals) — the sim uses it because those
    globals are not part of the deterministic virtual world."""

    def __init__(self, window_s: Optional[float] = None,
                 out_dir: Optional[str] = None,
                 cooldown_s: Optional[float] = None,
                 triggers: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time,
                 id_factory: Optional[Callable[[], str]] = None,
                 include_process_state: bool = True,
                 ring_len: int = 512,
                 max_incidents: int = 32):
        if window_s is None:
            window_s = env_float("DYN_BLACKBOX_WINDOW_S") or 0.0
        if cooldown_s is None:
            cooldown_s = env_float("DYN_BLACKBOX_COOLDOWN_S") or 0.0
        if out_dir is None:
            out_dir = env_str("DYN_BLACKBOX_DIR")
        if triggers is None:
            triggers = env_str("DYN_BLACKBOX_TRIGGERS") or "all"
        self.window_s = float(window_s)
        self.cooldown_s = float(cooldown_s)
        self.out_dir = out_dir
        self.triggers = self._parse_triggers(triggers)
        self.include_process_state = include_process_state
        self.ring_len = ring_len
        self._clock = clock
        self._wall = wall
        self._id_factory = id_factory
        self._lock = threading.Lock()
        # ring CREATION is locked; note() appends are lock-free deque pushes
        self.rings: Dict[str, ShadowRing] = {}  # guarded-by: self._lock
        # bounded-by: max_incidents (oldest incident evicted on insert)
        self._incidents: "OrderedDict[str, dict]" = OrderedDict()
        self._max_incidents = max_incidents
        self._sources: "OrderedDict[str, Callable[[], Any]]" = OrderedDict()
        # bounded-by: one weakref per registered engine; dead refs reaped at capture
        self._stats_sources: Dict[str, Any] = {}
        self._listeners: List[Callable[[dict], None]] = []
        self._deadlines: deque = deque(maxlen=STORM_N)  # bounded storm window
        self._last_capture: Optional[float] = None
        self._seq = 0
        self._baseline: dict = {}
        self.captures_total = 0
        self.suppressed_total = 0
        if self.enabled and include_process_state:
            self.refresh_baseline()

    @staticmethod
    def _parse_triggers(spec: str) -> frozenset:
        spec = (spec or "all").strip().lower()
        if spec in ("all", "*", ""):
            return frozenset(TRIGGERS)
        names = {t.strip() for t in spec.split(",") if t.strip()}
        unknown = names - set(TRIGGERS)
        if unknown:
            log.warning("DYN_BLACKBOX_TRIGGERS: unknown trigger(s) %s "
                        "ignored", sorted(unknown))
        return frozenset(names & set(TRIGGERS))

    # --------------------------------------------------------- hot path

    @property
    def enabled(self) -> bool:
        return self.window_s > 0

    def ring(self, worker: str) -> ShadowRing:
        r = self.rings.get(worker)
        if r is None:
            with self._lock:
                r = self.rings.get(worker)
                if r is None:
                    r = ShadowRing(worker, self.ring_len,
                                   self._clock, self._wall)
                    self.rings[worker] = r
        return r

    def note(self, worker: str, kind: str, **fields: Any) -> None:
        """The one per-event call sites pay while armed: a dict lookup
        and a deque append."""
        if not self.enabled:
            return
        self.ring(worker).note(kind, **fields)

    def note_deadline(self) -> None:
        """Deadline-storm detector: STORM_N DeadlineExceeded inside
        STORM_WINDOW_S trips a capture."""
        if not self.enabled or "deadline_storm" not in self.triggers:
            return
        now = self._clock()
        self._deadlines.append(now)
        if (len(self._deadlines) == STORM_N
                and now - self._deadlines[0] <= STORM_WINDOW_S):
            self.trip("deadline_storm", {
                "timeouts": STORM_N,
                "window_s": round(now - self._deadlines[0], 3)})

    # ------------------------------------------------------- registration

    def add_source(self, name: str, fn: Callable[[], Any]) -> None:
        """Extra snapshot provider folded into every bundle under
        ``sources.<name>`` (e.g. the frontend's SLO snapshot, the
        aggregator's last fleet scrape). Bound methods are held weakly
        so a source never pins its owner."""
        if hasattr(fn, "__self__"):
            fn = weakref.WeakMethod(fn)  # type: ignore[assignment]
            self._sources[name] = lambda ref=fn: (ref() or _none)()
        else:
            self._sources[name] = fn

    def register_stats_source(self, label: str, owner: Any) -> None:
        """An engine-shaped object whose ``stats()`` is folded into the
        bundle's ``telemetry.engines.<label>`` (held weakly)."""
        self._stats_sources[label] = weakref.ref(owner)

    def add_capture_listener(self, fn: Callable[[dict], None]) -> None:
        """Called with each freshly assembled bundle (DCP broadcast,
        tests)."""
        self._listeners.append(fn)

    def refresh_baseline(self) -> None:
        """Snapshot the engines' phase ledgers + cache stats as the
        pre-incident baseline a postmortem is read against.
        Called at construction, from CompileFence.arm() (end of
        warmup), and after every capture."""
        if not self.enabled or not self.include_process_state:
            self._baseline = {}
            return
        from . import profiling
        self._baseline = json_safe({
            "at_wall_ms": round(self._wall() * 1000.0, 3),
            "profiles": profiling.profiles_snapshot(),
            "caches": profiling.caches_snapshot(),
        })

    # ------------------------------------------------------------ capture

    def cooldown_remaining_s(self) -> float:
        if self._last_capture is None or self.cooldown_s <= 0:
            return 0.0
        return max(0.0, self.cooldown_s
                   - (self._clock() - self._last_capture))

    def trip(self, trigger: str, detail: Optional[dict] = None
             ) -> Optional[dict]:
        """Fire a trigger: freeze the rings and assemble a bundle.
        Returns None when disabled, the trigger is filtered out, or the
        cooldown debounce suppresses the capture."""
        if not self.enabled or trigger not in self.triggers:
            return None
        with self._lock:
            if self.cooldown_remaining_s() > 0:
                self.suppressed_total += 1
                return None
            self._last_capture = self._clock()
            bundle = self._assemble(trigger, detail)
            self._remember(bundle)
            self.captures_total += 1
        self._persist(bundle)
        for fn in list(self._listeners):
            try:
                fn(bundle)
            except Exception:
                log.exception("blackbox capture listener failed")
        self.refresh_baseline()
        return bundle

    def _next_id(self) -> str:
        if self._id_factory is not None:
            return self._id_factory()
        self._seq += 1
        return f"incident-{int(self._wall() * 1000.0):x}-{self._seq:02d}"

    def _assemble(self, trigger: str, detail: Optional[dict]) -> dict:
        bundle = {
            "id": self._next_id(),
            "trigger": trigger,
            "detail": json_safe(detail) if detail else {},
            "at_wall_ms": round(self._wall() * 1000.0, 3),
            "at_mono_ms": round(self._clock() * 1000.0, 3),
            "window_s": self.window_s,
            "workers": {label: r.export(self.window_s)
                        for label, r in sorted(self.rings.items())},
            "contributed": [],
            "baseline": self._baseline,
            "sources": self._fold_sources(),
        }
        if self.include_process_state:
            bundle["telemetry"] = self._fold_telemetry()
        return bundle

    def _fold_sources(self) -> dict:
        out = {}
        for name, fn in self._sources.items():
            try:
                out[name] = json_safe(fn())
            except Exception:
                log.exception("blackbox source %s failed", name)
                out[name] = None
        return out

    def _fold_telemetry(self) -> dict:
        """Cold path: fold the last window of every existing telemetry
        plane. Every read here is a snapshot of an already-bounded
        structure — nothing synchronizes with a device."""
        from . import guard, profiling, tracing
        since_ms = (self._wall() - self.window_s) * 1000.0
        tracer = tracing.get_tracer()
        spans = [s.to_dict() for s in tracer.snapshot()
                 if s.wall_start * 1000.0 >= since_ms]
        engines = {}
        for label, ref in list(self._stats_sources.items()):
            owner = ref()
            if owner is None:
                self._stats_sources.pop(label, None)
                continue
            try:
                engines[label] = owner.stats()
            except Exception:
                log.exception("blackbox stats source %s failed", label)
        return json_safe({
            "traces": tracer.traces_summary(limit=200, since_ms=since_ms),
            "spans": spans,
            "timelines": tracing.timelines_snapshot(limit=500,
                                                    since_ms=since_ms),
            "timeline_anchors": tracing.timeline_anchors(),
            "profiles": profiling.profiles_snapshot(),
            "caches": profiling.caches_snapshot(),
            "loop_lag": profiling.loop_lag_snapshot(),
            "stall_stacks": profiling.stall_stacks_folded(limit=50),
            "attributions": [
                {"request_id": rid, "cost": cost}
                for rid, cost in profiling.attributions_snapshot(limit=100)],
            "guard_counters": guard.counters_snapshot(),
            "breakers": guard.boards_snapshot(),
            "chaos": _chaos_snapshot(),
            "engines": engines,
        })

    def _remember(self, bundle: dict) -> None:
        self._incidents[bundle["id"]] = bundle
        while len(self._incidents) > self._max_incidents:
            self._incidents.popitem(last=False)

    def _persist(self, bundle: dict) -> None:
        if not self.out_dir:
            return
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            path = os.path.join(self.out_dir, f"{bundle['id']}.json")
            with open(path, "w", encoding="utf-8") as f:
                f.write(render_bundle_json(bundle))
        except OSError:
            log.exception("blackbox: failed to persist incident %s",
                          bundle["id"])

    # ----------------------------------------------------- incident table

    def incidents_summary(self) -> List[dict]:
        """Newest-first one-row-per-incident summaries for
        GET /debug/incidents."""
        with self._lock:
            rows = [{
                "id": b["id"],
                "trigger": b["trigger"],
                "at_wall_ms": b["at_wall_ms"],
                "workers": sorted(b["workers"].keys()),
                "contributed": list(b.get("contributed", [])),
                "remote": bool(b.get("remote", False)),
            } for b in self._incidents.values()]
        return rows[::-1]

    def get(self, incident_id: str) -> Optional[dict]:
        with self._lock:
            return self._incidents.get(incident_id)

    def rings_export(self, window_s: Optional[float] = None) -> dict:
        """All local rings, for contributing to a sibling's incident."""
        if window_s is None:
            window_s = self.window_s
        return {label: r.export(window_s)
                for label, r in sorted(self.rings.items())}

    def contribute(self, incident_id: str, workers: dict,
                   origin: Optional[str] = None) -> bool:
        """Merge a sibling's rings into an existing incident (first
        writer per worker label wins; re-persists the bundle)."""
        with self._lock:
            bundle = self._incidents.get(incident_id)
            if bundle is None:
                return False
            for label, data in workers.items():
                bundle["workers"].setdefault(label, json_safe(data))
            if origin:
                bundle["contributed"] = sorted(
                    set(bundle.get("contributed", [])) | {origin})
        self._persist(bundle)
        return True

    def observe_remote(self, incident_id: str, trigger: str, origin: str,
                       at_ms: Optional[float] = None) -> dict:
        """A sibling announced a capture: open a local incident stub
        (bypasses cooldown — the debounce belongs to the originator)
        carrying this process's rings."""
        with self._lock:
            bundle = self._incidents.get(incident_id)
            if bundle is not None:
                return bundle
            bundle = {
                "id": incident_id,
                "trigger": trigger,
                "detail": {},
                "origin": origin,
                "remote": True,
                "at_wall_ms": (round(float(at_ms), 3) if at_ms is not None
                               else round(self._wall() * 1000.0, 3)),
                "window_s": self.window_s,
                "workers": {label: r.export(self.window_s)
                            for label, r in sorted(self.rings.items())},
                "contributed": [],
                "baseline": self._baseline,
                "sources": self._fold_sources(),
            }
            self._remember(bundle)
        self._persist(bundle)
        return bundle


def _none() -> None:
    return None


def _chaos_snapshot() -> Optional[dict]:
    from . import guard
    inj = guard.chaos()
    injected = getattr(inj, "injected", None)
    if not injected:
        return None
    return {"injected": {f"{action}:{point}": n
                         for (action, point), n in sorted(injected.items())}}


def render_bundle_json(bundle: dict) -> str:
    """The one canonical bundle serialization: sorted keys, fixed
    indent, the dyntrace JSON-safe coercion — byte-stable given equal
    content (the fleet-sim determinism contract)."""
    return json.dumps(json_safe(bundle), sort_keys=True, indent=2)


# --------------------------------------------------------- module recorder

_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    """The process-wide recorder, created lazily from the environment."""
    global _recorder
    rec = _recorder
    if rec is None:
        with _recorder_lock:
            rec = _recorder
            if rec is None:
                rec = _recorder = FlightRecorder()
    return rec


def configure(recorder: Optional[FlightRecorder] = None,
              **kwargs: Any) -> FlightRecorder:
    """Install a specific recorder (tests, sims) or rebuild from kwargs."""
    global _recorder
    with _recorder_lock:
        _recorder = recorder if recorder is not None \
            else FlightRecorder(**kwargs)
    return _recorder


def reset() -> None:
    """Test hook: drop the process recorder (next use re-reads env)."""
    global _recorder
    with _recorder_lock:
        _recorder = None


def notify_trigger(trigger: str, detail: Optional[dict] = None
                   ) -> Optional[dict]:
    """Trigger-source entry point (guard/slo/jit_fence/profiling/revive
    lazy-import and call this on their cold event paths)."""
    return get_recorder().trip(trigger, detail)


def note(worker: str, kind: str, **fields: Any) -> None:
    """Shadow-ring append. A process that never configured or armed a
    recorder pays one global read and a ``None`` check."""
    rec = _recorder
    if rec is None or not rec.enabled:
        return
    rec.note(worker, kind, **fields)


def note_deadline() -> None:
    """Deadline-storm sample (guard.py). Same no-op contract as
    :func:`note` when nothing is armed."""
    rec = _recorder
    if rec is None or not rec.enabled:
        return
    rec.note_deadline()


# ------------------------------------------------------------ DCP fan-out


def capture_header(incident_id: str, trigger: str, worker_label: str,
                   at_ms: Optional[float] = None,
                   rings: Optional[dict] = None) -> dict:
    """Build + validate one ``blackbox.capture`` frame. ``rings`` absent
    = origin announcement; present = a sibling's contribution."""
    from . import wire
    header: Dict[str, Any] = {
        "event": "blackbox.capture",
        "incident_id": incident_id,
        "trigger": trigger,
        "worker_label": worker_label,
    }
    if at_ms is not None:
        header["at_ms"] = float(at_ms)
    if rings is not None:
        header["rings"] = rings
    return wire.checked(wire.BLACKBOX_CAPTURE, header)


async def broadcast_capture(drt: Any, namespace: str, bundle: dict,
                            worker_label: str = "") -> None:
    """Announce a capture to siblings (they reply with their rings via
    the :func:`attach_dcp` handler)."""
    from .dcp_client import pack
    frame = capture_header(bundle["id"], bundle["trigger"], worker_label,
                           at_ms=bundle.get("at_wall_ms"))
    await drt.dcp.publish(f"{namespace}.{BLACKBOX_SUBJECT}", pack(frame))


async def attach_dcp(drt: Any, namespace: str, recorder: FlightRecorder,
                     worker_label: str,
                     rings_fn: Optional[Callable[[], dict]] = None) -> int:
    """Join the capture fan-out: on a sibling's origin announcement,
    record a local incident stub and publish this process's rings back;
    on a ring-carrying frame, merge it into the matching incident.
    Returns the subscription id."""
    from . import wire
    from .dcp_client import pack, unpack

    subject = f"{namespace}.{BLACKBOX_SUBJECT}"

    async def _on_capture(msg: Any) -> None:
        try:
            frame = wire.decoded(wire.BLACKBOX_CAPTURE, unpack(msg.payload))
        except Exception:
            log.debug("blackbox: ignoring undecodable capture frame",
                      exc_info=True)
            return
        if frame.get("event") != BLACKBOX_SUBJECT:
            return  # a foreign frame type sharing the subject
        if frame.get("worker_label") == worker_label:
            return  # own broadcast echoed back
        rings = frame.get("rings")
        if rings is not None:
            recorder.contribute(frame["incident_id"], rings,
                                origin=frame.get("worker_label"))
            return
        recorder.observe_remote(frame["incident_id"],
                                frame.get("trigger", "manual"),
                                frame.get("worker_label", ""),
                                frame.get("at_ms"))
        own = rings_fn() if rings_fn is not None else recorder.rings_export()
        reply = capture_header(frame["incident_id"],
                               frame.get("trigger", "manual"),
                               worker_label, rings=own)
        await drt.dcp.publish(subject, pack(reply))

    return await drt.dcp.subscribe(subject, _on_capture)
