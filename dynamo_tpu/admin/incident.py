"""dynablack postmortem renderer: ``python -m dynamo_tpu.admin.incident``.

Turns one persisted incident bundle (a ``GET /debug/incidents/{id}``
payload / ``DYN_BLACKBOX_DIR`` file / fleet-sim report ``incident``
block) into the human-readable 3 a.m. view:

- header: trigger, detail, capture time, contributing workers
- burn-rate timeline (SLO alert transitions found in the bundle)
- per-stage trace rollup (span name -> count / total / max duration)
- cache hit-rate cliff (windowed vs lifetime hit rate per cache)
- per-worker shadow rings, aligned by their timeline anchors

Every section renders defensively: a bundle missing a plane (sim
bundles carry no process telemetry; a frontend-only capture carries no
fleet scrape) prints "(not captured)" instead of crashing — the
acceptance bar is that the renderer never errors on a real bundle.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional


def _fmt_ms(ms: Optional[float]) -> str:
    if ms is None:
        return "-"
    return f"{ms:,.1f}ms"


def _section(title: str) -> List[str]:
    return ["", title, "-" * len(title)]


def render_header(bundle: dict) -> List[str]:
    lines = [f"incident {bundle.get('id', '?')}",
             "=" * max(len(f"incident {bundle.get('id', '?')}"), 8)]
    lines.append(f"trigger:     {bundle.get('trigger', '?')}")
    detail = bundle.get("detail") or {}
    if detail:
        lines.append(f"detail:      {json.dumps(detail, sort_keys=True)}")
    lines.append(f"captured at: {_fmt_ms(bundle.get('at_wall_ms'))} "
                 f"(window {bundle.get('window_s', '?')}s)")
    if bundle.get("origin"):
        lines.append(f"origin:      {bundle['origin']} (remote capture)")
    workers = bundle.get("workers") or {}
    contributed = bundle.get("contributed") or []
    lines.append(f"workers:     {len(workers)} ring(s): "
                 f"{', '.join(sorted(workers)) or '(none)'}")
    if contributed:
        lines.append(f"contributed: {', '.join(contributed)}")
    return lines


def render_burn_timeline(bundle: dict) -> List[str]:
    lines = _section("burn-rate timeline")
    events: List[dict] = []
    detail = bundle.get("detail") or {}
    if "burn_fast" in detail:
        events.append(detail)
    scrape = (bundle.get("sources") or {}).get("fleet_scrape") or {}
    for ev in scrape.get("alerts", []):
        if ev not in events:
            events.append(ev)
    if not events:
        lines.append("(no alert transitions captured)")
        return lines
    for ev in events:
        lines.append(
            f"  t={ev.get('at', '?')}  {ev.get('objective', '?'):<24} "
            f"{ev.get('state', '?'):<8} "
            f"fast={ev.get('burn_fast', '?')} slow={ev.get('burn_slow', '?')}")
    return lines


def render_stage_rollup(bundle: dict) -> List[str]:
    lines = _section("per-stage trace rollup")
    spans = (bundle.get("telemetry") or {}).get("spans") or []
    if not spans:
        lines.append("(no spans captured)")
        return lines
    stages: Dict[str, List[float]] = {}
    for s in spans:
        dur = s.get("duration_ms")
        if dur is not None:
            stages.setdefault(s.get("name", "?"), []).append(float(dur))
    rows = sorted(stages.items(), key=lambda kv: -sum(kv[1]))
    lines.append(f"  {'stage':<32} {'count':>6} {'total':>12} {'max':>12}")
    for name, durs in rows[:20]:
        lines.append(f"  {name:<32} {len(durs):>6} "
                     f"{_fmt_ms(sum(durs)):>12} {_fmt_ms(max(durs)):>12}")
    return lines


def render_cache_cliff(bundle: dict) -> List[str]:
    lines = _section("cache hit-rate cliff (windowed vs lifetime)")
    caches = (bundle.get("telemetry") or {}).get("caches") or {}
    base = (bundle.get("baseline") or {}).get("caches") or {}
    if not caches:
        lines.append("(no cache snapshots captured)")
        return lines

    def _rates(snap: dict) -> tuple:
        windowed = snap.get("hit_rate_windowed", snap.get("hit_rate"))
        lifetime = snap.get("hit_rate_lifetime", snap.get("hit_rate"))
        return windowed, lifetime

    for name, snap in sorted(caches.items()):
        if not isinstance(snap, dict):
            continue
        windowed, lifetime = _rates(snap)
        base_w, _ = _rates(base.get(name, {})) if isinstance(
            base.get(name), dict) else (None, None)
        parts = [f"  {name:<40}"]
        parts.append(f"windowed={windowed if windowed is not None else '-'}")
        parts.append(f"lifetime={lifetime if lifetime is not None else '-'}")
        if base_w is not None:
            parts.append(f"baseline={base_w}")
        lines.append(" ".join(str(p) for p in parts))
    return lines


def render_worker_rings(bundle: dict, max_events: int = 12) -> List[str]:
    lines = _section("per-worker shadow rings (timeline-anchor aligned)")
    workers = bundle.get("workers") or {}
    if not workers:
        lines.append("(no shadow rings captured)")
        return lines
    for label in sorted(workers):
        data = workers[label] or {}
        anchors = data.get("anchors") or {}
        events = data.get("events") or []
        lines.append(f"  {label}: {len(events)} event(s), "
                     f"anchor wall={anchors.get('anchor_wall', '-')} "
                     f"mono={anchors.get('anchor_monotonic', '-')}")
        for ev in events[-max_events:]:
            kind = ev.get("kind", "?")
            rest = {k: v for k, v in ev.items()
                    if k not in ("kind", "mono_ms", "ts_ms")}
            lines.append(f"    +{ev.get('mono_ms', '?')}ms {kind:<14} "
                         + json.dumps(rest, sort_keys=True))
        if len(events) > max_events:
            lines.append(f"    ... ({len(events) - max_events} earlier "
                         "event(s) omitted)")
    return lines


def render_guard_state(bundle: dict) -> List[str]:
    lines = _section("guard plane (breakers / counters / chaos)")
    tel = bundle.get("telemetry") or {}
    breakers = tel.get("breakers") or {}
    counters = tel.get("guard_counters") or {}
    chaos = tel.get("chaos")
    if not breakers and not counters and chaos is None:
        lines.append("(not captured)")
        return lines
    for board, rows in sorted(breakers.items()):
        for key, st in sorted((rows or {}).items()):
            lines.append(f"  breaker {board}/{key}: {st.get('state', '?')} "
                         f"(failures={st.get('failures', '?')}, "
                         f"opened_total={st.get('opened_total', '?')})")
    for name, val in sorted(counters.items()):
        lines.append(f"  counter {name} = {val}")
    if chaos:
        lines.append(f"  chaos injected: "
                     f"{json.dumps(chaos.get('injected', {}), sort_keys=True)}")
    return lines


def render_postmortem(bundle: dict) -> str:
    lines: List[str] = []
    lines += render_header(bundle)
    lines += render_burn_timeline(bundle)
    lines += render_stage_rollup(bundle)
    lines += render_cache_cliff(bundle)
    lines += render_guard_state(bundle)
    lines += render_worker_rings(bundle)
    lines.append("")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1 or argv[0] in ("-h", "--help"):
        print("usage: python -m dynamo_tpu.admin.incident <bundle.json>\n"
              "       (also accepts '-' for stdin)", file=sys.stderr)
        return 2
    if argv[0] == "-":
        raw = sys.stdin.read()
    else:
        try:
            with open(argv[0], "r", encoding="utf-8") as f:
                raw = f.read()
        except OSError as e:
            print(f"error: cannot read {argv[0]}: {e}", file=sys.stderr)
            return 1
    try:
        bundle = json.loads(raw)
    except ValueError as e:
        print(f"error: {argv[0]} is not JSON: {e}", file=sys.stderr)
        return 1
    if not isinstance(bundle, dict):
        print("error: bundle must be a JSON object", file=sys.stderr)
        return 1
    # a fleet-sim report was passed instead of a bundle: descend
    if "incident" in bundle and "trigger" not in bundle:
        bundle = bundle["incident"]
    print(render_postmortem(bundle))
    return 0


if __name__ == "__main__":
    sys.exit(main())
