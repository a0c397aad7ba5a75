"""Deltas of the host's own counters over the measured window: the
per-thread and per-phase dictionaries ``JaxEngine.stats()`` carries
since PR 35 (``step_phase_cpu_seconds_total``, ``thread_cpu_seconds_
total``, ``thread_runq_wait_seconds_total``, ``loop_phase_seconds_
total``, ``gc_pause_seconds_total``). ``counters.py`` reads the flat
keys; this file adds to it and changes nothing there. Every function
returns None where a key is missing: the parent's ``stats()`` has none
of them, and a platform without ``/proc/self/task/<tid>/schedstat`` or
``gc.callbacks`` leaves its keys out rather than reporting zero.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.harness import counters

CPU_KEY = "step_phase_cpu_seconds_total"
# the step thread blocked on the device, with nothing queued, or waiting
# for the event loop to hand it the next iteration: not its own work
NOT_WORK = ("readback_window", "readback_prefill", "idle", "between_steps")


def dict_delta(raw: dict, key: str) -> Optional[Dict[str, float]]:
    a, b = raw["stats0"].get(key), raw["stats1"].get(key)
    if not isinstance(a, dict) or not isinstance(b, dict):
        return None
    return {k: v - a.get(k, 0.0) for k, v in b.items()}


def wall_s(raw: dict) -> Optional[float]:
    """Seconds between the two ``stats()`` reads: the step thread's
    phases add up to its wall time by construction."""
    d = counters.phase_deltas(raw)
    total = sum(d.values()) if d else 0.0
    return total if total > 0 else None


def share_of_wall(raw: dict, seconds: Optional[float]) -> Optional[float]:
    wall = wall_s(raw)
    if seconds is None or wall is None:
        return None
    return 100.0 * seconds / wall


def thread_seconds(raw: dict, key: str, threads) -> Optional[float]:
    """Sum over ``threads`` of the delta of a per-thread counter; None
    unless every one of them is there."""
    d = dict_delta(raw, key)
    if d is None or any(t not in d for t in threads):
        return None
    return sum(d[t] for t in threads)
