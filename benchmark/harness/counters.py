"""Deltas of the engine's own counters over the measured window.

``raw["stats0"]`` / ``raw["stats1"]`` are ``JaxEngine.stats()`` before
and after the window. A reader built on these returns None where a key
is missing: the driver runs this benchmark code against a parent
program whose ``stats()`` does not have it yet.
"""

from __future__ import annotations

from typing import Dict, Optional

PHASES_KEY = "step_phase_seconds_total"


def delta(raw: dict, key: str) -> Optional[float]:
    a, b = raw["stats0"].get(key), raw["stats1"].get(key)
    if a is None or b is None:
        return None
    return b - a


def ratio(raw: dict, num: str, den: str, scale: float = 1.0
          ) -> Optional[float]:
    """scale x delta(num) / delta(den); None without both keys or with
    nothing counted in the denominator."""
    n, d = delta(raw, num), delta(raw, den)
    if n is None or not d:
        return None
    return scale * n / d


def phase_deltas(raw: dict) -> Optional[Dict[str, float]]:
    """{phase: seconds of the step thread inside the window}."""
    a, b = raw["stats0"].get(PHASES_KEY), raw["stats1"].get(PHASES_KEY)
    if not a or not b:
        return None
    return {k: v - a.get(k, 0.0) for k, v in b.items()}
