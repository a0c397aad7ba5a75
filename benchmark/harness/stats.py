"""Arithmetic from the load generator's rows to latency numbers: the
part of the yardstick no later PR may change. No jax, no numpy.

A row is what harness/loadgen.py prints per request. Times are seconds
after the window opened, on the load generator's clock.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional


def pctile(vals: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, q in (0, 1]; None on empty (the rule of
    dynamo_tpu.runtime.slo.nearest_rank, copied so the yardstick does
    not move with the program)."""
    xs = sorted(vals)
    if not xs:
        return None
    return xs[min(max(math.ceil(q * len(xs)), 1), len(xs)) - 1]


def ok(row: dict) -> bool:
    """Answered in full: HTTP 200, SSE ended in [DONE], exactly the
    requested number of tokens, the usage block agreeing."""
    u = row.get("usage") or {}
    return (row["status"] == 200 and row["done"] and not row["error"]
            and row["tokens"] == row["output_len"]
            and u.get("completion_tokens") == row["output_len"]
            and row["finish"] in ("length", "stop"))


def failed(row: dict) -> bool:
    """A request that was not cut by the end of a closed-loop window and
    was not answered in full."""
    return not row["cut"] and not ok(row)


def ttft_s(row: dict) -> float:
    """First content chunk minus the time the request was DUE; a request
    that never produced a token (failed, refused, timed out) is the
    worst there can be."""
    if not row["chunk_s"]:
        return math.inf
    return row["chunk_s"][0] - row["due_s"]


def gaps_s(row: dict) -> List[float]:
    """Raw gaps between consecutive streamed content chunks."""
    t = row["chunk_s"]
    return [b - a for a, b in zip(t, t[1:])]


def tpot_s(row: dict) -> Optional[float]:
    """(last chunk - first chunk) / (output tokens - 1) of a request
    answered in full; None where that is undefined."""
    if not ok(row) or row["tokens"] < 2 or len(row["chunk_s"]) < 2:
        return None
    # tokens after the first CHUNK (a chunk may carry several)
    later = row["tokens"] - row["chunk_n"][0]
    if later < 1:
        return None
    return (row["chunk_s"][-1] - row["chunk_s"][0]) / later


def late_s(row: dict) -> Optional[float]:
    """How late the generator sent the request (sent - due)."""
    if row["sent_s"] is None or row["due_s"] is None:
        return None
    return row["sent_s"] - row["due_s"]


def tokens_in_window(rows: Iterable[dict], window_s: float) -> int:
    """Output tokens that ARRIVED inside the window, whether or not
    their request ended in it."""
    return sum(n for r in rows for t, n in zip(r["chunk_s"], r["chunk_n"])
               if 0.0 <= t <= window_s)


def finite_ms(x: Optional[float]) -> Optional[float]:
    """Seconds to milliseconds; an infinite (failed) value is reported as
    the run's timeout would be: None, so the metric is left out and the
    run is judged by ``failed``."""
    if x is None or math.isinf(x):
        return None
    return x * 1000.0
