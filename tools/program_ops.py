"""What one execution of a serving program is made of, from a device
trace: the ops that ran inside the executions of the programs whose name
matches ``--module`` (``prefill_step``, ``decode_window``), summed by
(kind, output type) and divided by the executions, heaviest first.

    python3 tools/program_ops.py .bench_trace/<cell> [--module prefill_step]

``.bench_trace/<cell>/`` is where ``benchmark/run.py --trace 1`` leaves
its slice. First line: executions, their mean duration, the ops' sum;
then ``ms an execution  x count an execution  kind type``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import trace as bm_trace


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="an .xplane.pb, or a directory with one")
    ap.add_argument("--module", default="prefill_step")
    ap.add_argument("--top", type=int, default=30)
    a = ap.parse_args()
    path = (a.trace if os.path.isfile(a.trace)
            else bm_trace.find_xplane(a.trace))
    plane = next(p for p in bm_trace.load(path).values() if p["ops"])
    mods = sorted((s, s + d) for n, s, d in plane["modules"]
                  if re.search(a.module, n))
    if not mods:
        print(json.dumps({"trace": path, "module": a.module,
                          "executions": 0}))
        return 1
    starts = [s for s, _ in mods]
    ms, count = defaultdict(float), defaultdict(int)
    for n, s, d in plane["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        kind, shp = bm_trace._op(n)
        if i < 0 or s >= mods[i][1] or bm_trace.CONTAINER_OP.match(kind):
            continue
        ms[kind, shp] += d * 1e3
        count[kind, shp] += 1
    n = len(mods)
    print(json.dumps({"trace": path, "module": a.module, "executions": n,
                      "mean_ms": sum(e - s for s, e in mods) * 1e3 / n,
                      "ops_sum_ms": sum(ms.values()) / n}))
    for key in sorted(ms, key=ms.get, reverse=True)[:a.top]:
        print("%8.3f ms  x%-6.1f %s %s" % (ms[key] / n, count[key] / n, *key))
    return 0


if __name__ == "__main__":
    sys.exit(main())
