"""Device time of one layer's MoE MLP in either form, at the cells' shapes.

    chiprun -- python3 tools/moe_form_timing.py

Times ``models.llama._moe_mlp`` on one layer of Mixtral-8x7B's experts
(8, top-2, 4,096 x 14,336) at N = 128 / 512 / 2,048 rows and of
Qwen3-30B-A3B's (128, top-8, 2,048 x 768) at N = 2,048, with ~13%, ~50%
and 100% of the rows live, in the dense-over-experts form and in the
sorted dispatch (``moe_experts_blocked``): the rule of
``_moe_use_blocked`` rests on this table (PERF.md, PR 28). The form is
forced from here, by patching the rule while the program is traced; the
sorted form is also timed at block 128 and 256, and reading
``w[layer, expert]`` in place from a ``[2, E, ...]`` stack. Dead rows
are what a padded prefill holds: one and the same row (the embedding of
token 0), marked by ``live`` where ``_moe_mlp`` takes it. A tree whose
``_moe_mlp`` takes no ``live`` (before PR 28) is timed as it is: its
sorted form is the static scan of ``N*k/256 + E`` blocks.

The time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions. The
sorted form's result is checked on the device against the dense form's
on the live rows; dead rows have to be zeros where ``live`` is taken.

Exits 1 where the platform is not a TPU: a CPU time is no device time.
One JSON line per measurement, the whole table under
``chiprun_out/moe_form_timing.json`` (``--out``).
"""

from __future__ import annotations

import argparse
import contextlib
import inspect
import json
import os
import statistics
import sys
import tempfile
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace as bm_trace
from dynamo_tpu.models import llama

# (name, E, k, D, I, N...)
SHAPES = [("mixtral", 8, 2, 4096, 14336, (128, 512, 2048)),
          ("qwen3", 128, 8, 2048, 768, (2048,))]
FILLS = (0.13, 0.5, 1.0)
TAKES_LIVE = "live" in inspect.signature(llama._moe_mlp).parameters


def programs(name, E, k, N):
    """(label, jitted fn(h, live, w_router, wg, wu, wd), in_place)."""
    out = []

    def build(label, use_sorted, block=None, in_place=False):
        def fn(h, live, wr, wg, wu, wd):
            kw = {}
            if TAKES_LIVE:
                kw["live"] = live[None]
                if in_place:
                    kw["layer"] = jnp.int32(1)
            with contextlib.ExitStack() as forced:
                forced.enter_context(mock.patch.object(
                    llama, "_moe_use_blocked", lambda *a: use_sorted))
                if block is not None:
                    forced.enter_context(mock.patch.object(
                        llama, "moe_block", lambda *a: block))
                return llama._moe_mlp(h[None], wr, wg, wu, wd, k, **kw)[0]

        fn.__name__ = "%s_n%d_%s" % (name, N, label)
        out.append((fn.__name__, jax.jit(fn), in_place))

    build("dense", False)
    if TAKES_LIVE:
        for block in (128, 256):
            build("sorted_b%d" % block, True, block)
        build("sorted_in_place", True, None, in_place=True)
    else:
        build("sorted", True)
    return out


def time_model(name, E, k, D, I, Ns, reps):
    """One model's weights on the device, every program of every N
    warmed and checked, then all of them under one trace."""
    key = jax.random.split(jax.random.PRNGKey(28), 5)
    shapes = ((E, D, I), (E, D, I), (E, I, D))
    layer1 = tuple(
        (jax.random.normal(kk, shp, jnp.bfloat16)
         * float(shp[-2]) ** -0.5).astype(jnp.bfloat16)
        for kk, shp in zip(key[:3], shapes))
    # [2, E, ...] for the in-place read: layer 1 is the layer timed
    stack = tuple(jnp.stack([w * 0.5, w]) for w in layer1)
    wr = (jax.random.normal(key[3], (D, E), jnp.bfloat16)
          * 2.0 * D ** -0.5).astype(jnp.bfloat16)
    runs, agree = [], True
    for N in Ns:
        rows = jax.random.normal(key[4], (N, D), jnp.bfloat16)
        inputs = {}
        for fill in FILLS:
            live = jnp.arange(N) < max(int(round(fill * N)), 1)
            inputs[fill] = (jnp.where(live[:, None], rows, rows[:1]), live)
        ref = {}
        for label, fn, in_place in programs(name, E, k, N):
            ws = stack if in_place else layer1
            for fill, (h, live) in inputs.items():
                y = np.asarray(jax.block_until_ready(
                    fn(h, live, wr, *ws)), np.float32)
                n_live = int(live.sum())
                if label.endswith("_dense"):
                    ref[fill] = y
                    continue
                err = float(np.abs(y[:n_live] - ref[fill][:n_live]).max())
                scale = float(np.abs(ref[fill][:n_live]).max())
                dead = float(np.abs(y[n_live:]).max()) if n_live < N else 0.0
                ok = err <= 0.02 * scale and (dead == 0.0 or not TAKES_LIVE)
                agree &= ok
                if not ok:
                    print(json.dumps({
                        "differs_from_dense": label, "fill": fill,
                        "max_err": err, "ref_max": scale,
                        "dead_rows_max": dead}))
            runs.append((label, fn, ws, inputs))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for label, fn, ws, inputs in runs:
            for h, live in inputs.values():
                for _ in range(reps):
                    jax.block_until_ready(fn(h, live, wr, *ws))
        jax.profiler.stop_trace()
        planes = bm_trace.load(bm_trace.find_xplane(tmp))
    plane = next(iter(planes.values()))
    table = []
    for label, fn, ws, inputs in runs:
        mine = sorted((s, d) for n, s, d in plane["modules"]
                      if n.startswith("jit_%s(" % label))
        assert len(mine) == reps * len(inputs), (label, len(mine))
        for i, fill in enumerate(inputs):
            part = mine[i * reps:(i + 1) * reps]
            durs = [d for _, d in part]
            t0, t1 = part[0][0], part[-1][0] + part[-1][1]
            ops = {}
            for n, s, d in plane["ops"]:
                kind, shp = bm_trace._op(n)
                if t0 <= s < t1 and not bm_trace.CONTAINER_OP.match(kind):
                    key = "%s_%s" % (kind, shp)
                    ops[key] = ops.get(key, 0.0) + d
            top = sorted(ops.items(), key=lambda kv: -kv[1])[:6]
            row = {"program": label, "live_share": fill, "n": len(durs),
                   "device_ms_median": statistics.median(durs) * 1e3,
                   "device_ms_min": min(durs) * 1e3,
                   "device_ms_max": max(durs) * 1e3,
                   "ops_ms": [[k, v / len(durs) * 1e3] for k, v in top]}
            table.append(row)
            print(json.dumps(row))
    return table, agree


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--out", default="chiprun_out/moe_form_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    table, agree = [], True
    for name, E, k, D, I, Ns in SHAPES:
        rows, ok = time_model(name, E, k, D, I, Ns, opts.reps)
        table += rows
        agree &= ok
    result = {"ok": agree, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind},
              "takes_live": TAKES_LIVE, "reps": opts.reps, "table": table}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": agree, "device": result["device"]}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
