"""Device time of one layer-step of Jamba's selective scan at decode (one
token a row from a stored state) at 1 / 8 / 128 rows of cell 4's shapes
(``jamba2-3b.reason-decode``: a state pool of 129 slots x 26 layers x
[16, 5120] float32 = 1.10 GB), in both of models/jamba.py's arms:

    chiprun -- python3 tools/ssm_step_timing.py [--rows-per-step 8,16,32]

* ``xla``: the rows gathered out of the pool, ``_ssm_step`` +
  ``dynamic_update_index_in_dim`` on the gathered rows layer by layer
  (a ``lax.scan`` over the 26 layers, ``--steps`` times, as the window
  unrolls its steps), the rows scattered back; charged a layer-step as a
  window charges them: the whole program over steps x layers.
  ``xla_step`` is the same loop on rows already gathered (no gather, no
  scatter): what is left is the recurrence itself.
* ``kernel_g<G>``: ops/selective_scan.py on the pool, G rows a grid
  step (``kernel_g0``: the module's own rule), the same loops.

Beside each the bytes' floor: the rows' state read and written once, and
dt, x, y, B, C moved once, at the chip's HBM peak
(benchmark/peaks.json). The time is the program's duration on the
device's clock (line ``XLA Modules`` of a profiler trace), median of
``--reps`` executions, over steps x layers. Every kernel form is checked
on the device against the XLA arm: the pools agree to float32 rounding,
a live row with dt = 0 and the drop slot that the padding rows share
keep their state bit for bit. ``lower_s`` is the wall time of tracing
and lowering the program (what a start pays before the compile cache
answers). ROWS_PER_STEP in ops/selective_scan.py rests on this table
(PERF.md, PR 36). Exits 1 where the platform is not a TPU or a form
disagrees. One JSON line per measurement, the table under
``chiprun_out/ssm_step_timing.json``. The builder's tool; the driver
does not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import roofline
from dynamo_tpu.models import jamba
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.selective_scan import selective_scan_step
from tools.paged_attn_timing import _time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "jamba2-3b.reason-decode"
F32 = jnp.float32


def _case(cfg, slots_total: int, B: int, live: int, seed: int):
    """The pool, the rows' slots and one token's operands: ``live`` rows
    on distinct slots (of two or more, the first has dt = 0: a row frozen
    by a stop), the other B - live on the drop slot with dt = 0. Also the
    slots whose state no program may change."""
    M, N, di = (jamba.num_mamba_layers(cfg), cfg.mamba_d_state,
                cfg.mamba_d_inner)
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    slots = np.full(B, slots_total - 1, np.int32)
    rows = rng.permutation(B)[:live]
    slots[rows] = rng.permutation(slots_total - 1)[:live]
    still = rows[:1] if live > 1 else rows[:0]
    dt = np.zeros((B, di), np.float32)
    dt[rows[len(still):]] = rng.uniform(1e-3, 1e-1,
                                        (live - len(still), di))
    pool = jax.random.normal(ks[0], (slots_total, M, N, di), F32)
    args = (jnp.asarray(slots), jnp.asarray(dt),
            jax.random.normal(ks[1], (B, di), F32),
            jax.random.normal(ks[2], (B, N), F32),
            jax.random.normal(ks[3], (B, N), F32),
            -jnp.exp(jax.random.normal(ks[4], (N, di), F32)))
    return pool, args, [slots_total - 1, *slots[still]]


def _programs(M: int, steps: int, forms, B: int):
    """name -> function of (pool, or rows for ``xla_step``; slots, dt, x,
    b, c, a) -> (pool or rows, a sum of every y), named for the trace."""
    layers = jnp.arange(M, dtype=jnp.int32)

    def loops(carry, layer):
        acc = jnp.zeros((), F32)
        for _ in range(steps):
            carry, ys = lax.scan(layer, carry, layers)
            acc = acc + jnp.sum(ys)
        return carry, acc

    def on_rows(rows, dt, x, b, c, a):
        def layer(rows, m):
            s, y = jamba._ssm_step(
                lax.dynamic_index_in_dim(rows, m, 1, False), dt, x, b, c, a)
            return lax.dynamic_update_index_in_dim(rows, s, m, 1), jnp.sum(y)
        return loops(rows, layer)

    def xla(pool, slots, *row):
        rows, acc = on_rows(pool[slots], *row)
        return pool.at[slots].set(rows), acc

    def xla_step(rows, slots, *row):
        return on_rows(rows, *row)

    out = {"xla": xla, "xla_step": xla_step}
    for G in forms:
        if 0 < B <= G:
            continue            # one group whatever G: kernel_g0's form
        def kernel(pool, slots, *row, G=G):
            def layer(pool, m):
                pool, y = selective_scan_step(pool, slots, m, *row,
                                              rows_per_step=G or None)
                return pool, jnp.sum(y)
            return loops(pool, layer)
        out["kernel_g%d" % G] = kernel
    for name, fn in out.items():
        fn.__name__ = "ssm_%s_b%d" % (name, B)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--rows", default="128,8,1")
    ap.add_argument("--rows-per-step", default="0,8,16,32",
                    help="comma-separated; 0 = the module's own rule")
    ap.add_argument("--out", default="chiprun_out/ssm_step_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    with open(os.path.join(ROOT, "benchmark/workloads", CELL + ".json")) as f:
        slots_total = json.load(f)["engine"]["max_batch"] + 1
    cfg = ModelConfig.from_local_path(
        os.path.join(ROOT, "benchmark/configs", CELL.split(".")[0]))
    M, N, di = (jamba.num_mamba_layers(cfg), cfg.mamba_d_state,
                cfg.mamba_d_inner)
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    forms = [int(g) for g in opts.rows_per_step.split(",")]
    per = opts.steps * M
    agree, table = True, []
    for B in (int(b) for b in opts.rows.split(",")):
        # a full window at 128 rows (cell 4's fill is 99%); below it some
        # rows of the bucket are padding, on the drop slot
        live = B if B in (1, slots_total - 1) else max(1, B * 5 // 8)
        pool, args, kept = _case(cfg, slots_total, B, live, 36 + B)
        before = np.asarray(pool[jnp.asarray(kept)])
        bytes_ = live * (2 * N * di * 4) + B * (3 * di + 2 * N) * 4
        shape = {"B": B, "live": live, "layers": M, "steps": opts.steps,
                 "least_us": roofline.least_seconds(
                     0, bytes_, dev.device_kind)["seconds"] * 1e6}
        want = None
        for name, fn in _programs(M, opts.steps, forms, B).items():
            label = fn.__name__
            fn = jax.jit(fn, donate_argnums=0)
            start = pool[args[0]] if name == "xla_step" else jnp.copy(pool)
            t0 = time.perf_counter()
            fn.lower(start, *args)
            row = {**shape, "program": label,
                   "lower_s": time.perf_counter() - t0}
            # the carry is donated: an execution is fed the last one's;
            # the first, untraced, is the one that is checked
            state = [jax.block_until_ready(fn(start, *args)[0])]
            del start
            if name == "xla":
                want = np.asarray(state[0])
            elif name != "xla_step":
                got = np.asarray(state[0])
                err = float(np.abs(got - want).max())
                same = bool((got[kept] == before).all())
                ok = err <= 1e-4 * float(np.abs(want).max()) and same
                agree &= ok
                row.update(agrees=ok, max_err=err, still_rows_kept=same)
                del got

            def run(*a, fn=fn, state=state):
                state[0], acc = fn(state[0], *a)
                return acc
            t = _time(label, run, args, opts.reps, opts_tr)
            row.update({k.replace("device_ms", "us_a_layer_step"):
                        v * 1e3 / per for k, v in t.items()})
            row["roofline_share"] = 100.0 * shape["least_us"] \
                / row["us_a_layer_step_median"]
            table.append(row)
            print(json.dumps(row), flush=True)
            del state[:], run
        del pool, args, want
    result = {"ok": agree, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind},
              "reps": opts.reps, "table": table}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": agree, "device": result["device"]}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
