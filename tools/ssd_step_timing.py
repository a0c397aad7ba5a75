"""Device time of one layer-step of the Mamba-2 recurrence at decode (one
token a row from a stored matrix state) at 64 / 4 / 1 rows of cell 8's
shapes (``granite-4.0-h-small.rag-decode``: a state pool of 65 slots x 9
layers x [128, 8192] float32 = 2.28 GiB), in both of models/granite.py's
arms (tools/ssm_step_timing.py's form, for the second kernel):

    chiprun -- python3 tools/ssd_step_timing.py

* ``xla_step``: ``_ssd_step`` + ``dynamic_update_index_in_dim`` on rows
  already gathered, layer by layer (a ``lax.scan`` over the 9 layers,
  ``--steps`` times, as the window unrolls its steps): the recurrence
  itself, without the 2.25 GiB gather and scatter that a window on this
  arm would add at 64 rows (and that does not fit beside the weights);
* ``kernel``: ops/selective_scan.py ``ssd_step`` on the pool, the same
  loops.

Beside each the bytes' floor: the live rows' state read and written
once, and dec, dt x, y, B, C moved once, at the chip's HBM peak
(benchmark/peaks.json). The time is the program's duration on the
device's clock (line ``XLA Modules`` of a profiler trace), median of
``--reps`` executions, over steps x layers. The kernel is checked on the
device against the XLA arm: the rows' states agree to float32 rounding,
a live row with dt = 0 and the drop slot that the padding rows share
keep their state bit for bit. Exits 1 where the platform is not a TPU
or the kernel disagrees. One JSON line per measurement, the table under
``chiprun_out/ssd_step_timing.json``. The builder's tool; the driver
does not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import roofline
from dynamo_tpu.models import granite
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.selective_scan import ssd_step
from tools.paged_attn_timing import _time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite-4.0-h-small.rag-decode"
F32 = jnp.float32


def _case(cfg, slots_total: int, B: int, live: int, seed: int):
    """The pool, the rows' slots and one token's operands: ``live`` rows
    on distinct slots (of two or more, the first has dt = 0: a row frozen
    by a stop), the other B - live on the drop slot with dt = 0. Also the
    slots whose state no program may change."""
    M, N, H, P = (granite.num_mamba_layers(cfg), cfg.mamba_d_state,
                  cfg.mamba_n_heads, cfg.mamba_d_head)
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    slots = np.full(B, slots_total - 1, np.int32)
    rows = rng.permutation(B)[:live]
    slots[rows] = rng.permutation(slots_total - 1)[:live]
    still = rows[:1] if live > 1 else rows[:0]
    dt = np.zeros((B, H), np.float32)
    dt[rows[len(still):]] = rng.uniform(0.05, 1.0, (live - len(still), H))
    dt = jnp.asarray(dt)
    a_neg = -jnp.exp(jax.random.normal(ks[1], (H,), F32))
    pool = jax.random.normal(ks[0], (slots_total, M, N, H * P), F32)
    args = (jnp.asarray(slots),
            jnp.repeat(jnp.exp(dt * a_neg), P, axis=-1),
            jnp.repeat(dt, P, axis=-1)
            * jax.random.normal(ks[2], (B, H * P), F32),
            jax.random.normal(ks[3], (B, N), F32),
            jax.random.normal(ks[4], (B, N), F32))
    return pool, args, [slots_total - 1, *slots[still]]


def _programs(M: int, steps: int, B: int):
    layers = jnp.arange(M, dtype=jnp.int32)

    def loops(carry, layer):
        acc = jnp.zeros((), F32)
        for _ in range(steps):
            carry, ys = lax.scan(layer, carry, layers)
            acc = acc + jnp.sum(ys)
        return carry, acc

    def xla_step(rows, slots, *row):
        def layer(rows, m):
            s, y = granite._ssd_step(
                lax.dynamic_index_in_dim(rows, m, 1, False), *row)
            return lax.dynamic_update_index_in_dim(rows, s, m, 1), jnp.sum(y)
        return loops(rows, layer)

    def kernel(pool, slots, *row):
        def layer(pool, m):
            pool, y = ssd_step(pool, slots, m, *row)
            return pool, jnp.sum(y)
        return loops(pool, layer)

    out = {"xla_step": xla_step, "kernel": kernel}
    for name, fn in out.items():
        fn.__name__ = "ssd_%s_b%d" % (name, B)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--rows", default="64,4,1")
    ap.add_argument("--out", default="chiprun_out/ssd_step_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    with open(os.path.join(ROOT, "benchmark/workloads", CELL + ".json")) as f:
        slots_total = json.load(f)["engine"]["max_batch"] + 1
    cfg = ModelConfig.from_local_path(
        os.path.join(ROOT, "benchmark/configs", CELL.rsplit(".", 1)[0]))
    M, N, C = (granite.num_mamba_layers(cfg), cfg.mamba_d_state,
               cfg.mamba_d_inner)
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    per = opts.steps * M
    agree, table = True, []
    for B in (int(b) for b in opts.rows.split(",")):
        # a full window at 64 rows (a closed loop of 64 clients); below
        # it some rows of the bucket are padding, on the drop slot
        live = B if B in (1, slots_total - 1) else max(1, B * 3 // 4)
        pool, args, kept = _case(cfg, slots_total, B, live, 40 + B)
        slots = args[0]
        before = np.asarray(pool[jnp.asarray(kept)])
        bytes_ = live * (2 * N * C * 4) + B * (3 * C + 2 * N) * 4
        shape = {"B": B, "live": live, "layers": M, "steps": opts.steps,
                 "least_us": roofline.least_seconds(
                     0, bytes_, dev.device_kind)["seconds"] * 1e6}
        want = None
        for name, fn in _programs(M, opts.steps, B).items():
            label = fn.__name__
            fn = jax.jit(fn, donate_argnums=0)
            start = pool[slots] if name == "xla_step" else pool
            row = {**shape, "program": label}
            # the carry is donated: an execution is fed the last one's;
            # the first, untraced, is the one that is checked
            state = [jax.block_until_ready(fn(start, *args)[0])]
            del start
            if name == "xla_step":
                want = np.asarray(state[0])
            else:
                del pool
                got_pool = state[0]
                got = np.asarray(got_pool[slots])
                at = np.asarray(slots) != slots_total - 1
                err = float(np.abs(got[at] - want[at]).max())
                same = bool((np.asarray(got_pool[jnp.asarray(kept)])
                             == before).all())
                ok = err <= 1e-4 * float(np.abs(want).max()) and same
                agree &= ok
                row.update(agrees=ok, max_err=err, still_rows_kept=same)
                del got, got_pool

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
        del args, want
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
