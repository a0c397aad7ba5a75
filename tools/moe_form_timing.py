"""Device time of one layer's routed experts in every form, at the cells'
shapes.

    chiprun -- python3 tools/moe_form_timing.py

Times ``models.llama.moe_experts`` behind a softmax top-k router on one
layer of Mixtral-8x7B's experts (8, top-2, 4,096 x 14,336; cells 1, 3)
at N = 128 / 512 / 2,048 rows, of Qwen3-30B-A3B's (128, top-8, 2,048 x
768; cells 2, 7) at N = 256 (the one-block ``[64, 4]`` forward of cell
7's window) / 2,048, of Kanana-2-30B-A3B's (128, top-6, 2,048 x 768;
cell 5) at N = 256 (its PB 1 x T 256 suffix chunk), of LFM2-24B-A2B's
(64, top-4, 2,048 x 1,536; cell 6) at N = 256 (the PB 1 x T 256 suffix
chunk) / 1,024 / 2,048, of granite-4.0-h-small's (the 36 this chip
holds of 72, top-10, 4,096 x 768; cell 8) at N = 512 / 2,048, and of Kimi-Linear-48B-A3B's
(the 64 held of 256, top-8, 2,304 x 1,024; cell 10) and Solar-Open2-250B's
(the 40 held of 320, top-8, 4,096 x 1,280; cell 11) at N = 128, their
B 128 decode window's rows, with ~13%, ~50% and 100% of the rows live,
in three forms:

- ``dense``: every expert on every row (what ``_moe_use_blocked``
  picks under the chip's ridge, ``llama._MOE_RIDGE_ROWS`` = 240 rows),
  the gate inside the down product, one contraction over (e, i) on
  ``w_down`` [E, I, D] as stored. The arm ALONE, on one layer's slice
  outside any loop: the whole-stack relayout a down product that keeps
  e costs a window (PR 59) belongs to the slice inside the window's loop
  over layers, and no line here shows it for either form; a window is
  judged by ``tools/program_ops.py`` on its trace;
- ``loop``: the sorted dispatch as a ``fori_loop`` of one small XLA
  program a block (``moe_experts_blocked`` off the TPU, and before
  PR 42 on it);
- ``kernel``: the sorted dispatch as one grouped-matmul kernel
  (ops/moe_grouped.py; what a TPU runs since PR 42).

The rule of ``_moe_use_blocked`` / ``moe_block`` rests on this table
(PERF.md, PR 28, PR 42, PR 66). Where the rule stands: the dense form
while its arithmetic hides under one read of the weights, the sorted
one from 240 rows up. At N = 128 the dense form IS one read of the
weights; at N = 256 it is bound by the MXU (Qwen3's and Kanana's 1.92 ms
a layer = 81% of the peak, LFM2's 1.71 = 92%) whatever the rows hold,
and the kernel costs 1.82-1.85 ms with every row live and every expert
touched (1.74 for LFM2: the one shape where it is 0.03 behind) and
11-12 us less for each expert that holds no pair (1.51-1.68 at 13%
live): PERF.md section 6, PR 66. The form is forced from here, by
patching what picks it while the program is traced. The sorted forms read ``w[layer,
expert]`` in place from a ``[2, E, ...]`` stack, as the cells' prefill
programs do, at ``moe_block``'s rows a block and, with ``--blocks 128,
256``, at others. Dead rows are what a padded prefill holds: one and the
same row, marked by ``live``.

The time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions;
``kernel_ms`` is the kernel's own op in it. ``blocks_run`` and
``experts_read_gb`` (the weights of the experts that hold a live pair,
each once) come from the routing, read back from the device;
``read_gbps_program`` / ``read_gbps_kernel`` are those bytes over either
time: against the chip's 819 GB/s, how near a form is to one stream of
its weights. Each sorted form's result is checked against the dense
form's, both computed on the device, on the live rows: ``max_err`` after
rounding both to bfloat16, as a bfloat16 model hands the result on (a
whole step of that type or nothing), ``max_err_f32`` before it; dead
rows have to be zeros.

Exits 1 where the platform is not a TPU: a CPU time is no device time.
One JSON line per measurement, the whole table under
``chiprun_out/moe_form_timing.json`` (``--out``).
"""

from __future__ import annotations

import argparse
import contextlib
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
from dynamo_tpu.ops import moe_grouped

# (name, experts held, router's width, first held, k, D, I, N...)
SHAPES = [("mixtral", 8, 8, None, 2, 4096, 14336, (128, 512, 2048)),
          ("qwen3", 128, 128, None, 8, 2048, 768, (256, 2048)),
          ("kanana", 128, 128, None, 6, 2048, 768, (256,)),
          ("lfm2", 64, 64, None, 4, 2048, 1536, (256, 1024, 2048)),
          ("granite", 36, 72, 18, 10, 4096, 768, (512, 2048)),
          ("kimi", 64, 256, 0, 8, 2304, 1024, (128,)),
          ("solar", 40, 320, 0, 8, 4096, 1280, (128,))]
FILLS = (0.13, 0.5, 1.0)


def route(h, wr, k):
    logits = (h @ wr).astype(jnp.float32)
    weights, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(weights, axis=-1), idx


def programs(name, E, k, D, I, first, width, N, blocks):
    """(label, form, block or None, jitted fn(h, live, wr, wg, wu, wd))."""
    out = []

    def build(form, block=None):
        def fn(h, live, wr, wg, wu, wd):
            weights, idx = route(h, wr, k)
            with contextlib.ExitStack() as forced:
                if form == "loop":
                    forced.enter_context(mock.patch.object(
                        llama, "_moe_kernel_interpret", lambda w: None))
                if block is not None:
                    forced.enter_context(mock.patch.object(
                        llama, "moe_block", lambda *a: block))
                if form == "dense":
                    return llama.moe_experts(
                        h[None], weights[None], idx[None], wg[1], wu[1],
                        wd[1], False, first=first)[0]
                return llama.moe_experts(
                    h[None], weights[None], idx[None], wg, wu, wd, True,
                    live=live[None], layer=jnp.int32(1), first=first,
                    width=width)[0]

        label = "%s_n%d_%s" % (name, N, form)
        if block is not None:
            label += "_b%d" % block
        fn.__name__ = label
        out.append((label, form, block, jax.jit(fn)))

    build("dense")
    own = llama.moe_block(N, k, (E, D, I), width)
    for form in ("loop", "kernel"):
        build(form)
        # the rule's own block again would be the same program under
        # another name, and the compile cache hands back the first
        for block in blocks:
            if block != own:
                build(form, block)
    return out


def as_served(y):
    """A float32 result as a bfloat16 model hands it on."""
    return np.asarray(jnp.asarray(y).astype(jnp.bfloat16), np.float32)


def plan_numbers(idx, live, E, first, block, D, I):
    """blocks run and bytes of the experts that hold a live pair."""
    e = np.asarray(idx)[np.asarray(live)].reshape(-1) - (first or 0)
    counts = np.bincount(e[(e >= 0) & (e < E)], minlength=E)
    return {"block": block,
            "blocks_run": int(np.sum(-(-counts // block))),
            "live_row_share": float(counts.sum()) / max(
                int(np.sum(-(-counts // block))) * block, 1),
            "experts_read_gb": int(np.sum(counts > 0)) * 3 * D * I * 2 / 1e9}


def time_model(name, E, width, first, k, D, I, Ns, reps, blocks):
    """One model's weights on the device, every program of every N
    warmed and checked, then all of them under one trace."""
    key = jax.random.split(jax.random.PRNGKey(28), 5)
    shapes = ((E, D, I), (E, D, I), (E, I, D))
    # [2, E, ...] for the in-place read: layer 1 is the layer timed
    stack = tuple(
        jnp.stack([w * 0.5, w]) for w in (
            (jax.random.normal(kk, shp, jnp.bfloat16)
             * float(shp[-2]) ** -0.5).astype(jnp.bfloat16)
            for kk, shp in zip(key[:3], shapes)))
    wr = (jax.random.normal(key[3], (D, width), jnp.bfloat16)
          * 2.0 * D ** -0.5).astype(jnp.bfloat16)
    runs, agree = [], True
    for N in Ns:
        rows = jax.random.normal(key[4], (N, D), jnp.bfloat16)
        inputs = {}
        for fill in FILLS:
            live = jnp.arange(N) < max(int(round(fill * N)), 1)
            inputs[fill] = (jnp.where(live[:, None], rows, rows[:1]), live)
        ref = {}
        for label, form, block, fn in programs(name, E, k, D, I, first,
                                                  width, N, blocks):
            checked = {}
            for fill, (h, live) in inputs.items():
                y = np.asarray(jax.block_until_ready(
                    fn(h, live, wr, *stack)), np.float32)
                n_live = int(live.sum())
                if form == "dense":
                    ref[fill] = y
                    continue
                err32 = float(np.abs(y[:n_live] - ref[fill][:n_live]).max())
                err = float(np.abs(as_served(y[:n_live])
                                   - as_served(ref[fill][:n_live])).max())
                scale = float(np.abs(ref[fill][:n_live]).max())
                dead = float(np.abs(y[n_live:]).max()) if n_live < N else 0.0
                ok = bool(np.isfinite(y).all() and err <= 0.02 * scale
                          and dead == 0.0)
                agree &= ok
                checked[fill] = {"max_err": err, "max_err_f32": err32,
                                 "ref_max": scale, "dead_rows_max": dead,
                                 "agrees": ok}
                _, idx = route(h, wr, k)
                checked[fill].update(plan_numbers(
                    idx, live, E, first,
                    block or llama.moe_block(N, k, (E, D, I), width), D, I))
            runs.append((label, form, fn, inputs, checked))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for label, form, fn, inputs, _ in runs:
            for h, live in inputs.values():
                for _ in range(reps):
                    jax.block_until_ready(fn(h, live, wr, *stack))
        jax.profiler.stop_trace()
        planes = bm_trace.load(bm_trace.find_xplane(tmp))
    plane = next(iter(planes.values()))
    table = []
    for label, form, fn, inputs, checked in runs:
        mine = sorted((s, d) for n, s, d in plane["modules"]
                      if n.startswith("jit_%s(" % label))
        assert len(mine) == reps * len(inputs), (label, len(mine))
        for i, fill in enumerate(inputs):
            part = mine[i * reps:(i + 1) * reps]
            durs = [d for _, d in part]
            t0, t1 = part[0][0], part[-1][0] + part[-1][1]
            ops, kernel = {}, 0.0
            for n, s, d in plane["ops"]:
                kind, shp = bm_trace._op(n)
                if t0 <= s < t1 and not bm_trace.CONTAINER_OP.match(kind):
                    op = "%s_%s" % (kind, shp)
                    ops[op] = ops.get(op, 0.0) + d
                    if kind == moe_grouped.NAME:
                        kernel += d
            top = sorted(ops.items(), key=lambda kv: -kv[1])[:6]
            ms = statistics.median(durs) * 1e3
            row = {"program": label, "live_share": fill, "n": len(durs),
                   "device_ms_median": ms,
                   "device_ms_min": min(durs) * 1e3,
                   "device_ms_max": max(durs) * 1e3,
                   **checked.get(fill, {})}
            if form != "dense":
                row["read_gbps_program"] = row["experts_read_gb"] / ms * 1e3
            if form == "kernel":
                row["kernel_ms"] = kernel / len(durs) * 1e3
                row["read_gbps_kernel"] = (row["experts_read_gb"]
                                           / row["kernel_ms"] * 1e3)
            row["ops_ms"] = [[op, v / len(durs) * 1e3] for op, v in top]
            table.append(row)
            print(json.dumps(row))
    return table, agree


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--models", default=",".join(s[0] for s in SHAPES))
    ap.add_argument("--blocks", default="",
                    help="rows a block to time besides moe_block's own")
    ap.add_argument("--out", default="chiprun_out/moe_form_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    blocks = [int(b) for b in opts.blocks.split(",") if b]
    table, agree = [], True
    for name, E, width, first, k, D, I, Ns in SHAPES:
        if name not in opts.models.split(","):
            continue
        rows, ok = time_model(name, E, width, first, k, D, I, Ns, opts.reps,
                              blocks)
        table += rows
        agree &= ok
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
