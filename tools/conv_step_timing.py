"""Device time of one layer-step's causal convolution at decode (one token
a row: the d_conv taps, the SiLU and the tail's advance; models/jamba.py
``_causal_conv`` under ``_stack``'s layer loop) at the tail shapes of the
four cells that run it, against the bytes' floor:

    chiprun -- python3 tools/conv_step_timing.py [--cells <cell>,...]

* ``parent``: the form until PR 54. The rows' tails carried ``[B, M, (d_conv
  - 1) * C]`` (the LAYER second-minor), a layer-step slices its layer out
  along that axis, concatenates ``[tail, x]`` in float32, cuts every row's
  next tail at an index of its own (``_causal_conv``'s chunk form, which
  a chunk of one token took too) and writes the layer back; the pool
  ``[S, M, W]``, gathered and scattered by slot. What the kernel is
  compared with.
* ``step``: the tails carried ``[M, B, (d_conv - 1) * C]`` (layer-major: a
  layer's tails are one contiguous block, rows on the sublanes, channels
  on the lanes), in plain XLA: the layer's block sliced out, the taps
  read where they lie, the next tail a shift and a select, the block
  written back. No program's form; kept here as what the kernel was
  chosen over.
* ``kernel``: the same carried array advanced in place by
  ops/conv_step.py (what a TPU runs).

Each in two programs. ``*_rows``: ``--steps`` steps of a ``lax.scan`` over
the M layers on tails already gathered (what a window's steps pay).
``*_window``: the same behind the gather of the rows' tails from the pool
(``[S, M, W]`` for the parent, ``[M, S, W]`` now) and before their scatter
back: what a window pays in all, charged a layer-step. A layer's input is
the last layer's output (a chain, as in the model); one live row is
frozen (``valid`` false) and the bucket's other rows advance.

The floor: a layer's tails read once and written once, x read and the
result written in float32, at the chip's HBM peak (benchmark/peaks.json).
The time is the program's duration on the device's clock, median of
``--reps`` executions, over steps x layers. The two forms are compared on
the device: the tails bit for bit, the results' sum to float32 rounding
of the reduction. Exits 1 where the platform is not a TPU or the forms
disagree. One JSON line a measurement, the table under
``chiprun_out/conv_step_timing.json``. The builder's tool; the driver
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
from dynamo_tpu.models import jamba
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.registry import get_model_module
from dynamo_tpu.ops.conv_step import conv_tail_step
from tools.paged_attn_timing import _time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("jamba2-3b.reason-decode", "granite-4.0-h-small.rag-decode",
         "kimi-linear-48b-a3b.doc-reason", "solar-open2-250b.long-reason")
F32 = jnp.float32


def _xla_step_conv(mp, x, valid, tail, dc: int):
    """The kernel's arithmetic in plain XLA: tail [B, (dc - 1) * C]."""
    C = x.shape[-1]
    cw, x = mp["conv_w"].astype(F32), x[:, 0]
    taps = [tail[:, k * C:(k + 1) * C].astype(F32)
            for k in range(dc - 1)] + [x]
    xc = jax.nn.silu(0.0 + sum(tap * cw[k] for k, tap in enumerate(taps)))
    tail = jnp.where(valid, jnp.concatenate(
        [tail[:, C:], x.astype(tail.dtype)], axis=1), tail)
    return xc[:, None], tail


def _programs(M: int, dc: int, C: int, steps: int, tag: str):
    """name -> function of (pool or rows, slots, x [B, 1, C], valid [B, 1],
    conv_w [M, dc, C]) -> (pool or rows, the sum of every result), named
    for the trace."""
    layers = jnp.arange(M, dtype=jnp.int32)

    def loops(conv, x, layer):
        acc = jnp.zeros((), F32)
        for _ in range(steps):
            (conv, x), ys = lax.scan(layer, (conv, x), layers)
            acc = acc + jnp.sum(ys)
        return conv, acc

    def parent_rows(conv, slots, x, valid, cw):
        def layer(carry, m):
            conv, x = carry
            xc, tail = jamba._causal_conv(
                {"conv_w": cw[m]}, x, valid,
                lax.dynamic_index_in_dim(conv, m, 1, False), dc)
            conv = lax.dynamic_update_index_in_dim(conv, tail, m, 1)
            return (conv, xc), jnp.sum(xc)
        return loops(conv, x, layer)

    def step_rows(conv, slots, x, valid, cw):
        def layer(carry, m):
            conv, x = carry
            xc, tail = _xla_step_conv(
                {"conv_w": cw[m]}, x, valid,
                lax.dynamic_index_in_dim(conv, m, 0, False), dc)
            return (lax.dynamic_update_index_in_dim(conv, tail, m, 0),
                    xc), jnp.sum(xc)
        return loops(conv, x, layer)

    def kernel_rows(conv, slots, x, valid, cw):
        def layer(carry, m):
            conv, x = carry
            xc, conv = jamba._causal_conv(
                {"conv_w": cw[m]}, x, valid, conv, dc,
                tail_step=lambda tails, *row: conv_tail_step(tails, m, *row))
            return (conv, xc), jnp.sum(xc)
        return loops(conv, x, layer)

    def parent_window(pool, slots, *row):
        conv, acc = parent_rows(pool[slots], slots, *row)
        return pool.at[slots].set(conv), acc

    def step_window(pool, slots, *row):
        conv, acc = step_rows(pool[:, slots], slots, *row)
        return jamba._store_tails(pool, slots, conv), acc

    def kernel_window(pool, slots, *row):
        conv, acc = kernel_rows(pool[:, slots], slots, *row)
        return jamba._store_tails(pool, slots, conv), acc

    out = {"parent_rows": parent_rows, "step_rows": step_rows,
           "kernel_rows": kernel_rows, "parent_window": parent_window,
           "step_window": step_window, "kernel_window": kernel_window}
    for name, fn in out.items():
        fn.__name__ = "conv_%s_%s" % (name, tag)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--out", default="chiprun_out/conv_step_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    agree, table = True, []
    for n, cell in enumerate(opts.cells.split(",")):
        with open(os.path.join(ROOT, "benchmark/workloads",
                               cell + ".json")) as f:
            B = json.load(f)["engine"]["max_batch"]
        cfg = ModelConfig.from_local_path(
            os.path.join(ROOT, "benchmark/configs", cell.rsplit(".", 1)[0]))
        model = get_model_module(cfg)
        M, dc = jamba.num_mamba_layers(cfg), cfg.mamba_d_conv
        S, dtype = B + 1, cfg.jax_dtype
        W = jax.eval_shape(lambda: model.init_state(cfg, S))[1].shape[-1]
        C = W // (dc - 1)
        ks = jax.random.split(jax.random.PRNGKey(55 + n), 3)
        rng = np.random.RandomState(55 + n)
        slots = jnp.asarray(rng.permutation(S - 1)[:B].astype(np.int32))
        valid = np.ones((B, 1), bool)
        valid[rng.randint(B)] = False           # a row frozen by a stop
        row = (slots, jax.random.normal(ks[0], (B, 1, C), F32),
               jnp.asarray(valid),
               jax.random.normal(ks[1], (M, dc, C), F32) * 0.5)
        pool = jax.random.normal(ks[2], (M, S, W), F32).astype(dtype)
        per = opts.steps * M
        bytes_ = B * (2 * W * pool.dtype.itemsize + 2 * C * 4)
        shape = {"cell": cell, "B": B, "layers": M, "channels": C,
                 "d_conv": dc, "steps": opts.steps,
                 "tails_mb": M * B * W * pool.dtype.itemsize / 1e6,
                 "least_us": roofline.least_seconds(
                     0, bytes_, dev.device_kind)["seconds"] * 1e6}
        want = {}
        for name, fn in _programs(M, dc, C, opts.steps,
                                  "m%d_c%d" % (M, C)).items():
            label = fn.__name__
            fn = jax.jit(fn, donate_argnums=0)
            form, kind = name.split("_")
            start = jnp.swapaxes(pool, 0, 1) if form == "parent" else pool
            if kind == "rows":
                start = start[slots] if form == "parent" \
                    else start[:, slots]
            else:
                start = jnp.copy(start)
            # the carry is donated: an execution is fed the last one's;
            # the first, untraced, is the one that is checked
            got, acc = jax.block_until_ready(fn(start, *row))
            del start
            out = np.asarray(jnp.swapaxes(got, 0, 1) if form == "parent"
                             else got)
            r = {**shape, "program": label}
            if form == "parent":
                want[kind] = (out, float(acc))
            else:
                same = bool((out == want[kind][0]).all())
                err = abs(float(acc) - want[kind][1])
                ok = same and err <= 1e-5 * abs(want[kind][1]) + 1e-3
                agree &= ok
                r.update(agrees=ok, tails_identical=same, sum_err=err)
            del out
            state = [got]

            def run(*a, fn=fn, state=state):
                state[0], acc = fn(state[0], *a)
                return acc
            t = _time(label, run, row, opts.reps, opts_tr)
            r.update({k.replace("device_ms", "us_a_layer_step"):
                      v * 1e3 / per for k, v in t.items()})
            r["times_the_floor"] = r["us_a_layer_step_median"] \
                / shape["least_us"]
            table.append(r)
            print(json.dumps(r), flush=True)
            del state[:], run, got
        del pool, row, want
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
