"""Device time of one layer's chunked gated delta rule at prefill (T
tokens a row from a carried matrix state) at 1 / 8 rows of cell 10's
shapes (``kimi-linear-48b-a3b.doc-reason``: programs of PB 1 and PB 8 x
T 512, 32 heads of 128 x 128), in both of models/kimi_linear.py's arms
(tools/ssd_step_timing.py's form, for the chunk kernel):

    chiprun -- python3 tools/kda_chunk_timing.py

* ``xla_q<Q>``: ``_kda_chunk`` in plain XLA at chunks of Q tokens (16:
  the configuration's ``kda_chunk_size``, what every prefill ran before
  PR 53 and what runs off the chip);
* ``kernel_q<Q>_c<C>``: ops/kda.py ``kda_chunk`` at chunks of Q tokens
  in sub-blocks of C (``--forms``; the module's constants first).

Beside each the recurrence's floor (benchmark/harness/kda_work.py
``kda_prefill``: its own operations at the bf16 peak or a token's
vectors at the HBM peak, whichever is larger, one layer) and the share
of it. The time is the program's duration on the device's clock (line
``XLA Modules`` of a profiler trace), median of ``--reps`` executions.
Every kernel form is checked on the device against ``xla_q16`` on the
same operands (half the rows enter with a carried state, one row's last
tokens do not count): the state and the outputs agree at the tolerance
two ``Precision.HIGHEST`` forms give each other, which a single-pass
bfloat16 product would miss by orders of magnitude: the control
``kernel_default_precision`` (the module's kernel with its products at
the default precision: single-pass bfloat16) has to read 30 x the bound
or more. Exits 1 where the platform is not a TPU, a form disagrees or
the control agrees. One JSON line per measurement, the table under
``chiprun_out/kda_chunk_timing.json``. The builder's tool; the driver
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

from benchmark.harness import kda_work, roofline
from dynamo_tpu.models import kimi_linear
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import kda
from tools.paged_attn_timing import _time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi-linear-48b-a3b.doc-reason"
F32 = jnp.float32
TOL = 2e-5      # of the largest value: two HIGHEST forms; bf16 reads 1e-2


def _case(B: int, T: int, H: int, d: int, seed: int, beta_scale: float):
    """_kda_chunk's operands as the mixer makes them: unit keys, queries
    of norm d ** -0.5, log decays of a head's A x softplus, beta in (0,
    ``beta_scale``) (2 for a configuration that allows negative
    eigenvalues: the entries of the unit-lower system double); odd rows
    enter with a carried state, the last row's trailing 37 tokens do not
    count."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    f = lambda i, *shape: jax.random.normal(ks[i], shape, F32)
    q = kimi_linear._l2norm(f(0, B, T, H, d)) * d ** -0.5
    k = kimi_linear._l2norm(f(1, B, T, H, d))
    a = jax.random.uniform(ks[2], (H, 1), F32, 1.0, 16.0)
    g = -a * jax.nn.softplus(f(3, B, T, H, d) - 4.0)
    beta = beta_scale * jax.nn.sigmoid(f(4, B, T, H))
    valid = jnp.arange(T)[None, :] < jnp.full((B,), T).at[-1].add(-37)[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    s0 = f(5, B, d, H * d) * (jnp.arange(B) % 2)[:, None, None]
    return (s0, q, k, f(6, B, T, H, d), g, beta), valid


def _single_pass(*args):
    """The control: the module's kernel traced with its products at the
    default precision (single-pass bfloat16)."""
    was = kda._HIGHEST
    kda._HIGHEST = None
    kda.kda_chunk.clear_cache()
    try:
        return kda.kda_chunk.__wrapped__(*args)
    finally:
        kda._HIGHEST = was
        kda.kda_chunk.clear_cache()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--rows", default="1,8")
    ap.add_argument("--forms", default="%dx%d,32x16,16x16,64x8,32x8"
                    % (kda.CHUNK, kda.SUB),
                    help="comma-separated <chunk>x<sub-block> of the kernel")
    ap.add_argument("--xla", default="16,64",
                    help="comma-separated chunk sizes of the XLA arm")
    ap.add_argument("--out", default="chiprun_out/kda_chunk_timing.json")
    ap.add_argument("--cell", default=CELL,
                    help="the cell whose KDA heads and prefill chunk are "
                    "timed (solar-open2-250b.long-reason: 64 heads)")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    with open(os.path.join(ROOT, "benchmark/workloads",
                           opts.cell + ".json")) as f:
        T = json.load(f)["engine"]["prefill_chunk"]
    cfg = ModelConfig.from_local_path(
        os.path.join(ROOT, "benchmark/configs", opts.cell.rsplit(".", 1)[0]))
    H, d = cfg.kda_n_heads, cfg.kda_head_dim
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    agree, table = True, []
    for B in (int(b) for b in opts.rows.split(",")):
        args, valid = _case(B, T, H, d, 53 + B, cfg.kda_beta_scale)
        ops, bytes_ = kda_work.kda_prefill(B * T, heads=H, head_dim=d,
                                           layers=1)
        least = roofline.least_seconds(ops, bytes_, dev.device_kind)
        shape = {"B": B, "T": T, "heads": H, "head_dim": d,
                 "beta_scale": cfg.kda_beta_scale,
                 "least_ms": least["seconds"] * 1e3, "bound": least["bound"]}
        forms = [("xla_q%s" % Q, lambda *a, Q=int(Q):
                  kimi_linear._kda_chunk(*a, Q))
                 for Q in opts.xla.split(",")]
        for form in opts.forms.split(","):
            Q, C = (int(x) for x in form.split("x"))
            forms.append(("kernel_q%d_c%d" % (Q, C), lambda *a, Q=Q, C=C:
                          kda.kda_chunk(*a, chunk=Q, sub=C)))
        forms.append(("kernel_default_precision", _single_pass))
        want = None
        for name, fn in forms:
            label = "kda_%s_b%d" % (name, B)
            fn.__name__ = label
            fn = jax.jit(fn)
            row = {**shape, "program": label}
            try:
                s, o = jax.block_until_ready(fn(*args))
            except Exception as e:  # a form the compiler refuses is a row
                print(json.dumps({**row, "refused": str(e)[:300]}),
                      flush=True)
                continue
            got = (np.asarray(s), np.asarray(o) * np.asarray(valid)[
                ..., None, None])
            if want is None:
                want = got
            else:
                errs = [float(np.abs(x - y).max() / np.abs(y).max())
                        for x, y in zip(got, want)]
                ok = max(errs) <= TOL and all(
                    np.isfinite(x).all() for x in got)
                if name == "kernel_default_precision":
                    row["control_shows"] = max(errs) >= 30 * TOL
                    agree &= row["control_shows"]
                elif name.startswith("kernel"):
                    agree &= ok
                row.update(agrees=ok, state_err=errs[0], out_err=errs[1])
            row.update(_time(label, fn, args, opts.reps, opts_tr))
            row["floor_share"] = 100.0 * shape["least_ms"] \
                / row["device_ms_median"]
            table.append(row)
            print(json.dumps(row), flush=True)
    result = {"ok": agree, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind},
              "reps": opts.reps, "constants": {"CHUNK": kda.CHUNK,
                                               "SUB": kda.SUB,
                                               "TILE": kda.TILE},
              "table": table}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": agree, "device": result["device"]}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
