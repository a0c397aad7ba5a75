"""Device time of one layer's call of the GQA decode kernel
(ops/paged_attention.py paged_attention_decode_layered) at the shapes
cells 1-4 give it, at 1 to 32 pages a chunk and at the module's own
rule, against the XLA gather arm
(llama._paged_attention) and the bytes' floor.

    chiprun -- python3 tools/paged_attn_timing.py

A cell's shape is its workload file's batch and page bucket and its
configuration's heads; its rows' contexts are drawn as its traffic file
draws them (a prompt, plus a uniform share of an answer), and as many
rows are padding (length 0, at random places) as its
``decode_slot_fill_share`` says (ledger, PR 31). Beside the cells: what
else runs the kernel (one row and eight, pages of 128, a one-page
table). ``--also FILE`` times another file's
``paged_attention_decode_layered`` on the same inputs (the parent
commit's, or another form of the kernel under trial).
DECODE_TOKENS_PER_STEP in ops/paged_attention.py rests on this table
(PERF.md, PR 32).

The time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions; every
kernel form is checked on the device against the XLA arm. Exits 1 where
the platform is not a TPU. One JSON line per measurement, the table
under ``chiprun_out/paged_attn_timing.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import random
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import roofline, traffic
from benchmark.harness import trace as bm_trace
from dynamo_tpu.models import llama
from dynamo_tpu.ops import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, LAYER = 2, 1
BF = jnp.bfloat16
# name: (workload, KV heads, group, share of the bucket's rows that are
# live: decode_slot_fill_share of the ledger's PR 31 lines, page size: the
# cells run 64; cell 2's rows over pages of 128 are cell 5's page size)
SHAPES = {
    "cell1": ("mixtral-8x7b.chat-steady", 8, 4, 0.49, 64),
    "cell2": ("qwen3-30b-a3b.decode-heavy", 4, 8, 0.99, 64),
    "cell3": ("mixtral-8x7b.shared-prefix", 8, 4, 0.48, 64),
    "cell4": ("jamba2-3b.reason-decode", 1, 20, 0.99, 64),
    "cell2_ps128": ("qwen3-30b-a3b.decode-heavy", 4, 8, 0.99, 128),
}
# (B, P, KV, group, ps, pages, head dim, contexts): one row (the K = 1
# decode step of run.py --model 8b), eight rows, a one-page table, heads
# of 64 (run.py --model 1b: _decode_kernel_narrow)
OTHERS = {
    "b1_kv8": (1, 64, 8, 4, 64, 768, 128, [3000]),
    "b8_kv8": (8, 64, 8, 4, 64, 768, 128,
               [200, 900, 0, 1500, 64, 0, 4096, 33]),
    "p1_kv4": (64, 1, 4, 8, 64, 1280, 128, [1 + i for i in range(64)]),
    "b8_hd64": (8, 64, 8, 4, 64, 768, 64,
                [200, 900, 0, 1500, 64, 0, 4096, 33]),
}


def _contexts(workload: str, live_share: float, rng: random.Random):
    """(B, P, pool pages, contexts) of a cell: a closed or open loop in
    its steady state holds rows somewhere inside their answers."""
    with open(os.path.join(ROOT, "benchmark/workloads", workload + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic", w["traffic"] + ".json")) as f:
        t = json.load(f)
    B, P = w["engine"]["max_batch"], w["engine"]["page_buckets"][-1]
    ctx = []
    for _ in range(B):
        if rng.random() >= live_share:
            ctx.append(0)
            continue
        prompt = traffic.quantile(t["prompt_len"], rng.random())
        answer = traffic.quantile(t["output_len"], rng.random())
        ctx.append(prompt + int(rng.random() * answer))
    return B, P, w["engine"]["num_pages"], ctx


def _inputs(key, B, P, KV, group, ps, pages, hd, ctx, rng):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, KV * group, hd), BF)
    k_pools = jax.random.normal(ks[1], (L, pages, KV, ps, hd), BF)
    v_pools = jax.random.normal(ks[2], (L, pages, KV, ps, hd), BF)
    need = [-(-n // ps) for n in ctx]
    assert max(need) <= P and sum(need) < pages, (max(need), P, sum(need))
    free = rng.permutation(pages - 1) + 1    # page 0 is the padding page
    table, at = np.zeros((B, P), np.int32), 0
    for b, n in enumerate(need):
        table[b, :n] = free[at:at + n]
        at += n
    return (q, k_pools, v_pools, jnp.asarray(table),
            jnp.asarray(ctx, jnp.int32))


def _time(label, fn, args, reps, opts_tr):
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts_tr)
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        planes = bm_trace.load(bm_trace.find_xplane(tmp))
    plane = next(iter(planes.values()))
    durs = [d for n, s, d in plane["modules"]
            if n.startswith("jit_%s(" % label)]
    assert len(durs) == reps, (label, len(durs))
    return {"device_ms_median": statistics.median(durs) * 1e3,
            "device_ms_min": min(durs) * 1e3, "device_ms_max": max(durs) * 1e3}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--pages-per-step", default="1,2,4,8,16,32,0",
                    help="comma-separated; 0 = the module's own rule")
    ap.add_argument("--shapes", default=",".join([*SHAPES, *OTHERS]))
    ap.add_argument("--also", default=None,
                    help="another file with paged_attention_decode_layered")
    ap.add_argument("--out", default="chiprun_out/paged_attn_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    forms = [("kernel_g%s" % g, pa.paged_attention_decode_layered,
              {"pages_per_step": int(g) or None})
             for g in opts.pages_per_step.split(",")]
    if opts.also:
        spec = importlib.util.spec_from_file_location("also_pa", opts.also)
        also = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(also)
        forms.append(("also", also.paged_attention_decode_layered, {}))
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    agree, table = True, []
    for name in opts.shapes.split(","):
        rng, hd = random.Random(32), 128
        if name in SHAPES:
            workload, KV, group, share, ps = SHAPES[name]
            B, P, pages, ctx = _contexts(workload, share, rng)
            P, pages = P * 64 // ps, pages * 64 // ps
        else:
            B, P, KV, group, ps, pages, hd, ctx = OTHERS[name]
        args = _inputs(jax.random.PRNGKey(32), B, P, KV, group, ps, pages,
                       hd, ctx, np.random.RandomState(32))
        ops, bytes_ = roofline.paged_attention_decode(
            [n for n in ctx if n], num_heads=KV * group, num_kv_heads=KV,
            head_dim=hd, page_size=ps)
        least_ms = roofline.least_seconds(ops, bytes_,
                                          dev.device_kind)["seconds"] * 1e3
        shape = {"shape": name, "B": B, "P": P, "KV": KV, "group": group,
                 "ps": ps, "hd": hd, "rows_live": sum(1 for n in ctx if n),
                 "pages_live": sum(-(-n // ps) for n in ctx),
                 "least_ms": least_ms}

        # the pools are ARGUMENTS (closed over, an array is a constant of
        # the HLO); the XLA arm is handed the layer's pool, already sliced
        def xla(q, k, v, t, n, hd=hd):
            return llama._paged_attention(
                q[:, None], k, v, t, jnp.maximum(n - 1, 0)[:, None],
                hd ** -0.5)[:, 0]
        label = "%s_xla" % name
        xla.__name__ = label
        xla_args = (args[0], args[1][LAYER], args[2][LAYER], *args[3:])
        live = np.asarray(args[4]) > 0
        xla = jax.jit(xla)
        want = np.asarray(jax.block_until_ready(xla(*xla_args)),
                          np.float32)[live]
        row = {**shape, "program": label,
               **_time(label, xla, xla_args, opts.reps, opts_tr)}
        table.append(row)
        print(json.dumps(row), flush=True)
        for form, fn, kw in forms:
            label = "%s_%s" % (name, form)

            def kern(q, k, v, t, n, fn=fn, kw=kw):
                return fn(q, k, v, jnp.int32(LAYER), t, n, **kw)
            kern.__name__ = label
            kern = jax.jit(kern)
            try:
                got = np.asarray(jax.block_until_ready(kern(*args)),
                                 np.float32)
            except Exception as e:  # a form the compiler refuses is a row
                print(json.dumps({**shape, "program": label,
                                  "refused": str(e)[:300]}), flush=True)
                continue
            err = float(np.abs(got[live] - want).max())
            ok = err <= 0.02 * float(np.abs(want).max()) \
                and not got[~live].any()
            agree &= ok
            row = {**shape, "program": label, "agrees": ok, "max_err": err,
                   **_time(label, kern, args, opts.reps, opts_tr)}
            row["roofline_share"] = 100.0 * least_ms / row["device_ms_median"]
            table.append(row)
            print(json.dumps(row), flush=True)
        del args, xla_args
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
