"""Device time of one layer's call of the GQA attention kernels
(ops/paged_attention.py): the decode kernel
(paged_attention_decode_layered) at the shapes cells 1-4 give it, at 1
to 32 pages a chunk and at the module's own rule, and the prefill kernel
(paged_attention_prefill) at the prefill shapes of cells 1-4 and 6, at
several blocks of queries and pages a chunk and at its own rule, each
against the XLA gather arm (llama._paged_attention) and the least time
the chip could take.

    chiprun -- python3 tools/paged_attn_timing.py [--arms decode,prefill]

A cell's shape is its workload file's batch and page bucket and its
configuration's heads; its rows' contexts are drawn as its traffic file
draws them (a prompt, plus a uniform share of an answer), and as many
rows are padding (length 0, at random places) as its
``decode_slot_fill_share`` says (ledger, PR 31). Beside the cells: what
else runs the kernel (one row and eight, pages of 128, a one-page
table). ``--also FILE`` times another file's
``paged_attention_decode_layered`` / ``paged_attention_prefill`` on the
same inputs (the parent commit's, or another form of the kernel under
trial). DECODE_TOKENS_PER_STEP and the prefill kernel's size rule in
ops/paged_attention.py rest on these tables (PERF.md, PR 32 and 34).

The prefill arm takes a cell's largest prefill batch bucket, its length
bucket and page bucket; as many rows are live as PREFILL says (by the
cell's ``prefill_slot_fill_share``, ledger PR 33), each a prompt drawn
as the traffic file draws it: the chunk after the shared prefix where
the traffic has one (a prefix hit hands over whole pages), else one of
the prompt's chunks of ``prefill_chunk`` tokens; the other rows are
padding (positions -1). The chunk's own K/V is in the pool, as
llama.forward has it when it calls _attention.

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

# name: (workload, KV heads, group, live rows of the batch bucket); cell 6
# as models/lfm2.py packs it: two KV heads of 64 to a 128-lane row
PREFILL = {
    "cell1": ("mixtral-8x7b.chat-steady", 8, 4, 2),
    "cell2": ("qwen3-30b-a3b.decode-heavy", 4, 8, 2),
    "cell3": ("mixtral-8x7b.shared-prefix", 8, 4, 2),
    "cell4": ("jamba2-3b.reason-decode", 1, 20, 4),
    "cell6": ("lfm2-24b-a2b.agent-loop", 4, 8, 3),
    "cell1_full": ("mixtral-8x7b.chat-steady", 8, 4, 4),
    "cell2_full": ("qwen3-30b-a3b.decode-heavy", 4, 8, 8),
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


def _chunks(workload: str, live: int, rng: random.Random):
    """(PB, T, P, pool pages, page size, [(start, count)] a row) of a
    cell's prefill program: ``live`` rows hold a chunk, the rest none."""
    with open(os.path.join(ROOT, "benchmark/workloads", workload + ".json")) as f:
        w = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic", w["traffic"] + ".json")) as f:
        t = json.load(f)
    e = w["engine"]
    ps, buckets = e.get("page_size", 64), e["prefill_buckets"]
    chunk = e.get("prefill_chunk", buckets[-1])
    PB = min(b for b in e["batch_buckets"] if b >= e["max_prefill_batch"])
    rows = []
    for _ in range(live):
        prompt = traffic.quantile(t["prompt_len"], rng.random())
        if "shared_prefix" in t:    # a hit: whole pages, a token is left
            start = min(t["shared_prefix"]["chars"], prompt - 1) // ps * ps
        else:
            start = chunk * rng.randrange(-(-prompt // chunk))
        rows.append((start, min(prompt - start, chunk)))
    T = min(b for b in buckets if b >= max(c for _, c in rows))
    rows += [(0, 0)] * (PB - live)
    rng.shuffle(rows)
    return PB, T, e["page_buckets"][-1], e["num_pages"], ps, rows


def _prefill_inputs(key, T, P, KV, group, ps, pages, hd, rows, rng):
    ks = jax.random.split(key, 3)
    B = len(rows)
    q = jax.random.normal(ks[0], (B, T, KV * group, hd), BF)
    k_pages = jax.random.normal(ks[1], (pages, KV, ps, hd), BF)
    v_pages = jax.random.normal(ks[2], (pages, KV, ps, hd), BF)
    need = [-(-(s + c) // ps) if c else 0 for s, c in rows]
    assert max(need) <= P and sum(need) < pages, (max(need), P, sum(need))
    free = rng.permutation(pages - 1) + 1    # page 0 is the padding page
    table, at = np.zeros((B, P), np.int32), 0
    pos = np.full((B, T), -1, np.int32)
    for b, (n, (s, c)) in enumerate(zip(need, rows)):
        table[b, :n] = free[at:at + n]
        at += n
        pos[b, :c] = s + np.arange(c)
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(pos)


def _prefill_least_ms(rows, H, KV, ps, hd, kind):
    """The least time for the live rows' attention: two products over
    the positions each query sees (the chip's bf16 peak) against the
    rows' pages of K and V read once and q and the output moved once
    (its HBM peak)."""
    ops = sum(4 * H * hd * sum(range(s + 1, s + c + 1)) for s, c in rows)
    bytes_ = sum(2 * (2 * -(-(s + c) // ps) * ps * KV * hd + 2 * c * H * hd)
                 for s, c in rows if c)
    return roofline.least_seconds(ops, bytes_, kind)["seconds"] * 1e3


def _prefill_arm(opts, dev, opts_tr, also):
    """One table row a (shape, form): the XLA arm, the kernel at its own
    rule and at --prefill-sizes, ``--also``'s kernel."""
    forms = [("kernel_%s" % z, pa.paged_attention_prefill,
              dict(zip(("block_tokens", "pages_per_step"),
                       (int(x) or None for x in z.split("x")))))
             for z in opts.prefill_sizes.split(",")]
    if also:
        forms.append(("also", also.paged_attention_prefill, {}))
    agree, table = True, []
    for name in opts.prefill_shapes.split(","):
        workload, KV, group, live = PREFILL[name]
        PB, T, P, pages, ps, rows = _chunks(workload, live, random.Random(34))
        hd = 128
        args = _prefill_inputs(jax.random.PRNGKey(34), T, P, KV, group, ps,
                               pages, hd, rows, np.random.RandomState(34))
        shape = {"arm": "prefill", "shape": name, "B": PB, "T": T, "P": P,
                 "KV": KV, "group": group, "ps": ps, "hd": hd, "rows": rows,
                 "least_ms": _prefill_least_ms(rows, KV * group, KV, ps, hd,
                                               dev.device_kind)}
        live_q = np.asarray(args[4]) >= 0

        def xla(q, k, v, t, p, hd=hd):
            return llama._paged_attention(q, k, v, t, p, hd ** -0.5)
        label = "pre_%s_xla" % name
        xla.__name__ = label
        xla = jax.jit(xla)
        want = np.asarray(jax.block_until_ready(xla(*args)),
                          np.float32)[live_q]
        row = {**shape, "program": label,
               **_time(label, xla, args, opts.reps, opts_tr)}
        table.append(row)
        print(json.dumps(row), flush=True)
        T, group = shape["T"], shape["group"]
        fit = [f for f in forms if not f[2].get("block_tokens")
               or not (T % f[2]["block_tokens"]
                       or f[2]["block_tokens"] * group % 16)]
        ok, rows = _time_forms(
            shape, "pre_" + name, fit,
            lambda fn, kw: lambda q, k, v, t, p: fn(q, k, v, t, p, **kw),
            args, want, live_q, opts, opts_tr)
        agree &= ok
        table += rows
        del args
    return agree, table


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


def _time_forms(shape, name, forms, call, args, want, live, opts, opts_tr):
    """One table row a kernel form: ``call(fn, kw)`` is the function of
    ``args`` to jit; checked on the device against the XLA arm's ``want``
    at the ``live`` entries (zeros elsewhere), then timed. Returns (all
    agree, rows)."""
    agree, rows = True, []
    for form, fn, kw in forms:
        label = "%s_%s" % (name, form)
        kern = call(fn, kw)
        kern.__name__ = label
        kern = jax.jit(kern)
        try:
            got = np.asarray(jax.block_until_ready(kern(*args)), np.float32)
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
        row["roofline_share"] = 100.0 * shape["least_ms"] \
            / row["device_ms_median"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return agree, rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--pages-per-step", default="1,2,4,8,16,32,0",
                    help="comma-separated; 0 = the module's own rule")
    ap.add_argument("--shapes", default=",".join([*SHAPES, *OTHERS]))
    ap.add_argument("--arms", default="decode,prefill")
    ap.add_argument("--prefill-shapes", default=",".join(PREFILL))
    ap.add_argument("--prefill-sizes",
                    default="0x0,32x8,64x8,128x8,256x8,128x4,128x16",
                    help="comma-separated <tokens a block>x<pages a "
                         "chunk>; 0 = the module's own rule")
    ap.add_argument("--also", default=None,
                    help="another file with paged_attention_decode_layered "
                         "and paged_attention_prefill")
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
    also = None
    if opts.also:
        spec = importlib.util.spec_from_file_location("also_pa", opts.also)
        also = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(also)
        forms.append(("also", also.paged_attention_decode_layered, {}))
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    agree, table = True, []
    arms = opts.arms.split(",")
    if "prefill" in arms:
        agree, table = _prefill_arm(opts, dev, opts_tr, also)
    for name in opts.shapes.split(",") if "decode" in arms else ():
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
        ok, rows = _time_forms(
            shape, name, forms,
            lambda fn, kw: lambda q, k, v, t, n: fn(
                q, k, v, jnp.int32(LAYER), t, n, **kw),
            args, want, live, opts, opts_tr)
        agree &= ok
        table += rows
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
