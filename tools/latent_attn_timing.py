"""Device time of one layer's latent (MLA) attention over the pool, at
the shapes of kanana-2-30b-a3b.doc-qa, in each form the program could
take.

    chiprun -- python3 tools/latent_attn_timing.py

Decode (B 64 rows, one token each, 32 heads, contexts ~8.6k in a
bucket of 72 pages of 128; ``--page-size 64`` for 144 of 64; every row
live, and with 34 of the 64 empty): the decode kernel
(``latent_attention_decode_layered``: a grid step a row, the row's pages
copied by the kernel) at ``pages_per_step`` 4 / 8 / 16 (and 32 pages of
64), beside the blocked kernel that was the decode entry until PR 39
(``latent_attention_layered`` with the heads of one token as its block:
every page an operand of a grid step) at its 1,024 tokens a step, and
the XLA arm (``mla._attend_pool_xla``). ``--arms decode`` times these
alone. Prefill (one chunk of T
512 a row over 4k and 8k of cached prefix, PB 1 and PB 4 with one and
all rows live): the kernel over blocks of 512 / 1,024 (token,
head) rows against the XLA arm in the absorbed form, and against the
MATERIALISED form in plain XLA (``k_nope = c . W_uk`` and ``v = c .
W_uv`` made for every cached token of the block, 192-wide keys and
128-wide values per head): what ISSUE 31 left to a measurement. The
constants in ops/paged_attention.py (LATENT_TOKENS_PER_STEP,
LATENT_BLOCK_ROWS), the cell's page size, mla._attend_pool's rule by
shape and its choice of the absorbed form for prefill rest on this table
(PERF.md, PR 31).

The time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions; each
kernel form is checked on the device against the XLA arm. Exits 1 where
the platform is not a TPU. One JSON line per measurement, the table
under ``chiprun_out/latent_attn_timing.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import latent_work, roofline
from benchmark.harness import trace as bm_trace
from dynamo_tpu.models import mla
from dynamo_tpu.ops import paged_attention as pa

H, R, DR, DW, DN, DV = 32, 512, 64, 128, 128, 128
L, PAGES, PS, P = 2, 1536, 128, 72
SCALE = (DN + DR) ** -0.5
BF = jnp.bfloat16


def _materialised(q_nope, q_rope, w_uk, w_uv, c_pool, r_pool, l_idx,
                  page_table, lengths):
    """The pool's part with per-head K and V made from the latents of
    each block (plain XLA, the blockwise loop of mla._attend_pool_xla):
    returns the part in VALUE space, [B, T, H, dv]."""
    B, T = q_nope.shape[:2]
    nb = mla._POOL_BLOCK_TOKENS // PS
    S = nb * PS
    cf = c_pool.reshape(L * PAGES, PS, R)
    rf = r_pool.reshape(L * PAGES, PS, DW)
    n_blocks = (jnp.max(lengths) + S - 1) // S

    def block(j, part):
        acc, m, l = part
        idx = l_idx * PAGES + jax.lax.dynamic_slice_in_dim(
            page_table, j * nb, nb, axis=1)
        c = cf[idx].reshape(B, S, R)
        kr = rf[idx].reshape(B, S, DW)
        k = jnp.einsum("bsr,rhd->bshd", c, w_uk,
                       preferred_element_type=jnp.float32).astype(BF)
        v = jnp.einsum("bsr,rhd->bshd", c, w_uv,
                       preferred_element_type=jnp.float32).astype(BF)
        s = (jnp.einsum("bthd,bshd->bths", q_nope, k,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthd,bsd->bths", q_rope, kr,
                          preferred_element_type=jnp.float32)) * SCALE
        valid = ((j * S + jnp.arange(S))[None, :]
                 < lengths[:, None])[:, None, None, :]
        s = jnp.where(valid, s, pa.NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bths,bshd->bthd", p.astype(BF), v,
            preferred_element_type=jnp.float32)
        return acc, m_new, alpha * l + jnp.sum(p, axis=-1)

    return jax.lax.fori_loop(0, n_blocks, block, (
        jnp.zeros((B, T, H, DV), jnp.float32),
        jnp.full((B, T, H), pa.NEG_INF, jnp.float32),
        jnp.zeros((B, T, H), jnp.float32)))


def cases(key, arms):
    """(label, fn, args, reference label or None, work) per form."""
    ks = jax.random.split(key, 8)
    c_pool = jax.random.normal(ks[0], (L, PAGES, 1, PS, R), BF)
    r_pool = jax.random.normal(ks[1], (L, PAGES, 1, PS, DW), BF)
    w_uk = (jax.random.normal(ks[2], (R, H, DN), BF) * R ** -0.5).astype(BF)
    w_uv = (jax.random.normal(ks[3], (R, H, DV), BF) * R ** -0.5).astype(BF)
    layer = 1
    out = []

    def table_of(B, rng):
        return jnp.asarray(np.stack([
            rng.permutation(PAGES - 1)[:P] + 1 for _ in range(B)]),
            jnp.int32)

    def queries(B, T, k):
        q_nope = jax.random.normal(k, (B, T, H, DN), BF)
        q_rope = jnp.pad(jax.random.normal(k, (B, T, H, DR), BF),
                         [(0, 0)] * 3 + [(0, DW - DR)])
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(BF)
        return q_nope, q_rope, q_lat

    # the pools and the weights are ARGUMENTS of every program: closed
    # over, an array is baked into the HLO as a constant, half a GB a
    # program (that ran the 40 GiB host out of memory in PR 31's first try)
    def xla(ql, qr, t, n, c_pool, r_pool):
        return mla._attend_pool_xla(ql, qr, c_pool, r_pool, layer, t, n,
                                    SCALE)

    # ---- decode: 64 rows of 8,257..8,833 tokens; ``_live30``: 34 of them
    # empty (a bucket of 64 at 30 running rows), scattered over the batch
    rng = np.random.RandomState(31)
    B = 64
    full = rng.randint(8257, 8833, B)
    table = table_of(B, rng)
    _, q_rope, q_lat = queries(B, 1, ks[4])
    empty = rng.permutation(B)[:34]
    decode = (("", full), ("_live30", np.where(
        np.isin(np.arange(B), empty), 0, full))) if "decode" in arms else ()
    for tag, lens in decode:
        ops, bytes_ = latent_work.latent_attention_decode(
            lens[lens > 0].tolist(), num_heads=H, kv_lora_rank=R,
            rope_dim=DR, page_size=PS)
        work = {"least_ms": roofline.least_seconds(
            ops, bytes_, jax.devices()[0].device_kind)["seconds"] * 1e3}
        args = (q_lat, q_rope, table, jnp.asarray(lens, jnp.int32), c_pool,
                r_pool)
        ref = "decode%s_xla" % tag
        out.append((ref, _named(xla, ref), args, None, work))

        def blocked(ql, qr, t, n, c_pool, r_pool):
            a, m, l = pa.latent_attention_layered(
                ql[:, 0], qr[:, 0], c_pool, r_pool, layer, t, n,
                scale=SCALE, name="latent_blocked")
            return a[:, None], m[:, None], l[:, None]
        label = "decode%s_blocked" % tag
        out.append((label, _named(blocked, label), args, ref, work))
        for G in (4, 8, 16) + ((32,) if PS < 128 else ()):
            def kern(ql, qr, t, n, c_pool, r_pool, G=G):
                a, m, l = pa.latent_attention_decode_layered(
                    ql[:, 0], qr[:, 0], c_pool, r_pool, layer, t, n,
                    scale=SCALE, pages_per_step=G)
                return a[:, None], m[:, None], l[:, None]
            label = "decode%s_kernel_g%d" % (tag, G)
            out.append((label, _named(kern, label), args, ref, work))

    # ---- prefill: chunks of T 512 over a cached prefix
    T = 512
    prefill = (("pb1_4k", 1, 1, 4096), ("pb1_8k", 1, 1, 8192),
               ("pb4_1live_8k", 4, 1, 8192),
               ("pb4_4live_8k", 4, 4, 8192)) if "prefill" in arms else ()
    for tag, B, live, ctx in prefill:
        lens = jnp.asarray([ctx] * live + [0] * (B - live), jnp.int32)
        table = table_of(B, rng)
        q_nope, q_rope, q_lat = queries(B, T, ks[5])
        ref = "prefill_%s_xla" % tag
        out.append((ref, _named(xla, ref),
                    (q_lat, q_rope, table, lens, c_pool, r_pool), None, {}))

        def mat(qn, qr, t, n, c_pool, r_pool, w_uk, w_uv):
            return _materialised(qn, qr, w_uk, w_uv, c_pool, r_pool, layer,
                                 t, n)
        label = "prefill_%s_materialised_xla" % tag
        out.append((label, _named(mat, label),
                    (q_nope, q_rope, table, lens, c_pool, r_pool, w_uk,
                     w_uv), None, {}))
        for mb in (512, 1024):
            def kern(ql, qr, t, n, c_pool, r_pool, mb=mb, B=B):
                a, m, l = pa.latent_attention_layered(
                    ql.reshape(B, T * H, R), qr.reshape(B, T * H, DW),
                    c_pool, r_pool, layer, t, n, scale=SCALE,
                    block_rows=mb, name=pa.PREFILL_NAME)
                return (a.reshape(B, T, H, R), m.reshape(B, T, H),
                        l.reshape(B, T, H))
            label = "prefill_%s_kernel_mb%d" % (tag, mb)
            out.append((label, _named(kern, label),
                        (q_lat, q_rope, table, lens, c_pool, r_pool), ref,
                        {}))
    return out


def _named(fn, label):
    """``fn`` jitted as a program of its own, named ``label``: the name
    its executions carry on the trace's ``XLA Modules`` line."""
    def program(*args):
        return fn(*args)
    program.__name__ = label
    return jax.jit(program)


def _normalised(part):
    acc, m, l = (np.asarray(x, np.float32) for x in part)
    return acc / np.maximum(l, 1e-9)[..., None]


def main() -> int:
    global PS, P, PAGES
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--page-size", type=int, default=PS, choices=[64, 128])
    ap.add_argument("--arms", default="decode,prefill",
                    help="comma-separated: decode, prefill")
    ap.add_argument("--out", default="chiprun_out/latent_attn_timing.json")
    opts = ap.parse_args()
    if opts.page_size != PS:
        PS, P, PAGES = opts.page_size, P * PS // opts.page_size, \
            PAGES * PS // opts.page_size
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    refs, agree, table = {}, True, []
    opts_tr = jax.profiler.ProfileOptions()
    opts_tr.python_tracer_level = 0     # device lines only: a small file
    for label, fn, args, ref, work in cases(jax.random.PRNGKey(31),
                                             opts.arms.split(",")):
        try:
            got = jax.block_until_ready(fn(*args))
        except Exception as e:     # a form the compiler refuses is a row
            print(json.dumps({"program": label, "refused": str(e)[:300]}),
                  flush=True)
            continue
        if ref is None:
            if not label.endswith("materialised_xla"):
                refs = {label: _normalised(got)}   # one at a time
        else:
            err = float(np.abs(_normalised(got) - refs[ref]).max())
            scale = float(np.abs(refs[ref]).max())
            ok = err <= 0.02 * scale
            agree &= ok
            if not ok:
                print(json.dumps({"differs_from": ref, "program": label,
                                  "max_err": err, "ref_max": scale}),
                      flush=True)
        del got
        # one short trace a program: its memory and its file stay small
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp, profiler_options=opts_tr)
            for _ in range(opts.reps):
                jax.block_until_ready(fn(*args))
            jax.profiler.stop_trace()
            planes = bm_trace.load(bm_trace.find_xplane(tmp))
        plane = next(iter(planes.values()))
        durs = [d for n, s, d in plane["modules"]
                if n.startswith("jit_%s(" % label)]
        assert len(durs) == opts.reps, (label, len(durs))
        row = {"program": label, "n": len(durs),
               "device_ms_median": statistics.median(durs) * 1e3,
               "device_ms_min": min(durs) * 1e3,
               "device_ms_max": max(durs) * 1e3}
        if work:
            row["roofline_share"] = (100.0 * work["least_ms"]
                                     / row["device_ms_median"])
        table.append(row)
        print(json.dumps(row), flush=True)
    result = {"ok": agree, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind},
              "reps": opts.reps, "page_size": PS, "table": table}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": agree, "device": result["device"]}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
