"""Device time of the sampler's candidate selection, one op at a time.

    chiprun -- python3 tools/sampler_op_timing.py

Times ``jax.lax.top_k(x, 64)``, ``engine.sampling.exact_top_k(x, 64)``
and ``jnp.argmax`` on float32 logits of the shapes the benchmark's cells
run, each as one jitted program of its own, and then the whole of
``sample_tokens`` on either selection: alone the plain call compiles to
a ``TopK`` custom call, inside ``sample_tokens`` to a stable sort of the
whole row, so only the second pair says what a decode step pays. Then
the branch of PR 47: ``greedy_tokens`` alone, and ``sample_tokens`` (one
program) on a batch of greedy rows alone, which runs that arm, and on
a batch whose last row alone is sampled, which runs the other. Last,
the draw where it stands in a step, behind a head (a bfloat16
``[B, H] x [H, V]`` matmul whose output fusion takes the temperature's
divide in): ``sample_tokens`` as it is beside the parent's program (the
same body with the sampled arm taken unconditionally, which is what
every batch ran before the branch), each on both batches, tokens
compared: the one reproducible reading of what a batch with a sampled
row pays, which no cell of the benchmark sends. The
time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions; the
host clock is not read. On the way it checks on the device both
selections against a stable sort made on the host (values descending,
equal values by lower index), on random, tied, constant, signed-zero
and mostly ``-inf`` rows; only ``exact_top_k`` has to pass (the plain
call does not, with one row: PERF.md, PR 25), and the greedy arm has to
draw that sort's first, alone and through the branch.

Exits 1 where the platform is not a TPU: a CPU time is no device time.
One JSON line per measurement, the whole table under
``chiprun_out/sampler_op_timing.json``.
"""

from __future__ import annotations

import argparse
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
from dynamo_tpu.engine import sampling

SHAPES = [(64, 151936), (8, 151936), (32, 32000), (4, 32000), (1, 32000),
          (256, 151936), (128, 65536)]
# the plain call's rows (PR 25's table), and the greedy arm's (PR 47:
# the draw of cells 1-3 and 5-9's windows, of cell 7's block window and
# of cell 4's)
PLAIN_SHAPES = set(SHAPES[:5])
GREEDY_SHAPES = {(64, 151936), (256, 151936), (128, 65536), (32, 32000)}
# the draw behind a head: (B, V) -> the hidden size of the cells that
# draw at that shape (cell 7's block window, cell 2's window, cell 4's)
HEAD_SHAPES = {(256, 151936): 2048, (64, 151936): 2048, (128, 65536): 2560}
K = 64


def sampling_rows(B, sampled="half"):
    """Half the rows greedy, half sampled with top-k and top-p; or no
    row sampled; or the last alone."""
    odd = {"half": np.arange(B) % 2, "none": np.zeros(B, np.int64),
           "last": np.arange(B) == B - 1}[sampled].astype(np.int64)
    return (jnp.asarray(0.8 * odd, jnp.float32),
            jnp.asarray(40 * odd, jnp.int32),
            jnp.asarray(1.0 - 0.1 * odd, jnp.float32),
            jnp.arange(B, dtype=jnp.uint32), jnp.zeros((B,), jnp.int32))


def variants(shape):
    """{name: (the row's label, the program's name in the trace, the
    jitted program, its operands after the logits)}"""
    def lax_top_k(x):
        return jax.lax.top_k(x, K)

    def exact_top_k(x):
        return sampling.exact_top_k(x, K)

    def argmax(x):
        return jnp.argmax(x, axis=-1)

    def greedy_tokens(x):
        return sampling.greedy_tokens(x)

    sample = sampling.sample_tokens.__wrapped__

    def sample_tokens_exact(x, *rows):
        return sample(x, *rows)

    def sample_tokens_plain(x, *rows):
        # traced inside the patch: the body as it stood before PR 25
        with mock.patch.object(sampling, "exact_top_k", jax.lax.top_k):
            return sample(x, *rows)

    half = sampling_rows(shape[0])
    fns = [("exact_top_k", exact_top_k, ()),
           ("sample_tokens_exact", sample_tokens_exact, half)]
    if shape in PLAIN_SHAPES:
        fns += [("lax_top_k", lax_top_k, ()),
                ("sample_tokens_plain", sample_tokens_plain, half)]
    if shape in GREEDY_SHAPES:
        # the last two are sample_tokens_exact, the SAME program, on
        # other operands: which arm runs is the batch's to say
        fns += [("argmax", argmax, ()), ("greedy_tokens", greedy_tokens, ()),
                ("sample_tokens_all_greedy", sample_tokens_exact,
                 sampling_rows(shape[0], "none")),
                ("sample_tokens_one_sampled", sample_tokens_exact,
                 sampling_rows(shape[0], "last"))]
    progs = {}

    def prog(fn):
        if fn not in progs:
            # the program's name in the trace: jit_<op>_<B>x<V>
            fn.__name__ = "%s_%dx%d" % (fn.__name__, *shape)
            progs[fn] = jax.jit(fn)
        return fn.__name__, progs[fn]

    return {label: ("%s_%dx%d" % (label, *shape), *prog(fn), rows)
            for label, fn, rows in fns}


def head_variants(shape, hidden, rng):
    """[(the row's label, the program's name in the trace, the jitted
    program, its operands)]: a head and the draw in one program, as the
    change has it and as the parent had it, each on a batch of greedy
    rows and on one whose last row alone is sampled."""
    B, V = shape
    h = jnp.asarray(rng.standard_normal((B, hidden)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((hidden, V)) * 0.05, jnp.bfloat16)
    sample = sampling.sample_tokens.__wrapped__

    def head_draw(h, w, *rows):
        with jax.named_scope("lm_head"):
            logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
        return sample(logits, *rows)

    def head_draw_parent(h, w, *rows):
        # traced inside the patch: no branch, the sampled arm's body for
        # every batch, which is sample_tokens as it stood before PR 47
        with mock.patch.object(jax.lax, "cond",
                               lambda pred, true, false: true()):
            return head_draw(h, w, *rows)

    out = []
    for fn in (head_draw_parent, head_draw):
        label = fn.__name__
        fn.__name__ = "%s_%dx%d" % (label, *shape)
        jitted = jax.jit(fn)
        out += [("%s_%s_%dx%d" % (label, rows, *shape), fn.__name__, jitted,
                 (h, w) + sampling_rows(B, rows))
                for rows in ("none", "last")]
    return out


def stable_top_k(x, k):
    """The contract, on the host: descending in float32's total order
    (0.0 above -0.0), ties by lower index."""
    bits = x.view(np.int32).astype(np.int64)
    rank = bits ^ ((bits >> 63) & 0x7FFFFFFF)
    idx = np.argsort(-rank, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(x, idx, axis=1), idx


def rows(shape, kind, rng):
    B, V = shape
    if kind == "random":
        return rng.standard_normal((B, V)).astype(np.float32)
    if kind == "tied":          # four distinct values: ties at every rank
        return rng.integers(0, 4, (B, V)).astype(np.float32)
    if kind == "constant":
        return np.zeros((B, V), np.float32)
    if kind == "signed_zeros":  # lax.top_k ranks 0.0 above -0.0
        x = np.minimum(rng.standard_normal((B, V)), 0.0).astype(np.float32)
        x[:, rng.choice(V, V // 4, replace=False)] = -0.0
        x[:, rng.choice(V, K // 2, replace=False)] = 0.0
        return x
    x = np.full((B, V), -np.inf, np.float32)     # mostly -inf
    x[:, rng.integers(0, V, 10)] = rng.standard_normal(10)
    return x


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/sampler_op_timing.json")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "platform is %s, not tpu"
                          % dev.platform}))
        return 1
    rng = np.random.default_rng(25)
    table = []
    agree = True
    with tempfile.TemporaryDirectory() as tmp:
        progs = []
        for shape in SHAPES:
            x = jnp.asarray(rows(shape, "random", rng))
            fns = variants(shape)
            for name, traced_as, fn, more in fns.values():
                jax.block_until_ready(fn(x, *more))     # compile, warm
                progs.append((name, traced_as, fn, (x,) + more, shape))
            for kind in ("random", "tied", "constant", "signed_zeros",
                         "mostly_inf"):
                y = rows(shape, kind, rng)
                wv, wi = stable_top_k(y, K)
                for base in ("lax_top_k", "exact_top_k"):
                    if base not in fns:
                        continue
                    name, _, fn, _ = fns[base]
                    gv, gi = fn(jnp.asarray(y))
                    same_v = bool(np.array_equal(wv, np.asarray(gv)))
                    same_i = bool(np.array_equal(wi, np.asarray(gi)))
                    if base == "exact_top_k":
                        agree &= same_v and same_i
                    if not (same_v and same_i):
                        print(json.dumps({
                            "differs_from_stable_sort": name, "rows": kind,
                            "values_equal": same_v, "indices_equal": same_i}))
                if "greedy_tokens" in fns:
                    # the greedy arm is the first of the stable sort,
                    # alone and through the branch
                    for base in ("greedy_tokens", "sample_tokens_all_greedy"):
                        name, _, fn, more = fns[base]
                        same = bool(np.array_equal(
                            wi[:, 0], np.asarray(fn(jnp.asarray(y), *more))))
                        agree &= same
                        if not same:
                            print(json.dumps({"tokens_differ": name,
                                              "rows": kind}))
            for kind in ("random", "tied"):
                y = jnp.asarray(rows(shape, kind, rng))
                run = lambda base: np.asarray(fns[base][2](y, *fns[base][3]))
                pairs = []      # (name, its tokens, what they have to be)
                if "sample_tokens_plain" in fns:
                    pairs.append((fns["sample_tokens_plain"][0],
                                  run("sample_tokens_plain"),
                                  run("sample_tokens_exact")))
                if "greedy_tokens" in fns:
                    # the sampled arm's greedy rows: every row but the last
                    pairs.append((fns["sample_tokens_one_sampled"][0],
                                  run("sample_tokens_one_sampled")[:-1],
                                  run("greedy_tokens")[:-1]))
                for name, got, want in pairs:
                    same = bool(np.array_equal(got, want))
                    agree &= same
                    if not same:
                        print(json.dumps({"tokens_differ": name,
                                          "rows": kind}))
        for shape, hidden in HEAD_SHAPES.items():
            drawn = {}
            for name, traced_as, fn, xs in head_variants(shape, hidden, rng):
                drawn[name] = np.asarray(jax.block_until_ready(fn(*xs)))
                progs.append((name, traced_as, fn, xs, shape))
            for rows_ in ("none", "last"):
                # the same tokens with and without the branch
                got, want = (drawn["%s_%s_%dx%d" % (label, rows_, *shape)]
                             for label in ("head_draw", "head_draw_parent"))
                same = bool(np.array_equal(got, want))
                agree &= same
                if not same:
                    print(json.dumps({
                        "tokens_differ": "head_draw_%s_%dx%d"
                        % (rows_, *shape), "from": "head_draw_parent"}))
        jax.profiler.start_trace(tmp)
        for _, _, fn, xs, _ in progs:
            for _ in range(opts.reps):
                jax.block_until_ready(fn(*xs))
        jax.profiler.stop_trace()
        planes = bm_trace.load(bm_trace.find_xplane(tmp))
    plane = next(iter(planes.values()))
    runs = {}       # a program's executions, in the order they were made
    for name, traced_as, _, _, shape in progs:
        if traced_as not in runs:
            runs[traced_as] = sorted(
                (s, d) for n, s, d in plane["modules"]
                if n.startswith("jit_%s(" % traced_as))
        mine, runs[traced_as] = (runs[traced_as][:opts.reps],
                                 runs[traced_as][opts.reps:])
        durs = [d for _, d in mine]
        t0, t1 = min(s for s, _ in mine), max(s + d for s, d in mine)
        ops = {}
        for n, s, d in plane["ops"]:
            kind, shp = bm_trace._op(n)
            if t0 <= s < t1 and not bm_trace.CONTAINER_OP.match(kind):
                key = "%s_%s" % (kind, shp)
                ops[key] = ops.get(key, 0.0) + d
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:6]
        row = {"op": name, "n": len(durs),
               "device_us_median": statistics.median(durs) * 1e6,
               "device_us_min": min(durs) * 1e6,
               "device_us_max": max(durs) * 1e6,
               "floor_us_one_read": shape[0] * shape[1] * 4 / 819e9 * 1e6,
               "ops_us": [[k, v / len(durs) * 1e6] for k, v in top]}
        table.append(row)
        print(json.dumps(row))
    result = {"ok": agree, "device": {"platform": dev.platform,
                                      "kind": dev.device_kind},
              "k": K, "reps": opts.reps, "table": table}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": agree, "device": result["device"]}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
