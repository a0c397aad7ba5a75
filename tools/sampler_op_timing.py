"""Device time of the sampler's candidate selection, one op at a time.

    chiprun -- python3 tools/sampler_op_timing.py

Times ``jax.lax.top_k(x, 64)``, ``engine.sampling.exact_top_k(x, 64)``
and ``jnp.argmax`` on float32 logits of the shapes the benchmark's cells
run, each as one jitted program of its own, and then the whole of
``sample_tokens`` on either selection: alone the plain call compiles to
a ``TopK`` custom call, inside ``sample_tokens`` to a stable sort of the
whole row, so only the second pair says what a decode step pays. The
time is the program's duration on the device's clock (line ``XLA
Modules`` of a profiler trace), median of ``--reps`` executions; the
host clock is not read. On the way it checks on the device both
selections against a stable sort made on the host (values descending,
equal values by lower index), on random, tied, constant, signed-zero
and mostly ``-inf`` rows; only ``exact_top_k`` has to pass (the plain
call does not, with one row: PERF.md, PR 25).

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

SHAPES = [(64, 151936), (8, 151936), (32, 32000), (4, 32000), (1, 32000)]
ARGMAX_SHAPES = {(64, 151936), (32, 32000)}
K = 64


def variants(shape):
    def lax_top_k(x):
        return jax.lax.top_k(x, K)

    def exact_top_k(x):
        return sampling.exact_top_k(x, K)

    def argmax(x):
        return jnp.argmax(x, axis=-1)

    sample = sampling.sample_tokens.__wrapped__

    def sample_tokens_exact(x, *rows):
        return sample(x, *rows)

    def sample_tokens_plain(x, *rows):
        # traced inside the patch: the body as it stood before PR 25
        with mock.patch.object(sampling, "exact_top_k", jax.lax.top_k):
            return sample(x, *rows)

    fns = [lax_top_k, exact_top_k, sample_tokens_plain, sample_tokens_exact]
    if shape in ARGMAX_SHAPES:
        fns.append(argmax)
    out = []
    for fn in fns:
        # the program's name in the trace: jit_<op>_<B>x<V>
        fn.__name__ = "%s_%dx%d" % (fn.__name__, *shape)
        out.append((fn.__name__, jax.jit(fn)))
    return out


def sampling_rows(B):
    """Half the rows greedy, half sampled with top-k and top-p."""
    odd = np.arange(B) % 2
    return (jnp.asarray(0.8 * odd, jnp.float32),
            jnp.asarray(40 * odd, jnp.int32),
            jnp.asarray(1.0 - 0.1 * odd, jnp.float32),
            jnp.arange(B, dtype=jnp.uint32), jnp.zeros((B,), jnp.int32))


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
            for name, fn in fns:
                args = (x,) + (sampling_rows(shape[0])
                               if name.startswith("sample_tokens") else ())
                jax.block_until_ready(fn(*args))        # compile, warm
                progs.append((name, fn, args))
            for kind in ("random", "tied", "constant", "signed_zeros",
                         "mostly_inf"):
                y = rows(shape, kind, rng)
                wv, wi = stable_top_k(y, K)
                for name, fn in fns[:2]:
                    gv, gi = fn(jnp.asarray(y))
                    same_v = bool(np.array_equal(wv, np.asarray(gv)))
                    same_i = bool(np.array_equal(wi, np.asarray(gi)))
                    if name.startswith("exact_top_k"):
                        agree &= same_v and same_i
                    if not (same_v and same_i):
                        print(json.dumps({
                            "differs_from_stable_sort": name, "rows": kind,
                            "values_equal": same_v, "indices_equal": same_i}))
            for kind in ("random", "tied"):
                y = (jnp.asarray(rows(shape, kind, rng)),
                     ) + sampling_rows(shape[0])
                same = bool(np.array_equal(np.asarray(fns[2][1](*y)),
                                           np.asarray(fns[3][1](*y))))
                agree &= same
                if not same:
                    print(json.dumps({"tokens_differ": fns[3][0],
                                      "rows": kind}))
        jax.profiler.start_trace(tmp)
        for name, fn, xs in progs:
            for _ in range(opts.reps):
                jax.block_until_ready(fn(*xs))
        jax.profiler.stop_trace()
        planes = bm_trace.load(bm_trace.find_xplane(tmp))
    plane = next(iter(planes.values()))
    for name, _, xs in progs:
        x = xs[0]
        mine = [(s, d) for n, s, d in plane["modules"]
                if n.startswith("jit_%s(" % name)]
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
               "floor_us_one_read": x.size * 4 / 819e9 * 1e6,
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
