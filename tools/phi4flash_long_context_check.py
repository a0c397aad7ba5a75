"""The engine of ``phi-4-mini-flash-reasoning.long-think`` against its plain
reference at the harness's own agreement lengths AND at a length where its
mechanisms bite, beside controls that compute ONE thing wrong, on the chip.

    chiprun --timeout 3000 -- python3 tools/phi4flash_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree) is
fixed at 96-token prompts + 8 greedy steps: a window of 512 never bites
there, no page of the window layers is given back and one prefill chunk
does it all. This builds the cell's engine exactly as benchmark/run.py
does (serve.build: the cell's engine data, weights from --seed), one
engine after the other, and asks it through ``engine.generate`` with
top-20 logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            attention kernels at the paired head size on both pools, the
            scan step and the conv tails in the state pool).
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    long    a prompt of ``--prompt`` (4,096) tokens prefilled in the
            cell's ``prefill_chunk``s of 512 (eight programs: the Mamba
            state carried through the slot, the window layers' pages
            written and given back, the full layer's K/V scattered, the
            cross half on each chunk's last position), then 1 +
            ``--steps`` (32) greedy tokens through the fused window over
            both pools and the state pool. Both have to pass.
    Then the SAME outputs judged against the reference with one fault
    (benchmark/configs/phi-4-mini-flash-reasoning/reference.py FAULTS):
    short/a2_from_k1      the second softmax over the first key of the
            pair, under the cell's own rule: has to FAIL (the cell's
            ``correct`` sees the differential form).
    long/window_ignored   the window layers see everything: has to FAIL.
    long/lam_fixed        lam = lam0: has to FAIL.
  state-8bit  (the first ``--control-seeds`` seeds) the same engine with
            every scan state and conv tail a Mamba mixer hands back
            rounded to 8-bit floats (5 exponent bits, 2 of mantissa)
            where models/phi4flash.py makes them, on the XLA arm
            (``DYN_DISABLE_PALLAS``: the rows' state is gathered, so the
            rounding has one place).
    short, long   have to FAIL.

``short`` is judged by the benchmark's one rule, ``benchmark/reference.py
judge`` (median of the per-position max |d logprob| over the engine's
top-20 <= 0.1, none over 2.5; nothing is widened here). ``long`` is set
against the configuration's reference (its full forward over prompt + the
engine's tokens, teacher-forced, every layer at every position, the last
1 + steps positions projected) under the same rule with a median limit of
its own, LONG_ATOL (about.json's ``weight_scales_why`` and PERF.md,
Findings PR 63, have the readings it lies between). Each line also
carries ``lam_gap_min``: the least |lam - lam0| over the 16 attending
layers at that seed's weights.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or one that has to fail passes, and where
the platform is not a TPU (``--cpu`` lets the plumbing be tried at a tiny
size with ``--root`` a copy of the benchmark that has such a cell).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import random
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# median limit of long. Between its readings (my chip runs, PR 63, twelve
# seeds at the configuration's weight scales): sound 0.0137-0.0171; the
# window ignored 0.434-0.714, lam = lam0 0.386-0.884, the state in 8-bit
# floats 0.501-0.612: 2.9 x the largest of the one, an eighth of the
# smallest of the others
LONG_ATOL = 0.05
BITS = (5, 2)       # the control's floats: exponent bits, mantissa bits


@contextlib.contextmanager
def rounded_state():
    """models/phi4flash.py with every scan state and conv tail its Mamba
    mixer returns rounded to 8-bit floats (``lax.reduce_precision``: a
    pair of converts is elided on the TPU), on the XLA arm, for the
    programs traced inside."""
    from jax import lax

    from dynamo_tpu.models import phi4flash

    sound = phi4flash._mamba

    def mamba(*args, **kw):
        out, s, tail, *y = sound(*args, **kw)
        return (out, lax.reduce_precision(s, *BITS),
                lax.reduce_precision(tail, *BITS), *y)

    os.environ["DYN_DISABLE_PALLAS"] = "1"      # read by runtime/config.py
    phi4flash._mamba = mamba
    try:
        yield
    finally:
        phi4flash._mamba = sound
        del os.environ["DYN_DISABLE_PALLAS"]


def lam_gap_min(params) -> float:
    """The least |lam - lam0| over the attending layers."""
    import jax.numpy as jnp

    f32 = jnp.float32
    d = (jnp.exp(jnp.sum(params["lq1"].astype(f32)
                         * params["lk1"].astype(f32), -1))
         - jnp.exp(jnp.sum(params["lq2"].astype(f32)
                           * params["lk2"].astype(f32), -1)))
    return float(jnp.min(jnp.abs(d)))


async def engine_cases(a, seed: int, cell, reference, tag: str) -> list:
    """The cases of one engine built from ``cell``: [(must, result)]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    rng = random.Random(f"{seed}/long-context")
    prompt = [rng.randrange(1, engine.cfg.vocab_size)
              for _ in range(a.prompt)]
    n = 1 + a.steps
    gap = lam_gap_min(engine.params)
    sound = tag == "own"

    def ref_logprobs(toks, fault=None):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1], last=n,
                fault=fault)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    out = []

    def report(name, must, res):
        res.pop("abs_logprob_diffs", None)
        res.update(case=f"{tag}/{name}", seed=seed, has_to=must,
                   lam_gap_min=gap)
        print(json.dumps(res), flush=True)
        out.append((must, res))

    try:
        if "short" in a.cases:
            report("short", sound, await serve.agree(
                engine, seed, reference.reference_logits))
            if sound:
                report("short/a2_from_k1", False, await serve.agree(
                    engine, seed, partial(reference.reference_logits,
                                          fault="a2_from_k1")))
        if "long" in a.cases:
            toks, tops = await serve.greedy(engine, prompt, n)
            faults = (None, "window_ignored", "lam_fixed") if sound \
                else (None,)
            for fault in faults:
                res = judge(await asyncio.to_thread(ref_logprobs, toks,
                                                    fault), toks, tops)
                res["ok"] = bool(res["ok"] and res[
                    "median_abs_logprob_diff"] <= LONG_ATOL)
                res.update(prompt_tokens=len(prompt),
                           prefill_chunk=engine.ecfg.prefill_chunk,
                           window_pages_released=engine.stats()[
                               "kv_window_pages_released_total"])
                report("long" + (f"/{fault}" if fault else ""),
                       sound and fault is None, res)
    finally:
        await engine.stop()
    return out


async def amain(a) -> int:
    import gc

    import jax

    from benchmark.harness import cells

    cell = cells.load_cell(a.workload, a.root)
    cell["weight_scales"] = {**cell["weight_scales"], **json.loads(a.scales)}
    reference = cells.load_reference(cell)
    results = []
    for k, seed in enumerate(int(x) for x in a.seeds.split(",")):
        for tag in ("own", "state-8bit"):
            if tag not in a.tags or (tag != "own" and k >= a.control_seeds):
                continue
            control = (contextlib.nullcontext() if tag == "own"
                       else rounded_state())
            with control:
                results += await engine_cases(a, seed, cell, reference, tag)
            # an engine's parameters and pools have to be gone before
            # the next one's are made (tools/latent_long_context_check)
            gc.collect()
            for x in jax.live_arrays():
                x.delete()
    ok = all(res["ok"] == must for must, res in results)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="phi-4-mini-flash-reasoning.long-think")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="63,3400000063")
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="how many of the seeds also run state-8bit")
    ap.add_argument("--tags", default="own,state-8bit",
                    type=lambda s: s.split(","))
    ap.add_argument("--cases", default="short,long",
                    type=lambda s: s.split(","))
    ap.add_argument("--prompt", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--scales", default="{}",
                    help="JSON: weight scales tried in place of the "
                    "configuration's")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("phi4flash_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
