"""The engine of ``granite-4.0-h-small.rag-decode`` against its plain
reference at the harness's own agreement lengths AND at the cell's
longest prompt, each beside controls whose matrix state is kept in a
precision below the pool's float32, on the chip.

    chiprun --timeout 2400 -- python3 tools/granite_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps, which lies inside ONE
chunk of the chunked scan (``mamba_chunk_size`` 256) and one prefill
chunk. This builds the cell's engine exactly as benchmark/run.py does
(serve.build: the cell's engine data, weights from --seed), one engine
after the other, and asks it through ``engine.generate`` with top-20
logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            step kernel on the pool, the Pallas attention kernels).
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    long    a prompt of ``--prompt`` (2,048) tokens prefilled in the
            cell's ``prefill_chunk``s of 512 (eight scan chunks each,
            the state carried between the programs through the pool),
            then 1 + ``--steps`` (32) greedy tokens through the decode
            window. Both have to pass.
  bf16      the same, with every state a mixer hands back (a prefill
            chunk's, a decode step's) rounded to bfloat16 where
            models/granite.py makes it: what a state pool in the
            nearest precision below float32 holds. The XLA arm runs
            (``DYN_DISABLE_PALLAS``: the rows' state is gathered, so
            the rounding has one place; the kernel arm's arithmetic is
            the same recurrence).
    short, long   are reported only: over 2k tokens a bf16 state reads
            what the sound engine reads (the engine's own bf16
            activations round more: PERF.md, Findings PR 40).
  8-bit     the same with 8-bit floats (5 exponent bits, 2 of mantissa).
    short, long   have to FAIL.

``short`` is judged by the benchmark's one rule, ``benchmark/reference.py
judge`` (median of the per-position max |d logprob| over the engine's
top-20 <= 0.1, none over 2.5; nothing is widened here). ``long`` is set
against the configuration's reference (its full forward over prompt +
the engine's tokens, teacher-forced, the per-token recurrence from zero,
attention in query blocks, the last 1 + steps positions projected) under
the same rule with a median limit of its own, LONG_ATOL (PERF.md,
Findings PR 40, has the readings it lies between). ``--seeds`` runs the
plan at several seeds, one after the other.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or one that has to fail passes, and where
the platform is not a TPU (``--cpu`` lets the plumbing be tried at a
tiny size with ``--root`` a copy of the benchmark that has such a cell).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# case -> has to pass (True), has to fail (False) or is only reported
PLAN = {
    "own": {"short": True, "long": True},
    "bf16": {"short": None, "long": None},
    "8-bit": {"short": False, "long": False},
}
# median limit of long. Between its two readings (my chip runs, PR 40,
# at the configuration's weight scales): sound 0.0148, a state in 8-bit
# floats 0.2441; 3.4 x the one, a fifth of the other
LONG_ATOL = 0.05
# 8-bit floats with 5 exponent bits: a state's elements pass the 448 that
# 4 exponent bits hold (the first try read NaN at 2,048 tokens)
BITS = {"bf16": (8, 7), "8-bit": (5, 2)}


@contextlib.contextmanager
def rounded_state(tag: str):
    """models/granite.py with every state its mixer returns rounded to
    the precision ``tag`` names (``lax.reduce_precision``: a pair of
    converts is elided on the TPU), on the XLA arm, for the programs
    traced inside."""
    from jax import lax

    from dynamo_tpu.models import granite

    sound = granite.BLOCKS
    exp, man = BITS[tag]

    def mixer(*args, **kw):
        out, s, tail = sound.mixer(*args, **kw)
        return out, lax.reduce_precision(s, exp, man), tail

    os.environ["DYN_DISABLE_PALLAS"] = "1"      # read by runtime/config.py
    granite.BLOCKS = sound._replace(mixer=mixer)
    try:
        yield
    finally:
        granite.BLOCKS = sound
        del os.environ["DYN_DISABLE_PALLAS"]


async def engine_cases(a, seed: int, cell, reference, tag: str) -> list:
    """PLAN[tag]'s cases on one engine built from ``cell``:
    [(must, result)]."""
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

    def ref_logprobs(toks):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1], last=n)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    out = []
    try:
        for name, must in PLAN[tag].items():
            if name == "short":
                res = await serve.agree(engine, seed,
                                        reference.reference_logits)
            else:
                toks, tops = await serve.greedy(engine, prompt, n)
                res = judge(await asyncio.to_thread(ref_logprobs, toks),
                            toks, tops)
                res["ok"] = bool(res["ok"] and res[
                    "median_abs_logprob_diff"] <= LONG_ATOL)
                res.update(prompt_tokens=len(prompt),
                           prefill_chunk=engine.ecfg.prefill_chunk)
            res.pop("abs_logprob_diffs")
            res.update(case=f"{tag}/{name}", seed=seed, has_to=must)
            print(json.dumps(res), flush=True)
            out.append((must, res))
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
    for seed in (int(x) for x in a.seeds.split(",")):
        for tag in a.tags.split(","):
            control = (contextlib.nullcontext() if tag == "own"
                       else rounded_state(tag))
            with control:
                results += await engine_cases(a, seed, cell, reference, tag)
            # an engine's parameters and pools have to be gone before
            # the next one's are made (tools/latent_long_context_check)
            gc.collect()
            for x in jax.live_arrays():
                x.delete()
    ok = all(must is None or res["ok"] == must for must, res in results)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="granite-4.0-h-small.rag-decode")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="40,3400000040")
    ap.add_argument("--tags", default="own,bf16,8-bit")
    ap.add_argument("--prompt", type=int, default=2048)
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
        print("granite_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
