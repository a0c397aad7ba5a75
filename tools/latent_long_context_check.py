"""A latent-attention cell's engine against its plain reference, at the
harness's own agreement lengths AND at the timed sizes, each with a
control in the nearest precision below the pools', on the chip.

    chiprun --timeout 1800 -- python3 tools/latent_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps: it runs the cell's
programs (its one page bucket, the window, the latent kernel) at a short
context only. This builds the cell's engine exactly as benchmark/run.py
does (serve.build: the cell's engine data, weights from --seed) twice,
one engine after the other, and asks it through ``engine.generate`` with
top-20 logprobs:

  own       the cell's weights, the program as it is.
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    cold    one document of ``--doc`` (8,192) tokens + a ``--suffix``
            (256) token question, prefilled in chunks of
            ``prefill_chunk`` (the later chunks read the earlier ones
            back from the latent pool), then 1 + ``--steps`` (32) greedy
            tokens through the decode window (the Pallas latent kernel
            over ~8.4k cached tokens);
    warm    the same prompt again: the document's pages come from the
            prefix cache, only the tail is prefilled.
            All three have to pass.
  8-bit     the cell's weights, and every token's (c_kv, k_rope) rounded
            to 8-bit floats (4 exponent bits, 3 of mantissa) where
            models/mla.py makes them (``_latent_qkv`` wrapped, here and
            nowhere else): what a latent cache kept in the nearest
            precision below bf16 holds, in the pools and in the window's
            buffer alike.
    short, cold   have to FAIL.

``short`` is judged by the benchmark's one rule, ``benchmark/reference.py
judge``, as a run of the cell judges it: median of the per-position max
|d logprob| over the engine's top-20 <= 0.1 and no position over 2.5
(PR 23's limits; nothing is widened here). That ``8-bit/short`` fails is
what the configuration's ``weight_scales`` were chosen for (about.json):
the cell's own ``correct`` sees the precision of the latent cache.

``cold`` and ``warm`` are set against the configuration's reference (its
full forward over prompt + the engine's tokens, teacher-forced, queries
in blocks, the last 1 + steps positions projected) under the same rule
with a median limit of their own, LONG_ATOL = 0.04. Over 8k random
tokens a head's softmax rests on more latents than over 96, one
latent's rounding counts for less, and both readings are lower than at
the short lengths: sound 0.0178-0.0246 cold and on a prefix hit,
8-bit 0.0818-0.1170 (my chip runs, PR 31, seeds 31 and 3400000077;
PERF.md has every reading). 0.04 lies between: 1.6 times the largest
sound reading, half the smallest control.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or one that has to fail passes, and where
the platform is not a TPU (a CPU run shows nothing about the chip's
kernel; ``--cpu`` lets the plumbing be tried at a tiny size).
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


# case -> has to pass (True) or has to fail (False)
PLAN = {
    "own": {"short": True, "cold": True, "warm": True},
    "8-bit": {"short": False, "cold": False},
}
LONG_ATOL = 0.04    # median limit of cold / warm: the docstring has why


def _to_f8(x):
    """By lax.reduce_precision, not a pair of converts: on the TPU XLA
    elides a narrowing and widening pair (excess precision is allowed),
    and the first try of such a case read the sound one's numbers to
    the last digit."""
    from jax import lax

    return lax.reduce_precision(x, 4, 3)


@contextlib.contextmanager
def eight_bit_latents():
    """models/mla.py with every token's (c_kv, k_rope) rounded to 8-bit
    floats where they are made, for the programs traced inside."""
    from dynamo_tpu.models import mla

    sound = mla._latent_qkv

    def rounded(*args, **kw):
        q_lat, q_rope, c_kv, k_rope = sound(*args, **kw)
        return q_lat, q_rope, _to_f8(c_kv), _to_f8(k_rope)

    mla._latent_qkv = rounded
    try:
        yield
    finally:
        mla._latent_qkv = sound


async def engine_cases(a, cell, reference, tag: str) -> list:
    """PLAN[tag]'s cases on one engine built from ``cell``:
    [(must, result)]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, a.seed, serve.free_port())
    rng = random.Random(f"{a.seed}/long-context")
    prompt = [rng.randrange(1, engine.cfg.vocab_size)
              for _ in range(a.doc + a.suffix)]
    n = 1 + a.steps

    def ref_logprobs(toks):
        with engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1], last=n)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    async def long_case():
        hits0 = engine.stats()["prefix_hit_tokens_total"]
        toks, tops = await serve.greedy(engine, prompt, n)
        res = judge(await asyncio.to_thread(ref_logprobs, toks), toks, tops)
        res.update(prefix_hit_tokens=engine.stats()[
            "prefix_hit_tokens_total"] - hits0, prompt_tokens=len(prompt))
        return res

    out = []
    try:
        for name, must in PLAN[tag].items():
            if name == "short":
                res = await serve.agree(engine, a.seed,
                                        reference.reference_logits)
            else:
                res = await long_case()
                res["ok"] = bool(res["ok"] and res[
                    "median_abs_logprob_diff"] <= LONG_ATOL)
                if name == "warm" and res["prefix_hit_tokens"] < a.doc // 2:
                    res["ok"] = None       # no prefix hit: the case is void
            res.pop("abs_logprob_diffs")
            res.update(case=f"{tag}/{name}", has_to=must)
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
    reference = cells.load_reference(cell)
    results = []
    for tag, control in (("own", contextlib.nullcontext()),
                         ("8-bit", eight_bit_latents())):
        with control:
            results += await engine_cases(a, cell, reference, tag)
        # an engine's parameters and pools (9 GB of the chip's 16) have
        # to be gone before the next one's are made: dropping the last
        # reference did not free them on the chip (PR 31's first try)
        gc.collect()
        for x in jax.live_arrays():
            x.delete()
    ok = all(res["ok"] == must for must, res in results)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="kanana-2-30b-a3b.doc-qa")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--doc", type=int, default=8192)
    ap.add_argument("--suffix", type=int, default=256)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("latent_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
