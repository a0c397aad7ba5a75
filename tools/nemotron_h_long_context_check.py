"""The engine of ``nemotron-3-super-120b-a12b.agent-reason`` against its
plain reference at the harness's own agreement lengths AND at a
2,048-token prompt, each beside two controls computed in 8-bit floats,
on the chip (tools/granite_long_context_check.py's line).

    chiprun --timeout 2400 -- python3 tools/nemotron_h_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps, which lies inside ONE
chunk of the chunked scan (``chunk_size`` 128) and one prefill chunk.
This builds the cell's engine exactly as benchmark/run.py does
(serve.build: the cell's engine data, weights from --seed), one engine
after the other, and asks it through ``engine.generate`` with top-20
logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            step kernel on the pool by groups, the grouped expert kernel
            in prefill, the Pallas attention kernels).
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    long    a prompt of ``--prompt`` (2,048) tokens prefilled in the
            cell's ``prefill_chunk``s of 512 (four scan chunks each, the
            state carried between the programs through the pool), then
            1 + ``--steps`` (32) greedy tokens through the decode
            window. Both have to pass.
  state-8bit    the same, with every matrix state and every conv tail a
            Mamba-2 mixer hands back (a prefill chunk's, a decode
            step's: what the two state pools keep) rounded to 8-bit
            floats (5 exponent bits, 2 of mantissa) where
            models/granite.py makes it. The XLA arm runs
            (``DYN_DISABLE_PALLAS``: the rows' state is gathered, so the
            rounding has one place; the kernel arm's arithmetic is the
            same recurrence).
  latent-8bit   the same, with the input the routed experts read (the
            token's latent vector, ``x W_lat_in``) COMPUTED in 8-bit
            floats: x, ``W_lat_in`` and the product each rounded to them
            (models/nemotron_h.py latent_in), on the program's own arms.
    short, long   of both controls have to FAIL.

``short`` is judged by the benchmark's one rule, ``benchmark/reference.py
judge`` (median of the per-position max |d logprob| over the engine's
top-20 <= 0.1, none over 2.5; nothing is widened here). ``long`` is set
against the configuration's reference (its full forward over prompt +
the engine's tokens, teacher-forced, the per-token recurrence from zero,
attention in query blocks, the last 1 + steps positions projected) under
the same rule with a median limit of its own, LONG_ATOL (about.json's
``weight_scales_why`` and PERF.md, Findings PR 60, have the readings it
lies between). ``--seeds`` runs the plan at several seeds, one after the
other.

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

# case -> has to pass (True) or has to fail (False)
PLAN = {
    "own": {"short": True, "long": True},
    "state-8bit": {"short": False, "long": False},
    "latent-8bit": {"short": False, "long": False},
}
# median limit of long. Between its readings (my chip runs, PR 60, at
# the configuration's weight scales, seeds 60 | 3600000060 | 3600000001):
# sound 0.0231 | 0.0121 | 0.0112, the latent input computed in 8-bit
# floats 0.1390 | 0.1798 | 0.1606, the state in 8-bit floats 0.3063 |
# 0.2103 | 0.2705: 3.0 x the largest of the one, 0.50 of the smallest of
# the others
LONG_ATOL = 0.07
# 8-bit floats with 5 exponent bits: a state's elements pass the 448 that
# 4 exponent bits hold (tools/granite_long_context_check.py)
EXP_BITS, MANTISSA_BITS = 5, 2


def _round8(x):
    from jax import lax

    return lax.reduce_precision(x, EXP_BITS, MANTISSA_BITS)


@contextlib.contextmanager
def control(tag: str):
    """models/nemotron_h.py with one thing computed in 8-bit floats, for
    the programs traced inside: ``state-8bit`` every state and conv tail
    its Mamba-2 mixer returns (on the XLA arm), ``latent-8bit`` the
    latent vector the expert forms are handed, operands and result."""
    from dynamo_tpu.models import nemotron_h

    if tag == "own":
        yield
        return
    sound_blocks, sound_latent = nemotron_h.BLOCKS, nemotron_h.latent_in
    if tag == "state-8bit":
        def mixer(*args, **kw):
            out, s, tail = sound_blocks.mixer(*args, **kw)
            return out, _round8(s), _round8(tail)

        os.environ["DYN_DISABLE_PALLAS"] = "1"  # read by runtime/config.py
        nemotron_h.BLOCKS = sound_blocks._replace(mixer=mixer)
    else:
        nemotron_h.latent_in = lambda x, w: _round8(
            sound_latent(_round8(x), _round8(w)))
    try:
        yield
    finally:
        nemotron_h.BLOCKS = sound_blocks
        nemotron_h.latent_in = sound_latent
        os.environ.pop("DYN_DISABLE_PALLAS", None)


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
            if name not in a.cases.split(","):
                continue
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
            with control(tag):
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
                    default="nemotron-3-super-120b-a12b.agent-reason")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="60,3600000060")
    ap.add_argument("--tags", default="own,state-8bit,latent-8bit")
    ap.add_argument("--cases", default="short,long")
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
        print("nemotron_h_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
