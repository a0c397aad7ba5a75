"""The SDAR cell's engine against its plain reference AT THE TIMED SIZES
(a prompt of the traffic's own shape with a tail, then 64 blocks through
the window in a batch of 64), with two controls that have to fail, on
the chip.

    chiprun --timeout 1800 -- python3 tools/diffusion_block_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts (no tail) + 9 tokens, one row at a time: it
never runs the window at the cell's batch, a prompt's tail, or more than
three blocks. This builds the cell's engine exactly as benchmark/run.py
does (serve.build: the cell's engine data, weights from --seed), one
engine after the other, and asks it through ``engine.generate`` with
top-20 logprobs:

  own       the cell's weights, the program as it is.
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    long    ``--rows`` (64) requests at once, prompts of ``--prompt``
            (253: a tail of one token) + 0..3 tokens (every tail) drawn
            as the traffic draws them, ``--blocks`` (64) blocks each;
            ``--judged`` (4) of them, one of each tail, ask for
            logprobs and are set against the reference: the
            log-probabilities of the forward that made each position
            final against ``reference_logits`` over prompt + the
            engine's tokens. Has to pass.
  8-bit-kv  every token's K and V rounded to 8-bit floats (4 exponent
            bits, 3 of mantissa) where models/llama.py makes them for a
            block configuration (``_block_kv`` wrapped, here and nowhere
            else): what pools kept in the nearest precision below bf16
            hold.  short, long   have to FAIL.
  causal    the CAUSAL mask in place of the block mask in prefill (the
            prefill program built from the configuration with
            block_length 1: a prompt position no longer sees to the end
            of its block).  short, long   have to FAIL.

``short`` is judged by the benchmark's one rule (``benchmark/reference.py
judge``: median of the per-position max |d logprob| over the engine's
top-20 <= 0.1 and no position over 2.5; nothing is widened here); that
the controls fail it is what the configuration's ``weight_scales`` were
chosen for (about.json). ``long`` is judged by the same rule over all
its positions together.

``--scales '{"leaf": x}'`` replaces the configuration's weight scales
(how they were chosen); ``--strategy`` replaces the configuration's
``remasking_strategy`` for the ``own`` engine's ``long`` case, which is
then NOT judged against the reference (the reference's rows are those of
sequential unmasking) but reports the program's own counts: how the
dynamic strategy's early exits were looked for on the cell's weights.
Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or one that has to fail passes, and where
the platform is not a TPU (``--cpu`` lets the plumbing be tried at a
tiny size).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# case -> has to pass (True) or has to fail (False)
PLAN = {
    "own": {"short": True, "long": True},
    "8-bit-kv": {"short": False, "long": False},
    "causal": {"short": False, "long": False},
}


@contextlib.contextmanager
def control(what: str):
    """models/llama.py with every token's K and V of a block
    configuration rounded to 8-bit floats where they are made
    ("8-bit-kv"), for the programs traced inside; else as it is. By
    lax.reduce_precision, not a pair of converts: on the TPU XLA elides a
    narrowing and widening pair (PR 31's first try)."""
    from jax import lax

    from dynamo_tpu.models import llama

    made = llama._block_kv
    if what == "8-bit-kv":
        def rounded(k, v, dtype):
            k, v = made(k, v, dtype)
            return (lax.reduce_precision(k, 4, 3),
                    lax.reduce_precision(v, 4, 3))

        llama._block_kv = rounded
    try:
        yield
    finally:
        llama._block_kv = made


async def engine_cases(a, cell, reference, tag: str) -> list:
    """PLAN[tag]'s cases on one engine built from ``cell``:
    [(must, result)]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge
    from dynamo_tpu.models import llama

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, a.seed, serve.free_port())
    if tag == "causal":
        engine.prefill_fn = llama.make_step_fns(
            dataclasses.replace(engine.cfg, block_length=1))[0]
    L = engine.cfg.block_length
    rng = random.Random(f"{a.seed}/diffusion-block")
    V = engine.cfg.vocab_size
    prompts = [[rng.randrange(1, V) for _ in range(a.prompt + i % L)]
               for i in range(a.rows)]
    n = a.blocks * L

    def ref_logprobs(prompt, toks):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1])
            return np.asarray(
                jax.nn.log_softmax(logits[len(prompt) - 1:], -1))

    async def quiet(prompt):
        from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                     SamplingOptions,
                                                     StopConditions)
        from dynamo_tpu.runtime.engine import Context

        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=n, ignore_eos=True))
        got = 0
        async for out in engine.generate(req, Context()):
            got += len(out.token_ids)
            if out.finish_reason is not None:
                break
        return got

    async def long_case():
        s0 = engine.stats()
        judged = prompts[:a.judged]
        outs = await asyncio.gather(
            *(serve.greedy(engine, p, n) for p in judged),
            *(quiet(p) for p in prompts[a.judged:]))
        s1 = engine.stats()
        counts = {k: s1[k] - s0[k] for k in s1
                  if k.startswith("diffusion_")}
        counts["windows"] = (s1["decode_windows_total"]
                             - s0["decode_windows_total"])
        counts["rows_mean"] = (
            (s1["decode_rows_total"] - s0["decode_rows_total"])
            / max(counts["windows"], 1) / engine.ecfg.decode_steps)
        if a.strategy:
            return {"ok": True, "judged": False, **counts}
        refs, toks, tops = [], [], []
        for p, (t, top) in zip(judged, outs[:a.judged]):
            serve.check(len(t) == n, f"{len(t)} tokens of {n}")
            refs.append(await asyncio.to_thread(ref_logprobs, p, t))
            toks += t
            tops += top
        res = judge(np.concatenate(refs), toks, tops)
        res.update(counts, prompt_tokens=[len(p) for p in judged])
        return res

    out = []
    try:
        for name, must in PLAN[tag].items():
            if a.cases and name not in a.cases:
                continue
            if name == "short":
                res = await serve.agree(engine, a.seed,
                                        reference.reference_logits)
            else:
                res = await long_case()
            res.pop("abs_logprob_diffs", None)
            res.update(case=f"{tag}/{name}", has_to=must)
            print(json.dumps(res), flush=True)
            out.append((must, res))
    finally:
        await engine.stop()
    return out


def load_cell(a) -> dict:
    """The cell's files, with ``--scales`` and ``--strategy`` laid over
    them (the strategy is the configuration's, so it is run from a copy
    of config.json under .bench_trace/)."""
    from benchmark.harness import cells

    cell = cells.load_cell(a.workload, a.root)
    if a.scales is not None:
        cell["weight_scales"] = json.loads(a.scales)
    if a.strategy:
        cell["model_config"]["remasking_strategy"] = a.strategy
        path = os.path.join(a.root, ".bench_trace", "strategy-config")
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(cell["model_config"], f)
        cell["model_path"] = path
    return cell


async def amain(a, cell) -> int:
    import gc

    import jax

    from benchmark.harness import cells

    reference = cells.load_reference(cell)
    results = []
    for tag in a.only or (["own"] if a.strategy else PLAN):
        with control(tag):
            results += await engine_cases(a, cell, reference, tag)
        # an engine's parameters and pools have to be gone before the
        # next one's are made (PR 31: dropping the last reference did not
        # free them on the chip)
        gc.collect()
        for x in jax.live_arrays():
            x.delete()
    ok = all(res["ok"] == must for must, res in results)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="sdar-30b-a3b-chat.decode-heavy")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--prompt", type=int, default=253)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--judged", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--only", action="append", choices=sorted(PLAN))
    ap.add_argument("--cases", action="append", choices=["short", "long"])
    ap.add_argument("--scales")
    ap.add_argument("--strategy")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("diffusion_block_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a, load_cell(a)))


if __name__ == "__main__":
    sys.exit(main())
