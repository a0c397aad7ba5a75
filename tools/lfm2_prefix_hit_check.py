"""The LFM2 cell's engine against its plain reference at the cell's own
prompt shape, cold and ON A PREFIX HIT (pages and conv state from the
prefix cache), each with controls in the nearest precision below the
pools', on the chip.

    chiprun --timeout 1800 -- python3 tools/lfm2_prefix_hit_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps: a context too short for a
prefix hit, so it never reads a page's state snapshot. This builds the
cell's engine exactly as benchmark/run.py does (serve.build: the cell's
engine data, weights from --seed), one engine after the other, and asks
it through ``engine.generate`` with top-20 logprobs:

  own       the cell's weights, the program as it is.
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    cold    a system prompt of ``--shared`` (3,072) tokens + BOS and a
            turn of ``--suffix`` (200) tokens, prefilled in chunks of
            ``prefill_chunk`` from an empty prefix cache (the conv state
            carried from chunk to chunk through the slot, a snapshot
            written at every page's end), then 1 + ``--steps`` (64)
            greedy tokens through the decode window (the packed decode
            kernel over ~3.3k cached tokens, the state carried from
            window to window);
    warm    the same system prompt and ANOTHER turn: the shared pages
            come from the prefix cache and the row's conv state from the
            snapshot of the last of them; only the turn is prefilled.
            Void unless the hit covers the shared part and the engine
            counted a restore. All three have to pass.
  8-bit state   every gated input z rounded to 8-bit floats (4 exponent
            bits, 3 of mantissa) where models/lfm2.py makes it
            (``_gated_input`` wrapped, here and nowhere else): what a
            state pool and its snapshots kept in the nearest precision
            below bf16 hold.
    short, warm   have to FAIL.
  8-bit KV  every token's K and V rounded the same way where
            ``lfm2._qkv`` makes them: the pools and the window's buffer.
    short, warm   have to FAIL.

``short`` is judged by the benchmark's one rule (``benchmark/reference.py
judge``: median of the per-position max |d logprob| over the engine's
top-20 <= 0.1 and no position over 2.5; nothing is widened here). That
the two controls fail it is what the configuration's ``weight_scales``
were chosen for (about.json): the cell's own ``correct`` sees the
precision of the state and of the KV cache. ``cold`` and ``warm`` are set
against the reference's full forward over prompt + the engine's tokens
(teacher-forced, queries in blocks, the last 1 + steps positions
projected) under the same rule with a median limit of their own,
``LONG_ATOL``; PERF.md (Findings PR 33) has the readings it lies between.

``--scales '{"leaf": x}'`` replaces the configuration's weight scales
(how they were chosen). Prints one JSON line per case and a last line
{"ok": ...}. Exits 1 where a case that has to pass fails or one that has
to fail passes, and where the platform is not a TPU (``--cpu`` lets the
plumbing be tried at a tiny size).
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
    "8-bit-state": {"short": False, "warm": False},
    "8-bit-kv": {"short": False, "warm": False},
}
LONG_ATOL = 0.05    # median limit of cold / warm: PERF.md has why


def _to_f8(x):
    """By lax.reduce_precision, not a pair of converts: on the TPU XLA
    elides a narrowing and widening pair (PR 31's first try)."""
    from jax import lax

    return lax.reduce_precision(x, 4, 3)


@contextlib.contextmanager
def eight_bit(what: str):
    """models/lfm2.py with the state's values ("8-bit-state") or every
    token's K and V ("8-bit-kv") rounded to 8-bit floats where they are
    made, for the programs traced inside; "own": as it is."""
    from dynamo_tpu.models import lfm2

    gated, qkv = lfm2._gated_input, lfm2._qkv
    if what == "8-bit-state":
        lfm2._gated_input = lambda b, x, dtype: _to_f8(gated(b, x, dtype))
    elif what == "8-bit-kv":
        def rounded(*args, **kw):
            q, k, v = qkv(*args, **kw)
            return q, _to_f8(k), _to_f8(v)

        lfm2._qkv = rounded
    try:
        yield
    finally:
        lfm2._gated_input, lfm2._qkv = gated, qkv


async def engine_cases(a, cell, reference, tag: str) -> list:
    """PLAN[tag]'s cases on one engine built from ``cell``:
    [(must, result)]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, a.seed, serve.free_port())
    rng = random.Random(f"{a.seed}/prefix-hit")
    V = engine.cfg.vocab_size
    shared = [rng.randrange(1, V) for _ in range(1 + a.shared)]
    turns = {name: [rng.randrange(1, V) for _ in range(a.suffix)]
             for name in ("cold", "warm")}
    n = 1 + a.steps

    def ref_logprobs(prompt, toks):
        with engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1], last=n)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    async def long_case(name):
        prompt = shared + turns[name]
        s0 = engine.stats()
        toks, tops = await serve.greedy(engine, prompt, n)
        res = judge(await asyncio.to_thread(ref_logprobs, prompt, toks),
                    toks, tops)
        s1 = engine.stats()
        res.update(
            prefix_hit_tokens=(s1["prefix_hit_tokens_total"]
                               - s0["prefix_hit_tokens_total"]),
            state_restores=(s1["state_restores_total"]
                            - s0["state_restores_total"]),
            prompt_tokens=len(prompt))
        return res

    out = []
    try:
        if "warm" in PLAN[tag] and "cold" not in PLAN[tag]:
            # the control's hit needs pages to hit: serve the cold turn
            # first, unjudged
            await serve.greedy(engine, shared + turns["cold"], 1)
        for name, must in PLAN[tag].items():
            if name == "short":
                res = await serve.agree(engine, a.seed,
                                        reference.reference_logits)
            else:
                res = await long_case(name)
                res["ok"] = bool(res["ok"] and res[
                    "median_abs_logprob_diff"] <= LONG_ATOL)
                if name == "warm" and not (
                        res["prefix_hit_tokens"] >= a.shared
                        and res["state_restores"] == 1):
                    res["ok"] = None       # no hit: the case is void
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
    if a.scales is not None:
        cell["weight_scales"] = json.loads(a.scales)
    reference = cells.load_reference(cell)
    results = []
    for tag in a.only or PLAN:
        with eight_bit(tag):
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
    ap.add_argument("--workload", default="lfm2-24b-a2b.agent-loop")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--shared", type=int, default=3072)
    ap.add_argument("--suffix", type=int, default=200)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--only", action="append", choices=sorted(PLAN))
    ap.add_argument("--scales")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("lfm2_prefix_hit_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
