"""The engine of ``kimi-linear-48b-a3b.doc-reason`` against its plain
reference at the cell's TIMED lengths, beside three controls that leave
out a part of the model's mathematics, on the chip.

    chiprun --timeout 3000 -- python3 tools/kimi_linear_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps: inside one prefill chunk,
with a state carried over six scan chunks and eight decode steps. The
cell's traffic carries the state over up to 14 prefill chunks and 1,500
decode steps. This builds the cell's engine exactly as benchmark/run.py
does (serve.build: the cell's engine data, weights from --seed), one
engine after the other, and asks it through ``engine.generate`` with
top-20 logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            KDA step kernel on the pool, the latent kernels). Prompts of
            512, 2,048 and 7,168 tokens prefilled in the cell's
            ``prefill_chunk``s of 512 (the state carried over 1, 4 and
            14 chunks through the pool, the latents through their
            pages), then 1 + ``--steps`` (256) greedy tokens through the
            decode window. ``--seeds``: every seed runs the longest
            prompt, the first seed the shorter ones too. Each has to
            pass: median gap <= LONG_ATOL.
  a-head    the decay a key CHANNEL replaced by its mean over the head's
            channels: a scalar-gated delta rule, the mathematics a
            kernel would most cheaply leave out.
  8-bit     every state a mixer hands back (a prefill chunk's, a decode
            step's) rounded to an 8-bit float (5 exponent bits, 2 of
            mantissa): what a pool far below float32 holds.
  roped     the rotary embedding applied to the 64 shared key columns
            and the queries' (``mla_use_nope`` ignored).
            Each control runs the longest prompt at the first seed, on
            the XLA arm where it changes a mixer (``DYN_DISABLE_PALLAS``:
            the rows' state is gathered, so the change has one place),
            and has to read at least ``CONTROL_FACTOR`` (3) times the
            worst ``own`` reading at that length AND over LONG_ATOL.

Every case is set against the configuration's reference (its full
forward over prompt + the engine's tokens, teacher-forced, the token
recurrence from zero, un-absorbed attention in query blocks, the last 1
+ steps positions projected) and judged by ``benchmark/reference.py
judge``'s median of the per-position max |d logprob| over the engine's
top-20. ``--scales`` tries weight scales in place of the
configuration's.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or a control reads too little, and where
the platform is not a TPU (``--cpu`` lets the plumbing be tried at a
tiny size with ``--root`` a copy of the benchmark that has such a cell).
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

CONTROLS = ("a-head", "8-bit", "roped")
CONTROL_FACTOR = 3.0
# median limit of a long case. Between its two readings (my chip runs,
# PR 50, at the configuration's weight scales; about.json
# weight_scales_why has them)
LONG_ATOL = 0.05


@contextlib.contextmanager
def control(tag: str):
    """models/kimi_linear.py with one part of its mathematics left out,
    for the programs traced inside."""
    import jax.numpy as jnp
    from jax import lax

    from dynamo_tpu.models import kimi_linear, llama, mla

    sound = kimi_linear.BLOCKS
    undo = []

    def patch(mod, name, value):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    def xla_arm():
        os.environ["DYN_DISABLE_PALLAS"] = "1"  # read by runtime/config.py

    if tag == "a-head":
        def mean(g):
            return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)

        step, chunk = kimi_linear._kda_step, kimi_linear._kda_chunk
        xla_arm()
        patch(kimi_linear, "_kda_chunk",
              lambda s, q, k, v, g, b, c: chunk(s, q, k, v, mean(g), b, c))
        patch(kimi_linear, "BLOCKS", sound._replace(
            mixer=lambda cfg, mp, u, valid, s, tail: sound.mixer(
                cfg, mp, u, valid, s, tail,
                lambda s, q, k, v, g, b: step(s, q, k, v, mean(g), b))))
    elif tag == "8-bit":
        def mixer(*args, **kw):
            out, s, tail = sound.mixer(*args, **kw)
            return out, lax.reduce_precision(s, 5, 2), tail

        xla_arm()
        patch(kimi_linear, "BLOCKS", sound._replace(mixer=mixer))
    elif tag == "roped":
        qkv = mla._latent_qkv

        def rotated(cfg, lp, x, safe_pos, inv_freq, dtype):
            return qkv(dataclasses.replace(cfg, mla_nope=False), lp, x,
                       safe_pos,
                       llama.rope_freqs(cfg, dim=cfg.qk_rope_head_dim),
                       dtype)

        patch(mla, "_latent_qkv", rotated)
    try:
        yield
    finally:
        for mod, name, value in reversed(undo):
            setattr(mod, name, value)
        os.environ.pop("DYN_DISABLE_PALLAS", None)


async def engine_cases(a, seed: int, cell, reference, tag: str,
                       prompts: list) -> list:
    """The cases of one engine built from ``cell``: a prompt of each
    length in ``prompts``; [result]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    n = 1 + a.steps
    out = []
    try:
        for length in prompts:
            rng = random.Random(f"{seed}/{length}/long-context")
            prompt = [rng.randrange(1, engine.cfg.vocab_size)
                      for _ in range(length)]

            def ref_logprobs(toks):
                with jax.default_matmul_precision("highest"), \
                        engine._on_device():
                    logits = reference.reference_logits(
                        engine.params, engine.cfg, prompt + toks[:-1],
                        last=n)
                    return np.asarray(jax.nn.log_softmax(logits, -1))

            toks, tops = await serve.greedy(engine, prompt, n)
            res = judge(await asyncio.to_thread(ref_logprobs, toks), toks,
                        tops)
            res.pop("abs_logprob_diffs")
            res.pop("ok")
            res.update(case=tag, seed=seed, prompt_tokens=length,
                       prefill_chunk=engine.ecfg.prefill_chunk,
                       carried_chunks=engine.stats()[
                           "prefill_row_chunks_carried_total"])
            print(json.dumps(res), flush=True)
            out.append(res)
    finally:
        await engine.stop()
    return out


async def amain(a, control=control, reported_only=()) -> int:
    """The sound cases, then the controls. ``control`` is the context
    manager that leaves a part of the mathematics out (this module's, or
    another family's tool's); a control named in ``reported_only`` is run
    and printed and required of nothing."""
    import gc

    import jax

    from benchmark.harness import cells

    cell = cells.load_cell(a.workload, a.root)
    cell["weight_scales"] = {**cell["weight_scales"], **json.loads(a.scales)}
    reference = cells.load_reference(cell)
    lengths = [int(x) for x in a.prompts.split(",")]
    seeds = [int(x) for x in a.seeds.split(",")]

    async def run(seed, tag, prompts):
        with contextlib.nullcontext() if tag == "own" else control(tag):
            res = await engine_cases(a, seed, cell, reference, tag, prompts)
        # an engine's parameters and pools have to be gone before the
        # next one's are made (tools/latent_long_context_check)
        gc.collect()
        for x in jax.live_arrays():
            x.delete()
        return res

    own = []
    for i, seed in enumerate(seeds):
        own += await run(seed, "own", lengths if i == 0 else lengths[-1:])
    worst = max(r["median_abs_logprob_diff"] for r in own
                if r["prompt_tokens"] == lengths[-1])
    ok = all(r["median_abs_logprob_diff"] <= LONG_ATOL for r in own)
    for tag in (t for t in a.controls.split(",") if t):
        (res,) = await run(seeds[0], tag, lengths[-1:])
        shows = res["median_abs_logprob_diff"] >= max(
            CONTROL_FACTOR * worst, LONG_ATOL)
        print(json.dumps({"control": tag, "shows": bool(shows),
                          "required": tag not in reported_only,
                          "over_worst_sound": round(
                              res["median_abs_logprob_diff"] / worst, 2)}),
              flush=True)
        ok = ok and (shows or tag in reported_only)
    print(json.dumps({"ok": bool(ok), "worst_sound_at_longest": worst,
                      "long_atol": LONG_ATOL}), flush=True)
    return 0 if ok else 1


def main(doc=__doc__, workload="kimi-linear-48b-a3b.doc-reason",
         seeds="50,3500000050,51", prompts="512,2048,7168",
         controls=CONTROLS, **family) -> int:
    """The command line; another family's tool calls it with its own
    defaults, ``control`` and ``reported_only`` (``amain``'s)."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--workload", default=workload)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default=seeds)
    ap.add_argument("--prompts", default=prompts)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--controls", default=",".join(controls))
    ap.add_argument("--scales", default="{}",
                    help="JSON: weight scales tried in place of the "
                    "configuration's")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("%s: not a TPU" % os.path.basename(sys.argv[0]),
              file=sys.stderr)
        return 1
    return asyncio.run(amain(a, **family))


if __name__ == "__main__":
    sys.exit(main())
