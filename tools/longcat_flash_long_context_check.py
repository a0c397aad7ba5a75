"""The engine of ``longcat-flash-omni.omni-turns`` against its plain reference
at the cell's TIMED lengths, beside controls that compute ONE thing
wrong, on the chip.

    chiprun --timeout 3000 -- python3 tools/longcat_flash_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree) is
fixed at 96-token prompts + 8 greedy steps: inside one prefill chunk and
two windows. The cell's traffic prefills up to 14 chunks and decodes up
to 1,536 steps over 8,704 positions. This builds the cell's engine
exactly as benchmark/run.py does (serve.build: the cell's engine data,
weights from --seed), one engine after the other, and asks it through
``engine.generate`` with top-20 logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            latent kernels at 64 heads over pools of 8 entries, the
            sorted dispatch in prefill, the dense-over-experts form in
            the window).
    short   ``serve.agree`` itself: what decides ``correct`` in a run.
    p<N>    prompts of ``--prompts`` (256, 1,024, 7,168) tokens prefilled
            in the cell's ``prefill_chunk``s of 512 (1, 2 and 14
            programs, the latents through their pages), then 1 +
            ``--steps`` (256) greedy tokens through the fused window.
            ``--seeds``: every seed runs the longest prompt, the first
            seed the shorter ones too. Each has to pass: median gap <=
            LONG_ATOL.
    Then the SAME outputs judged against the reference with one fault
    (benchmark/configs/longcat-flash-omni/reference.py FAULTS), the
    first seed, every length:
    p<N>/identity_dropped   an identity pair adds nothing;
    p<N>/shortcut_early     ``s`` added after sub-block 0;
    p<N>/lora_scales_off    both LoRA scales 1.
            Each has to read at least ``CONTROL_FACTOR`` (3) times the
            worst ``own`` reading at that length AND over LONG_ATOL.
  latent-8bit  (the first seed) the same weights with every token's
            (c_kv, k_rope) rounded to 8-bit floats (4 exponent bits, 3 of
            mantissa) where models/mla.py makes them (``_latent_qkv``
            wrapped, here and nowhere else): what a latent cache kept in
            the nearest precision below bf16 holds, in the pools and in
            the window's buffers alike. p<N> at every length: the same
            two conditions.

Every case is set against the configuration's reference (its full
forward over prompt + the engine's tokens, teacher-forced, per-head K
and V made from the latent, queries in blocks, the last 1 + steps
positions projected) and judged by ``benchmark/reference.py judge``'s
median of the per-position max |d logprob| over the engine's top-20.
``--scales`` tries weight scales in place of the configuration's;
``--controls ""`` runs the sound cases alone.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or a control reads too little, and where
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# median limit of p<N>. Between its readings (my chip run, PR 65, seeds
# 65 / 3500000065 / 66 at the configuration's weight scales; about.json's
# ``long_context`` has each): sound 0.0330 at 256 tokens, 0.0282 at 1,024,
# 0.0200-0.0235 at 7,168; the controls 0.112-0.217 (the latent cache in
# 8-bit floats), 0.226-0.295 (the shortcut early), 0.60-0.96 (identity
# pairs dropped), 2.07-2.45 (the LoRA scales off): 1.8 x the largest of
# the one, 1.9 x under the smallest of the others
LONG_ATOL = 0.06
CONTROL_FACTOR = 3.0
FAULTS = ("identity_dropped", "shortcut_early", "lora_scales_off")


@contextlib.contextmanager
def eight_bit_latents():
    """models/mla.py with every token's (c_kv, k_rope) rounded to 8-bit
    floats where they are made (``lax.reduce_precision``: a pair of
    converts is elided on the TPU), for the programs traced inside;
    models/longcat_flash.py asks ``mla._latent_qkv`` by name."""
    from jax import lax

    from dynamo_tpu.models import mla

    sound = mla._latent_qkv

    def rounded(*args, **kw):
        q_lat, q_rope, c_kv, k_rope = sound(*args, **kw)
        return (q_lat, q_rope, lax.reduce_precision(c_kv, 4, 3),
                lax.reduce_precision(k_rope, 4, 3))

    mla._latent_qkv = rounded
    try:
        yield
    finally:
        mla._latent_qkv = sound


async def engine_cases(a, seed: int, first: bool, cell, reference,
                       tag: str) -> list:
    """The cases of one engine built from ``cell``:
    [(kind, length, result)], kind "sound" or "control"."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    n = 1 + a.steps
    sound = tag == "own"
    out = []

    def report(name, kind, length, res):
        res.pop("abs_logprob_diffs", None)
        res.update(case=f"{tag}/{name}", seed=seed, kind=kind)
        print(json.dumps(res), flush=True)
        out.append((kind, length, res))

    def ref_logprobs(prompt, toks, fault=None):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                engine.params, engine.cfg, prompt + toks[:-1], last=n,
                fault=fault)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    try:
        if "short" in a.cases and first:
            res = await serve.agree(engine, seed, reference.reference_logits)
            report("short", "sound" if sound else "reported", 0, res)
        asked = []
        lengths = a.prompts if first else a.prompts[-1:]
        for length in lengths if "long" in a.cases else ():
            rng = random.Random(f"{seed}/long-context/{length}")
            prompt = [rng.randrange(1, engine.cfg.vocab_size)
                      for _ in range(length)]
            toks, tops = await serve.greedy(engine, prompt, n)
            asked.append((length, prompt, toks, tops))
        stats = engine.stats()
        # every request is made; the pools go before the reference runs
        # (a 7.4k-token forward in float32 beside 9.6 GiB of weights)
        engine.kv_k.delete()
        engine.kv_v.delete()
        for length, prompt, toks, tops in asked:
            faults = (None,) + (tuple(f for f in FAULTS if f in a.controls)
                                if sound and first else ())
            for fault in faults:
                res = judge(await asyncio.to_thread(
                    ref_logprobs, prompt, toks, fault), toks, tops)
                res.update(prompt_tokens=length,
                           prefill_chunk=engine.ecfg.prefill_chunk,
                           identity_share=stats["moe_pairs_identity_total"]
                           / max(stats["moe_pairs_routed_total"], 1))
                report(f"p{length}" + (f"/{fault}" if fault else ""),
                       "sound" if sound and fault is None else "control",
                       length, res)
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
        for tag in ("own", "latent-8bit"):
            if tag != "own" and (k > 0 or tag not in a.controls):
                continue
            control = (contextlib.nullcontext() if tag == "own"
                       else eight_bit_latents())
            with control:
                results += await engine_cases(a, seed, k == 0, cell,
                                              reference, tag)
            # an engine's parameters and pools have to be gone before
            # the next one's are made (tools/latent_long_context_check)
            gc.collect()
            for x in jax.live_arrays():
                x.delete()
    ok = True
    worst = {}
    for kind, length, res in results:
        if kind == "sound":
            limit = LONG_ATOL if length else None
            good = res["ok"] and (limit is None or res[
                "median_abs_logprob_diff"] <= limit)
            ok &= bool(good)
            worst[length] = max(worst.get(length, 0.0),
                                res["median_abs_logprob_diff"])
    for kind, length, res in results:
        if kind == "control":
            need = max(LONG_ATOL, CONTROL_FACTOR * worst.get(length, 0.0))
            seen = res["median_abs_logprob_diff"] >= need
            print(json.dumps({"control": res["case"], "needs": need,
                              "reads": res["median_abs_logprob_diff"],
                              "seen": bool(seen)}), flush=True)
            ok &= bool(seen)
    print(json.dumps({"ok": bool(ok), "worst_sound": worst}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="longcat-flash-omni.omni-turns")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="65,3500000065,66")
    ap.add_argument("--controls", default=",".join(FAULTS + ("latent-8bit",)),
                    type=lambda s: [c for c in s.split(",") if c])
    ap.add_argument("--cases", default="short,long",
                    type=lambda s: s.split(","))
    ap.add_argument("--prompts", default="256,1024,7168",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--scales", default="{}",
                    help="JSON: weight scales tried in place of the "
                    "configuration's")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("longcat_flash_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
