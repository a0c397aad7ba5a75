"""The Command A+ cell's engine against its plain reference at the timed
lengths, with eight controls that must fail, on the chip.

    chiprun --timeout 3000 -- python3 tools/command_a_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps, where a window of 4,096
never bites, no page is given back and a context of 30k is far away.
This builds the cell's engine as benchmark/run.py does (serve.build: the
cell's engine data, weights from the seed) and asks it through
``engine.generate`` with top-20 logprobs:

  agree     ``serve.agree`` itself on every seed's engine: what decides
            ``correct`` in a run of the cell. Has to pass.
  sound     prompts of ``--prompts`` (512, 4,096, 16,384, 32,768) random
            tokens through chunked prefill over both pools (window-pool
            pages given back between chunks) + 1 + ``--steps`` (256)
            greedy tokens through the decode window (pages given back
            between windows), against ``reference_logits(..., last=1 +
            steps)`` teacher-forced on the engine's tokens, judged by
            ``benchmark/reference.py judge``: its median gap, and its two
            limits (0.1 on the median, 2.5 on any position). Has to pass.
            The longest runs on every seed of ``--seeds``, the others on
            the first.
  controls  the SAME engine output against the reference with ONE fault
            (reference.py ``CONTROLS``: the window ignored, the full
            layer rotated, the half-split rotation, the block run
            sequentially, RMSNorm for LayerNorm, the shared experts
            summed, (routed + shared) / 2, a softmax gate), at
            ``--control-prompts`` (4,096 and 32,768) on the first seed. Each
            has to read a median of at least ``--factor`` (2) times the
            sound median of the same seed and length (the same tokens
            and the same engine output: only the reference differs): the
            program computes what the sound reference computes and not
            the neighbour.

All of an engine's requests are made first; then its pools are deleted
(its parameters stay) so that a reference of 33k tokens fits beside 9
GiB of weights, and the references are computed.

On random weights attention is near uniform and some controls would not
show; the configuration's ``weight_scales`` (about.json) were chosen
with ``--scales`` until every control does and ``serve.agree`` still
passes.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a sound case fails or a control reads under the factor, and where the
platform is not a TPU (``--cpu`` runs it there all the same: slow at the
cell's size).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "command-a-plus-05-2026.rag-long"


async def seed_cases(a, seed: int, first: bool, cell, reference) -> list:
    """Every case of one seed: [result dicts]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    cfg = engine.cfg
    n = 1 + a.steps
    lengths = a.prompts if first else a.prompts[-1:]
    prompts = {p: [random.Random(f"{seed}/long/{p}").randrange(
        1, cfg.vocab_size) for _ in range(p)] for p in lengths}
    out, runs = [], {}

    def report(case, p, res, **more):
        res.pop("abs_logprob_diffs", None)
        res.update(case=case, seed=seed, prompt_tokens=p, **more)
        print(json.dumps(res), flush=True)
        out.append(res)

    try:
        if a.agree:
            report("agree", 104, await serve.agree(
                engine, seed, reference.reference_logits))
        for p in lengths:
            s0 = engine.stats()
            toks, tops = await serve.greedy(engine, prompts[p], n)
            s1 = engine.stats()
            runs[p] = (toks, tops, {k: s1[k] - s0[k] for k in (
                "kv_window_pages_allocated_total",
                "kv_window_pages_released_total",
                "moe_pairs_routed_total", "moe_pairs_held_total")})
    finally:
        await engine.stop()
    # the pools go, the parameters stay: the references need the room
    params = engine.params
    for x in (engine.kv_k, engine.kv_v, *engine.wkv):
        x.delete()

    def ref_logprobs(prompt, toks, control=None):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                params, cfg, prompt + toks[:-1], last=n, control=control)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    for p in lengths:
        toks, tops, moved = runs[p]
        report("sound", p, judge(ref_logprobs(prompts[p], toks), toks, tops),
               **moved)
    if first:
        for p in a.control_prompts or a.prompts[-1:]:
            toks, tops, _ = runs[p]
            for control in a.controls:
                report(control, p, judge(
                    ref_logprobs(prompts[p], toks, control), toks, tops))
    return out


def verdict(results: list, factor: float) -> dict:
    """Sound cases pass the harness's rule; each control's median is at
    least ``factor`` times the sound median of its own seed and
    length."""
    sound = [r for r in results if r["case"] == "sound"]
    agree = [r for r in results if r["case"] == "agree"]
    controls = [r for r in results if r["case"] not in ("sound", "agree")]
    worst = max((r["median_abs_logprob_diff"] for r in sound), default=0.0)
    own = {(r["seed"], r["prompt_tokens"]): r["median_abs_logprob_diff"]
           for r in sound}
    low = [r for r in controls if r["median_abs_logprob_diff"]
           < factor * own[r["seed"], r["prompt_tokens"]]]
    best = min((r["median_abs_logprob_diff"] for r in controls),
               default=None)
    return {"ok": bool(all(r["ok"] for r in sound + agree) and not low),
            "agree_medians": [r["median_abs_logprob_diff"] for r in agree],
            "sound_failed": [[r["seed"], r["prompt_tokens"]]
                             for r in sound if not r["ok"]],
            "worst_sound_median": worst,
            "sound_medians": {str(p): sorted(
                r["median_abs_logprob_diff"] for r in sound
                if r["prompt_tokens"] == p)
                for p in sorted({r["prompt_tokens"] for r in sound})},
            "control_medians": {
                c: sorted(r["median_abs_logprob_diff"] for r in controls
                          if r["case"] == c)
                for c in sorted({r["case"] for r in controls})},
            # each control over the sound reading of its own run
            "control_ratios": {
                c: sorted(round(r["median_abs_logprob_diff"]
                                / own[r["seed"], r["prompt_tokens"]], 2)
                          for r in controls if r["case"] == c)
                for c in sorted({r["case"] for r in controls})},
            "controls_under_factor": [
                [r["case"], r["seed"], r["prompt_tokens"]] for r in low],
            # where a limit on the median would lie: above the worst
            # sound reading, under the best control
            "limit_lies_between": [worst, best]}


async def amain(a) -> int:
    import gc

    import jax

    from benchmark.harness import cells

    cell = cells.load_cell(a.workload, a.root)
    reference = cells.load_reference(cell)
    if a.controls is None:
        a.controls = list(reference.CONTROLS)
    worst_rc = 0
    own = dict(cell["weight_scales"])
    for scales in a.scales:
        cell["weight_scales"] = {**own, **scales}
        print(json.dumps({"weight_scales": cell["weight_scales"]}),
              flush=True)
        results = []
        for k, seed in enumerate(a.seeds):
            results += await seed_cases(a, seed, k == 0, cell, reference)
            # an engine's parameters have to be gone before the next
            # one's are made (tools/latent_long_context_check)
            gc.collect()
            for x in jax.live_arrays():
                x.delete()
        v = verdict(results, a.factor)
        print(json.dumps(v), flush=True)
        worst_rc |= 0 if v["ok"] else 1
    return worst_rc


def main() -> int:
    def ints(s):
        return [int(x) for x in s.split(",") if x]

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="56,3400000056,1700000056", type=ints)
    ap.add_argument("--prompts", default="512,4096,16384,32768", type=ints)
    ap.add_argument("--control-prompts", default="4096,32768", type=ints,
                    help="lengths the controls are judged at")
    ap.add_argument("--controls", default=None,
                    type=lambda s: [c for c in s.split(",") if c],
                    help="the reference's faults to try (all of CONTROLS)")
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--factor", type=float, default=2.0)
    ap.add_argument("--no-agree", dest="agree", action="store_false",
                    help="leave out serve.agree itself (a seed's first "
                    "case: what decides correct in a run)")
    ap.add_argument("--scales", default=[{}], type=json.loads,
                    help="JSON list of weight-scale sets, each tried in "
                    "turn over the configuration's")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("command_a_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
