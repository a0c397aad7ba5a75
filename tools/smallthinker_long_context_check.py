"""The window-and-full cell's engine against its plain reference at the
timed lengths, with two controls that must fail, on the chip.

    chiprun --timeout 3000 -- python3 tools/smallthinker_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps, where a window of 4,096
never bites and no page is given back. This builds the cell's engine as
benchmark/run.py does (serve.build: the cell's engine data, weights from
the seed) and asks it through ``engine.generate`` with top-20 logprobs:

  agree     ``serve.agree`` itself on the first seed's engine: what
            decides ``correct`` in a run of the cell. Has to pass.
  sound     prompts of ``--prompts`` (512, 4,096, 8,192, 12,288) random
            tokens through chunked prefill (window-pool pages given back
            between chunks) + 1 + ``--steps`` (256) greedy tokens through
            the decode window (pages given back between windows),
            against ``reference_logits(..., last=1 + steps)`` teacher-
            forced on the engine's tokens, judged by
            ``benchmark/reference.py judge``: its median gap, and its two
            limits (0.1 on the median, 2.5 on any position). Has to pass.
            Lengths past the window run on every seed of ``--seeds``, the
            others on the first.
  early     the same engine, the same programs, the same weights, and
            every give-back made one page early (``early_give_back``:
            the window's edge moved one page, so the oldest page a query
            may still see is gone from its table).
  ignored   an engine on the same weights whose window layers are handed
            the whole context (``window_ignored``: the configuration's
            window set to ``max_position_embeddings``, so nothing is
            masked and nothing given back).
            Both at the lengths past the window, and each has to read a
            median of at least ``--factor`` (3) times the worst sound
            median at those lengths.

A control whose greedy tokens leave the sound run's is judged against a
reference teacher-forced on its own tokens (a reference of 12.5k tokens
takes seconds on the chip); ``median_gap_to_sound_engine`` is taken over
the positions both runs drew from the same tokens.

On random weights attention is near uniform and neither control would
show; the configuration's ``weight_scales`` (about.json) were chosen
with ``--scales`` until ``ignored`` does and ``serve.agree`` still
passes. ``early`` does NOT reach the factor at any scale tried (PERF.md,
Findings PR 46): one page is 64 of the 4,096 positions a query sees, and
what it moves of the logits stays inside the bf16 quantum of the
engine's own logits (1/64 near a log-probability of -3). The tool
therefore prints beside the rule what each control moved of the sound
ENGINE's own top-20 (``median_gap_to_sound_engine``; ``--repeat`` runs
the sound engine once more, which reads 0.0 there), and the verdict
carries ``ok_but_for_early``; at the tests' size, where a page is a
quarter of the window, both controls fail against the reference
(tests/test_smallthinker.py).

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a sound case fails or a control reads under the factor, and where the
platform is not a TPU (``--cpu`` runs it there all the same: slow at the
cell's size).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import os
import random
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

def window_ignored(cfg, context: int):
    """``cfg`` with its window layers handed the whole context: the
    window as wide as ``context``, so no position is masked and no page
    lies behind it."""
    return dataclasses.replace(
        cfg, sliding_window=context,
        layer_window=tuple(None if w is None else context
                           for w in cfg.layer_window))


@contextlib.contextmanager
def early_give_back(engine):
    """Every give-back of ``engine`` made one page early: as if each row
    were a page further than it is."""
    wpm = engine.wpm
    sound = wpm.give_back

    def early(held, first, pos):
        return sound(held, first, pos + wpm.page_size)

    wpm.give_back = early
    try:
        yield
    finally:
        wpm.give_back = sound


def shared_prefix(a: list, b: list) -> int:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


async def seed_cases(a, seed: int, first: bool, cell, reference) -> list:
    """Every case of one seed: [result dicts]."""
    import jax
    import numpy as np

    from benchmark.harness import serve
    from benchmark.reference import judge
    from dynamo_tpu.engine.jax_engine import JaxEngine

    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    cfg, window = engine.cfg, engine.cfg.sliding_window
    n = 1 + a.steps
    lengths = [p for p in a.prompts if first or p > window]
    past = [p for p in lengths if p > window]
    prompts = {p: [random.Random(f"{seed}/long/{p}").randrange(
        1, cfg.vocab_size) for _ in range(p)] for p in lengths}

    def ref_logprobs(prompt, toks):
        with jax.default_matmul_precision("highest"), engine._on_device():
            logits = reference.reference_logits(
                engine.params, cfg, prompt + toks[:-1], last=n)
            return np.asarray(jax.nn.log_softmax(logits, -1))

    out, refs, sound_toks, sound_tops = [], {}, {}, {}

    def report(case, p, res, **more):
        res.pop("abs_logprob_diffs", None)
        res.update(case=case, seed=seed, prompt_tokens=p, **more)
        print(json.dumps(res), flush=True)
        out.append(res)

    async def control(eng, case, p):
        toks, tops = await serve.greedy(eng, prompts[p], n)
        # where the control's greedy tokens leave the sound run's, the
        # reference is made again, teacher-forced on the control's own
        ref = refs[p] if toks == sound_toks[p] else await asyncio.to_thread(
            ref_logprobs, prompts[p], toks)
        # beside the rule: the positions both runs drew from the same
        # tokens against the sound ENGINE's own top-20 (the same programs
        # in the same precision: nothing but the control moves it)
        m = min(shared_prefix(toks, sound_toks[p]) + 1, n)
        own = [max((abs(v - sound_tops[p][j][i])
                    for i, v in tops[j].items() if i in sound_tops[p][j]),
                   default=0.0) for j in range(m)]
        report(case, p, judge(ref, toks, tops), shared_positions=m,
               median_gap_to_sound_engine=statistics.median(own),
               max_gap_to_sound_engine=max(own))

    try:
        if first and a.agree:
            res = await serve.agree(engine, seed, reference.reference_logits)
            report("agree", 104, res)
        for p in lengths:
            s0 = engine.stats()
            toks, tops = await serve.greedy(engine, prompts[p], n)
            refs[p] = await asyncio.to_thread(ref_logprobs, prompts[p], toks)
            sound_toks[p], sound_tops[p] = toks, tops
            s1 = engine.stats()
            report("sound", p, judge(refs[p], toks, tops), **{
                k: s1[k] - s0[k] for k in (
                    "kv_window_pages_allocated_total",
                    "kv_window_pages_released_total")})
        if a.controls:
            if a.repeat:
                for p in past:      # the floor of the engine-to-engine gap
                    await control(engine, "repeat", p)
            with early_give_back(engine):
                for p in past:
                    await control(engine, "early", p)
    finally:
        await engine.stop()
    if a.controls and past:
        # the pools of the sound engine go, its parameters stay
        params = engine.params
        for x in (engine.kv_k, engine.kv_v, *engine.wkv):
            x.delete()
        context = engine.cap_tokens
        wide = JaxEngine(window_ignored(cfg, context), engine.ecfg,
                         params=params, seed=seed)
        try:
            for p in past:
                await control(wide, "ignored", p)
        finally:
            await wide.stop()
    return out


def verdict(results: list, window: int, factor: float) -> dict:
    """Sound cases pass the harness's rule; each control's median is at
    least ``factor`` times the worst sound median past the window."""
    sound = [r for r in results if r["case"] == "sound"]
    long_ = [r["median_abs_logprob_diff"] for r in sound
             if r["prompt_tokens"] > window]
    worst = max(long_, default=0.0)
    controls = [r for r in results if r["case"] in ("early", "ignored")]
    low = [r for r in controls
           if r["median_abs_logprob_diff"] < factor * worst]
    agree = [r for r in results if r["case"] == "agree"]
    sound_ok = all(r["ok"] for r in sound + agree)
    return {"ok": bool(sound_ok and not low),
            # the same rule with the early control left out of it, and
            # what that control moved of the sound ENGINE's own top-20
            # (a repeat of the sound run reads 0.0 there)
            "ok_but_for_early": bool(sound_ok and not [
                r for r in low if r["case"] != "early"]),
            "early_median_gaps_to_sound_engine": sorted(
                r["median_gap_to_sound_engine"] for r in controls
                if r["case"] == "early"),
            "agree_medians": [r["median_abs_logprob_diff"] for r in agree],
            "sound_failed": [(r["seed"], r["prompt_tokens"])
                             for r in sound if not r["ok"]],
            "worst_sound_median_past_window": worst,
            "sound_medians": sorted(r["median_abs_logprob_diff"]
                                    for r in sound),
            "control_medians": {
                c: sorted(r["median_abs_logprob_diff"] for r in controls
                          if r["case"] == c)
                for c in sorted({r["case"] for r in controls})},
            "controls_under_factor": [
                (r["case"], r["seed"], r["prompt_tokens"]) for r in low],
            "median_of_sound_medians": statistics.median(
                r["median_abs_logprob_diff"] for r in sound)
            if sound else None}


async def amain(a) -> int:
    import gc

    import jax

    from benchmark.harness import cells

    cell = cells.load_cell(a.workload, a.root)
    reference = cells.load_reference(cell)
    window = cell["model_config"]["sliding_window_size"]
    worst_rc = 0
    own = dict(cell["weight_scales"])
    for scales in a.scales:
        cell["weight_scales"] = {**own, **scales}
        print(json.dumps({"weight_scales": cell["weight_scales"]}),
              flush=True)
        results = []
        for k, seed in enumerate(a.seeds):
            results += await seed_cases(a, seed, k == 0, cell, reference)
            # an engine's parameters and pools have to be gone before
            # the next one's are made (tools/latent_long_context_check)
            gc.collect()
            for x in jax.live_arrays():
                x.delete()
        v = verdict(results, window, a.factor)
        print(json.dumps(v), flush=True)
        worst_rc |= 0 if v["ok"] else 1
    return worst_rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="smallthinker-21b-a3b.mixed-length")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--seeds", default="46,3400000046,1700000046",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--prompts", default="512,4096,8192,12288",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--factor", type=float, default=3.0)
    ap.add_argument("--no-controls", dest="controls", action="store_false")
    ap.add_argument("--no-agree", dest="agree", action="store_false",
                    help="leave out serve.agree itself (the first seed's "
                    "first case: what decides correct in a run)")
    ap.add_argument("--repeat", action="store_true",
                    help="the sound engine once more as a control: the "
                    "floor of the engine-to-engine gap")
    ap.add_argument("--scales", default=[{}], type=json.loads,
                    help="JSON list of weight-scale sets, each tried in "
                    "turn over the configuration's")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    import jax

    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu" and not a.cpu:
        print("smallthinker_long_context_check: not a TPU", file=sys.stderr)
        return 1
    return asyncio.run(amain(a))


if __name__ == "__main__":
    sys.exit(main())
