#!/usr/bin/env python3
"""Beside the benchmark's own agreement rule, once, on the chip: what the
rule's 96-token prompts cannot show of a model with recurrent state.

    chiprun -- python3 tools/jamba_chip_check.py [--seed N]

Builds the engine of ``jamba2-3b.reason-decode`` (published widths, the
benchmark's weights) and judges, by ``benchmark/reference.py judge``:

1. a prompt LONGER than ``prefill_chunk`` (700 tokens: chunks of 512 and
   188, the state carried between them) and 64 decoded tokens (16
   windows) against the configuration's reference: must pass;
2. the rule's own 3 x (96 + 8) positions against a reference that DROPS
   the recurrent state at the prefill -> decode boundary (every Mamba
   mixer sees the tokens from position 96 on as a new sequence;
   attention still sees everything): must fail.

Prints one JSON line per check and exits 1 if either comes out wrong.
Exits 1 off the TPU. The builder's tool; the driver does not run it.
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

CELL = "jamba2-3b.reason-decode"
LONG_PROMPT, DECODED = 700, 64


async def amain(seed: int) -> int:
    import numpy as np

    from benchmark.harness import cells, serve
    from benchmark.reference import judge

    cell = cells.load_cell(CELL, ROOT)
    ref = cells.load_reference(cell)
    serve.device_info(1, "tpu")
    _args, (engine, _mdc, _) = await asyncio.to_thread(
        serve.build, cell, seed, serve.free_port())
    try:
        assert LONG_PROMPT > engine.ecfg.prefill_chunk
        rng = random.Random(f"{seed}/long")
        prompt = [rng.randrange(1, engine.cfg.vocab_size)
                  for _ in range(LONG_PROMPT)]
        toks, tops = await serve.greedy(engine, prompt, 1 + DECODED)
        want = await asyncio.to_thread(
            serve._reference_logprobs, engine, ref.reference_logits,
            prompt, toks)
        long_ = judge(np.asarray(want), toks, tops)
        print(json.dumps({"check": "long_prompt", "prompt": LONG_PROMPT,
                          "decoded": DECODED, **long_}), flush=True)

        whole = ref._mamba

        def dropped(cfg, params, u, m):
            import jax.numpy as jnp

            cut = serve.AGREE_PROMPT
            if u.shape[0] <= cut:
                return whole(cfg, params, u, m)
            return jnp.concatenate([whole(cfg, params, u[:cut], m),
                                    whole(cfg, params, u[cut:], m)])

        sound = await serve.agree(engine, seed, ref.reference_logits)
        ref._mamba = dropped
        try:
            lost = await serve.agree(engine, seed, ref.reference_logits)
        finally:
            ref._mamba = whole
        print(json.dumps({"check": "rule_on_sound_reference", **sound}),
              flush=True)
        print(json.dumps({"check": "rule_on_state_dropped_at_boundary",
                          **lost}), flush=True)
    finally:
        await engine.stop()
    return 0 if long_["ok"] and sound["ok"] and not lost["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2600000027)
    a = ap.parse_args()
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmark.harness import serve

    try:
        return asyncio.run(amain(a.seed))
    except serve.BenchFailed as e:
        print(f"tools/jamba_chip_check.py: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
