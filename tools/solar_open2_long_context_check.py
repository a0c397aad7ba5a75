"""The engine of ``solar-open2-250b.long-reason`` against its plain
reference at the cell's TIMED lengths, beside controls that leave out a
part of the model's mathematics, on the chip.

    chiprun --timeout 3300 -- python3 tools/solar_open2_long_context_check.py

The benchmark's own agreement check (benchmark/harness/serve.py agree)
is fixed at 96-token prompts + 8 greedy steps: inside one prefill chunk.
The cell's traffic carries the state over up to 32 prefill chunks and
1,500 decode steps, and its attention over 17,920 positions. This builds
the cell's engine exactly as benchmark/run.py does (serve.build: the
cell's engine data, weights from --seed), one engine after the other,
and asks it through ``engine.generate`` with top-20 logprobs:

  own       the cell's weights, the program as it is (on the chip: the
            KDA step kernel on the pool, the chunk kernel, the GQA
            kernels). Prompts of 512, 4,096 and 16,384 tokens prefilled
            in the cell's ``prefill_chunk``s of 512 (the state carried
            over 1, 8 and 32 chunks through the pool, K/V through their
            pages), then 1 + ``--steps`` (256) greedy tokens through the
            decode window. ``--seeds``: every seed runs the longest
            prompt, the first seed the shorter ones too. Each has to
            pass: median gap <= LONG_ATOL.
  beta01    beta left in (0, 1): ``kda_allow_neg_eigval`` ignored.
  no-gate   the attention gate left out (``use_gqa_gate`` ignored).
  roped     a rotary embedding (``rope_theta``) applied to q and k of
            the attending layer: ``use_rope`` ignored.
  bf16      every state a mixer hands back (a prefill chunk's, a decode
            step's) rounded to bfloat16: what a bf16 pool holds.
  8-bit     the same rounded to an 8-bit float (5 exponent bits, 2 of
            mantissa).
  a-head    the decay a key CHANNEL replaced by its mean over the head's
            channels: a scalar-gated delta rule.
            Each control runs the longest prompt at the first seed; the
            last three on the XLA arm (``DYN_DISABLE_PALLAS``: the rows'
            state is gathered, so the change has one place). Each has to
            read at least ``CONTROL_FACTOR`` (3) times the worst ``own``
            reading at that length AND over LONG_ATOL, but ``bf16``,
            which is reported and required of nothing (the engine's own
            bf16 activations round more over thousands of tokens than a
            bf16 state does: PERF.md, Findings PR 40 and PR 54; the
            float32 of the pool rests on tests/test_solar_open2.py).

Every case is set against the configuration's reference (its full
forward over prompt + the engine's tokens, teacher-forced, the token
recurrence from zero, softmax attention over the whole prefix in query
blocks, the last 1 + steps positions projected) and judged by
``benchmark/reference.py judge``'s median of the per-position max |d
logprob| over the engine's top-20. ``--scales`` tries weight scales in
place of the configuration's.

Prints one JSON line per case and a last line {"ok": ...}. Exits 1 where
a case that has to pass fails or a required control reads too little,
and where the platform is not a TPU (``--cpu`` lets the plumbing be
tried at a tiny size with ``--root`` a copy of the benchmark that has
such a cell).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = ("beta01", "no-gate", "roped", "bf16", "8-bit", "a-head")
REPORTED_ONLY = ("bf16",)
# CONTROL_FACTOR (3) and LONG_ATOL (0.05: between this cell's two readings
# too, my chip runs, PR 54; about.json weight_scales_why has them) are
# tools/kimi_linear_long_context_check.py's


@contextlib.contextmanager
def control(tag: str):
    """models/solar_open2.py with one part of its mathematics left out,
    for the programs traced inside."""
    import jax.numpy as jnp
    from jax import lax

    from dynamo_tpu.models import jamba, kimi_linear, llama, solar_open2

    sound = solar_open2.BLOCKS
    undo = []

    def patch(mod, name, value):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    def xla_arm():
        os.environ["DYN_DISABLE_PALLAS"] = "1"  # read by runtime/config.py

    if tag == "beta01":
        patch(solar_open2, "BLOCKS", sound._replace(
            mixer=lambda cfg, *a, **kw: sound.mixer(
                dataclasses.replace(cfg, kda_beta_scale=1.0), *a, **kw)))
    elif tag == "no-gate":
        patch(jamba, "_gated",
              lambda params, a, x, out: out.reshape(*x.shape[:2], -1))
    elif tag == "roped":
        # the rows' positions reach _qkv through the two places that know
        # them: the chunk's and the window step's attend
        at = {}
        chunk, window, qkv = (jamba.GQA.chunk, jamba.GQA.window, jamba._qkv)

        def roped_chunk(cfg, params, positions, *rest):
            at["pos"] = jnp.maximum(positions, 0)
            return chunk(cfg, params, positions, *rest)

        def roped_window(cfg, interpret, mesh):
            begin, attend_of, commit = window(cfg, interpret, mesh)

            def attend_at(w, i, pos):
                at["pos"] = jnp.maximum(pos, 0)[:, None]
                return attend_of(w, i, pos)

            return begin, attend_at, commit

        def rotated(cfg, params, a, x):
            q, k, v = qkv(cfg, params, a, x)
            inv = llama.rope_freqs(cfg)
            return (llama.apply_rope(q, at["pos"], inv),
                    llama.apply_rope(k, at["pos"], inv), v)

        patch(jamba, "_qkv", rotated)
        patch(solar_open2, "BLOCKS", sound._replace(
            attending=jamba.Attending(roped_chunk, roped_window)))
    elif tag in ("bf16", "8-bit"):
        bits = (8, 7) if tag == "bf16" else (5, 2)

        def mixer(*args, **kw):
            out, s, tail = sound.mixer(*args, **kw)
            return out, lax.reduce_precision(s, *bits), tail

        xla_arm()
        patch(solar_open2, "BLOCKS", sound._replace(mixer=mixer))
    elif tag == "a-head":
        def mean(g):
            return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)

        step, chunk = kimi_linear._kda_step, kimi_linear._kda_chunk
        xla_arm()
        patch(kimi_linear, "_kda_chunk",
              lambda s, q, k, v, g, b, c: chunk(s, q, k, v, mean(g), b, c))
        patch(solar_open2, "BLOCKS", sound._replace(
            mixer=lambda cfg, mp, u, valid, s, tail: sound.mixer(
                cfg, mp, u, valid, s, tail,
                lambda s, q, k, v, g, b: step(s, q, k, v, mean(g), b))))
    else:
        raise ValueError(f"no control {tag!r}")
    try:
        yield
    finally:
        for mod, name, value in reversed(undo):
            setattr(mod, name, value)
        os.environ.pop("DYN_DISABLE_PALLAS", None)


def main() -> int:
    # the cases, the judging and the command line are one tool's for both
    # KDA families: tools/kimi_linear_long_context_check.py
    from tools.kimi_linear_long_context_check import main as run

    return run(__doc__, "solar-open2-250b.long-reason",
               "54,3500000054,55", "512,4096,16384", CONTROLS,
               control=control, reported_only=REPORTED_ONLY)


if __name__ == "__main__":
    sys.exit(main())
