"""Seeded random weights, made on the device in ONE jitted call, in the
type they are served in.

The program's own ``init_params`` draws every tensor eagerly in float32
and casts afterwards: for Mixtral's expert tensors that is 5.6 GB of
float32 twice over beside the tensor itself, more than the chip has
left. Here the tree's shapes come from the program
(``jax.eval_shape(init_params)``, so a new parameter shows up by
itself), and each tensor with a leading layer axis is drawn slice by
slice under ``lax.map``, so at most one layer's float32 draw is alive.

Rule per leaf, by its name: ``ln_*`` / ``*_norm`` are ones, ``b*``
(biases) zeros, everything else normal with std 1/sqrt(fan_in) (fan_in =
second-to-last axis), the rule ``init_params`` uses. A configuration
names the leaves that need another scale in its about.json
(``weight_scales``: leaf name -> multiple of what the rule gives, or
``"zeros"``; harness/cells.py). One leaf gets a scale of its own from
the benchmark itself:

``lm_head``: the columns of the printable ASCII ids (32..126) keep the
rule's scale, every other column gets 1/8 of it. The byte tokenizer
decodes an id above 255 to no text at all, and the frontend sends a
content chunk only when there is text; with this head a greedy step
always lands on a printable id (the best of 95 logits of std 1 against
the best of V of std 1/8), so every engine emission reaches the client
as a content chunk of one character per token and the clients need not
ask for logprobs to be able to time chunks. The head's shape, type and
work are unchanged.

Why ``w_router`` is 2.0 in the two top-k-softmax configurations (router
logits of std ~2: where bf16 and float32 choose a different k-th expert
the swapped one is light; the agreement check reads lowest there on the
chip): benchmark/reference.py and PERF.md, Findings PR 23.
"""

from __future__ import annotations

import math

PRINTABLE = (32, 127)      # ids the byte tokenizer decodes to one ASCII char
OTHER_IDS_SCALE = 0.125


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31,
    which a 32-bit key constructor refuses)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_tree(model, cfg, key, scales):
    """The parameter tree from a key; traceable (make_params jits it,
    rehearse.py compiles it for a described chip). ``scales`` is the
    configuration's ``weight_scales``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    names = sorted(shapes)
    unknown = sorted(set(scales) - set(names))
    if unknown:
        raise ValueError(f"weight_scales names {unknown}: no such leaf "
                         f"in the tree ({names})")

    def draw(key, shape, dtype, gain=1.0):
        scale = 1.0 / math.sqrt(shape[-2]) if len(shape) > 1 else 0.02
        return (jax.random.normal(key, shape, jnp.float32)
                * (scale * gain)).astype(dtype)

    def leaf(name, key, sds):
        gain = scales.get(name, 1.0)
        if gain == "zeros" or name.startswith("b"):
            return jnp.zeros(sds.shape, sds.dtype)
        if name.startswith("ln_") or name.endswith("_norm"):
            return jnp.full(sds.shape, gain, sds.dtype)
        if name == "lm_head":
            ids = jnp.arange(sds.shape[-1])
            gain = gain * jnp.where(
                (ids >= PRINTABLE[0]) & (ids < PRINTABLE[1]), 1.0,
                OTHER_IDS_SCALE)
            return draw(key, sds.shape, sds.dtype, gain)
        if len(sds.shape) >= 3:
            return lax.map(lambda k: draw(k, sds.shape[1:], sds.dtype, gain),
                           jax.random.split(key, sds.shape[0]))
        return draw(key, sds.shape, sds.dtype, gain)

    keys = jax.random.split(key, len(names))
    return {n: leaf(n, k, shapes[n]) for n, k in zip(names, keys)}


def make_params(model, cfg, seed: int, scales: dict):
    import jax

    return jax.jit(lambda key: build_tree(model, cfg, key, scales))(
        seed_key(seed))
