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
second-to-last axis), the rule ``init_params`` uses. Two leaves get a
scale of their own, for reasons that are the benchmark's:

``lm_head``: the columns of the printable ASCII ids (32..126) keep the
rule's scale, every other column gets 1/8 of it. The byte tokenizer
decodes an id above 255 to no text at all, and the frontend sends a
content chunk only when there is text; with this head a greedy step
always lands on a printable id (the best of 95 logits of std 1 against
the best of V of std 1/8), so every engine emission reaches the client
as a content chunk of one character per token and the clients need not
ask for logprobs to be able to time chunks. The head's shape, type and
work are unchanged.

``w_router``: ROUTER_GAIN times the rule's scale, see the constant.
"""

from __future__ import annotations

import math

PRINTABLE = (32, 127)      # ids the byte tokenizer decodes to one ASCII char
OTHER_IDS_SCALE = 0.125
# The router's weights are drawn at ROUTER_GAIN times the rule's scale
# (router logits of std ~2 over a unit-RMS input): a softmax over the
# chosen experts as peaked as a trained router's, so that where bf16 and
# float32 choose a different k-th expert the swapped one is light. 2 is
# where the agreement check reads lowest on both configurations on the
# chip (benchmark/reference.py; PERF.md, Findings PR 23).
ROUTER_GAIN = 2.0


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31,
    which a 32-bit key constructor refuses)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_tree(model, cfg, key):
    """The parameter tree from a key; traceable (make_params jits it,
    rehearse.py compiles it for a described chip)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    names = sorted(shapes)

    def draw(key, shape, dtype, gain=1.0):
        scale = 1.0 / math.sqrt(shape[-2]) if len(shape) > 1 else 0.02
        return (jax.random.normal(key, shape, jnp.float32)
                * (scale * gain)).astype(dtype)

    def leaf(name, key, sds):
        if name.startswith("ln_") or name.endswith("_norm"):
            return jnp.ones(sds.shape, sds.dtype)
        if name.startswith("b"):
            return jnp.zeros(sds.shape, sds.dtype)
        if name == "lm_head":
            ids = jnp.arange(sds.shape[-1])
            gain = jnp.where((ids >= PRINTABLE[0]) & (ids < PRINTABLE[1]),
                             1.0, OTHER_IDS_SCALE)
            return draw(key, sds.shape, sds.dtype, gain)
        gain = ROUTER_GAIN if name == "w_router" else 1.0
        if len(sds.shape) >= 3:
            return lax.map(lambda k: draw(k, sds.shape[1:], sds.dtype, gain),
                           jax.random.split(key, sds.shape[0]))
        return draw(key, sds.shape, sds.dtype, gain)

    keys = jax.random.split(key, len(names))
    return {n: leaf(n, k, shapes[n]) for n, k in zip(names, keys)}


def make_params(model, cfg, seed: int):
    import jax

    return jax.jit(lambda key: build_tree(model, cfg, key))(seed_key(seed))
