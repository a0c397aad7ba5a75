"""Pallas TPU kernel: the blocks of the sorted MoE dispatch as ONE
grouped matmul.

``models/llama.py moe_experts_blocked`` sorts a prefill's (token,
expert) pairs by expert and cuts every expert's run into blocks of
``block`` rows (``moe_block_plan``). Each block is one expert's MLP on
its rows:

    y = (act(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]

or, for an expert that is not gated (``w_gate`` None: two matrices, each
read once),

    y = act(x @ w_up[e]) @ w_down[e]

Off the TPU a ``fori_loop`` runs one small XLA program a block. On the
v5e that loop cannot overlap anything: a block's three weight matrices
are sliced out of the stack, multiplied and dropped before the next
block's are asked for, and thin experts (Qwen3-30B-A3B's 2,048 x 768,
granite-4.0-h-small's 4,096 x 768) stream at 105-345 GB/s of the chip's
819 (tools/moe_form_timing.py; PERF.md, PR 42).

Here the plan is the grid. Step ``j`` is block ``j``; the three expert
stacks stay in HBM as they are stored, whole (``[L, E, ...]``), and a
block's weights are chosen by the ``index_map`` from ``(layer,
block_e[j])``, which the plan hands over as prefetched scalars. So

- the pipeline fetches block j+1's expert while block j computes;
- an expert that owns consecutive blocks is fetched once (the block
  index does not change, so nothing is copied);
- a step at or past ``n_blocks`` maps to the last live block's indices,
  fetches nothing and skips its body: the grid is the worst case
  ``n_max``, the work is the live blocks'.

Where an expert's three matrices do not fit VMEM twice over (Mixtral's
4,096 x 14,336) a second grid axis tiles ``I`` and the down-projection
accumulates in the block's output, which stays in VMEM across the
tiles (``i_tile``: from D, I, the block and the bytes of an element).

Rows come in already gathered, ``[n_max * block, D]``: block j's rows
at ``j * block``; what lies past its expert's run is computed and never
read (a row's result depends on no other row). Each block writes its own
``[block, D]`` slot of the result; slots of blocks that did not run are
never written and hold whatever was there: the caller reads live pairs'
rows only.

Precision: operands reach the MXU in the dtype the weights are stored
in and accumulate in float32. For bfloat16 weights that is one bfloat16
pass, what XLA's default precision gives the loop form's float32 dots
on the chip; for float32 weights (the CPU tests) nothing is rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "moe_grouped_mlp"        # the kernel's name in a device trace
# what the tiles of one grid step may take (two buffers of every
# operand the pipeline moves, and the body's temporaries), and what the
# compiler is told it may use: a v5e core has 128 MiB
_VMEM_BYTES = 64 << 20
_VMEM_LIMIT = 100 << 20
_LANES = 128


def _vmem_bytes(block: int, D: int, ti: int, w_bytes: int) -> int:
    weights = 2 * 3 * D * ti * w_bytes
    rows = 2 * block * D * (w_bytes + 4)            # x in, y out
    body = block * ti * (3 * 4 + w_bytes) + block * D * 4
    return weights + rows + body


def i_tile(block: int, D: int, I: int, w_bytes: int) -> int:
    """Columns of ``I`` a grid step takes: all of them where that fits
    (an expert's consecutive blocks then share one fetch), else the
    largest whole number of lanes that divides ``I`` and fits."""
    if _vmem_bytes(block, D, I, w_bytes) <= _VMEM_BYTES or I % _LANES:
        return I
    fits = [t for t in range(_LANES, I, _LANES)
            if I % t == 0 and _vmem_bytes(block, D, t, w_bytes) <= _VMEM_BYTES]
    return max(fits, default=_LANES)


def _mlp_kernel(act, n_i: int,
                # scalar prefetch
                n_blocks_ref, block_e_ref, layer_ref,
                # a block's rows [block, D]; its expert's [D, ti] (the
                # gate's, where it has one), [D, ti], [ti, D]; its slot
                # of the result [block, D]
                x_ref, *refs):
    del block_e_ref, layer_ref            # the index maps read them
    *wg_ref, wu_ref, wd_ref, y_ref = refs
    gated = bool(wg_ref)
    j, it = pl.program_id(0), pl.program_id(1)

    @pl.when(j < n_blocks_ref[0])
    def _():
        x = x_ref[...]
        if gated:
            g = jnp.dot(x, wg_ref[0][...],
                        preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (act(g) * u if gated else act(u)).astype(wd_ref.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)
        if n_i == 1:
            y_ref[...] = y
            return

        @pl.when(it == 0)
        def _():
            y_ref[...] = y

        @pl.when(it > 0)
        def _():
            y_ref[...] += y


@functools.partial(jax.jit, static_argnames=("block", "act", "interpret",
                                             "tile"))
def moe_grouped_mlp(xs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, layer: jax.Array,
                    n_blocks: jax.Array, block_e: jax.Array, *, block: int,
                    act=jax.nn.silu, interpret: bool = False,
                    tile: int | None = None) -> jax.Array:
    """Every live block's expert MLP, float32 ``[n_max * block, D]``.

    xs: ``[n_max * block, D]`` in the weights' dtype, block j's rows at
    ``j * block``; w_gate (None: an expert that is not gated), w_up ``[L,
    E, D, I]`` and w_down ``[L, E, I, D]``, left where they are; layer:
    int32 scalar; n_blocks: int32 scalar, block_e: int32 ``[n_max]``
    (``moe_block_plan``). Rows of a block at or past ``n_blocks`` are not
    written. ``tile`` overrides ``i_tile`` (the tests')."""
    n_max = block_e.shape[0]
    gated = w_gate is not None
    L, E, D, I = w_up.shape
    assert xs.shape == (n_max * block, D), (xs.shape, n_max, block, D)
    assert xs.dtype == w_up.dtype, (xs.dtype, w_up.dtype)
    ti = tile or i_tile(block, D, I, w_up.dtype.itemsize)
    assert I % ti == 0, (I, ti)
    n_i = I // ti

    def at(j, it, n_blocks_ref):
        """(block, tile) whose operands step (j, it) holds: its own, or
        past the plan's end the last live block's last tile, so that a
        step which does nothing moves nothing."""
        dead = j >= n_blocks_ref[0]
        return (jnp.where(dead, jnp.maximum(n_blocks_ref[0] - 1, 0), j),
                jnp.where(dead, n_i - 1, it))

    def rows(j, it, nb, be, ly):
        return at(j, it, nb)[0], 0

    def up(j, it, nb, be, ly):
        jj, tt = at(j, it, nb)
        return ly[0], be[jj], 0, tt

    def down(j, it, nb, be, ly):
        jj, tt = at(j, it, nb)
        return ly[0], be[jj], tt, 0

    return pl.pallas_call(
        functools.partial(_mlp_kernel, act, n_i),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_max, n_i),
            in_specs=[pl.BlockSpec((block, D), rows),
                      *[pl.BlockSpec((None, None, D, ti), up)] * (1 + gated),
                      pl.BlockSpec((None, None, ti, D), down)],
            out_specs=pl.BlockSpec((block, D), rows)),
        out_shape=jax.ShapeDtypeStruct((n_max * block, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # in order: a dead step rests on the last live block's slot
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(n_blocks.reshape(1).astype(jnp.int32), block_e.astype(jnp.int32),
      layer.reshape(1).astype(jnp.int32), xs,
      *([w_gate] if gated else []), w_up, w_down)
