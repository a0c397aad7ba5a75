"""Pallas TPU kernel: one token of a causal depthwise convolution on conv
tails that are advanced WHERE THEY LIE.

What a sequence carries for a layer's short convolution is its last
``d_conv - 1`` inputs, oldest first, ``C`` channels each, in the model's
dtype: a row of ``W = (d_conv - 1) * C`` values. A program carries its
rows' tails layer-major, ``[M, B, W]`` (models/jamba.py: rows on the
sublanes, channels on the lanes), and a decode step does, a layer,

    pre   = bias + sum_k tap_k * w_k      tap_k = tail[:, k C:(k + 1) C],
                                          tap_{d_conv - 1} = x   (float32)
    tail' = [tail[:, C:], x]  where the row's token counts, else tail

and nothing else: its time is the tails' bytes, read once and written
once. In plain XLA the same shift and select on the same carried array
takes 2.6 times the bytes' time at Solar Open 2's 24,576 channels and
1.4-1.5 at the other three families' (the layer's block is sliced out,
advanced and written back by separate passes), and ``_causal_conv``'s
chunk form on tails carried rows-major, which a token's step ran until
PR 54, 4-5 times (tools/conv_step_timing.py; PERF.md, PR 55). Here
the carried array is an operand aliased to the result; a grid step takes
a block of whole rows ``[Bt, W]`` of layer ``m`` through VMEM (Pallas'
own double-buffered pipeline), reads the taps at their lane offsets
(multiples of C, itself a multiple of 128 at every served width) and
writes the block back shifted by C lanes with x at its end. The other
layers' blocks are never touched.

The arithmetic is ``_causal_conv``'s to the bit: the same float32
products summed in the same order, x rounded to the tails' dtype as the
tail takes it in. The SiLU stays outside (the caller's, in XLA), so the
result does not depend on which compiler expands the logistic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "conv_tail_step"         # the kernel's name in a device trace
# bytes of tails a grid step moves each way; with x and the result beside
# them, twice over for the pipeline, a step's VMEM stays under 24 MiB at
# every served width
_BLOCK_BYTES = 2 << 20
_CHUNK = 2048                   # lanes a pass of the arithmetic takes


def _rows_per_step(B: int, row_bytes: int) -> int:
    """Whole rows a grid step takes: the largest divisor of B that is a
    multiple of 16 (a bf16 tile's sublanes) and fits _BLOCK_BYTES, 16
    where none fits; all of B where B is no multiple of 16."""
    if B % 16:
        return B
    return max((bt for bt in range(16, B + 1, 16)
                if B % bt == 0 and bt * row_bytes <= _BLOCK_BYTES),
               default=16)


def _kernel(dc: int, C: int,
            m_ref,                              # scalar prefetch: the layer
            tail_ref, x_ref, valid_ref, w_ref, bias_ref,
            pre_ref, out_ref):
    f32 = jnp.float32
    for c0 in range(0, C, _CHUNK):
        n = min(_CHUNK, C - c0)
        # the taps in float32, x last; a tail's value comes back from
        # float32 as it went in, so the select below runs on them
        taps = [tail_ref[:, k * C + c0:k * C + c0 + n].astype(f32)
                for k in range(dc - 1)] + [x_ref[:, c0:c0 + n]]
        acc = taps[0] * w_ref[0:1, c0:c0 + n]
        for k in range(1, dc):
            acc = acc + taps[k] * w_ref[k:k + 1, c0:c0 + n]
        pre_ref[:, c0:c0 + n] = bias_ref[:, c0:c0 + n] + acc
        keep = jnp.broadcast_to(valid_ref[...], acc.shape) == 0
        for k in range(dc - 1):
            out_ref[:, k * C + c0:k * C + c0 + n] = jnp.where(
                keep, taps[k], taps[k + 1]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv_tail_step(tails: jax.Array, layer: jax.Array, x: jax.Array,
                   valid: jax.Array, w: jax.Array, bias: jax.Array, *,
                   interpret: bool = False):
    """One token of the convolution for B rows whose tails are
    ``tails[layer]``, in place.

    tails: [M, B, (dc - 1) * C] in the model's dtype; ``layer`` a traced
    int32 scalar; x: [B, C] float32, the rows' new input; valid: [B]
    bool, false for a row whose token does not count (it keeps its tail);
    w: [dc, C] and bias: [C] float32. Returns (tails, pre [B, C]
    float32) with ``pre = bias + sum_k tap_k * w[k]``, the convolution
    before its activation. jit-ted so that the window's unrolled steps
    share one trace (ops/selective_scan.py)."""
    M, B, W = tails.shape
    dc, C = w.shape
    assert W == (dc - 1) * C and x.shape == (B, C), (tails.shape, x.shape,
                                                     w.shape)
    bt = _rows_per_step(B, W * tails.dtype.itemsize)

    def rows(i, m):
        return (i, 0)

    def whole(i, m):
        return (0, 0)

    def block(i, m):
        return (m[0], i, 0)

    pre, tails = pl.pallas_call(
        functools.partial(_kernel, dc, C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // bt,),
            in_specs=[pl.BlockSpec((None, bt, W), block),
                      pl.BlockSpec((bt, C), rows),
                      pl.BlockSpec((bt, 1), rows),
                      pl.BlockSpec((dc, C), whole),
                      pl.BlockSpec((1, C), whole)],
            out_specs=[pl.BlockSpec((bt, C), rows),
                       pl.BlockSpec((None, bt, W), block)]),
        out_shape=[jax.ShapeDtypeStruct((B, C), jnp.float32),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        # operand 1 (after the prefetched layer) IS result 1: a layer's
        # rows are written where they were read
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * bt * (W * tails.dtype.itemsize + C * 4)
            + 4 * (dc + 1) * C * 4 + (8 << 20)),
        interpret=interpret,
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tails, x,
      valid.astype(jnp.int32).reshape(B, 1), w, bias.reshape(1, C))
    return tails, pre
