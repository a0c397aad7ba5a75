"""Pallas TPU kernel: one token of Kimi Delta Attention (a gated delta
rule) on matrix state that STAYS IN ITS POOL.

A KDA layer keeps, a head, a matrix ``S [d_k, d_v]`` float32. A token
decays it A KEY CHANNEL, corrects it by a rank-1 delta, and reads it:

    S' = Diag(exp(g)) S
    S  = S' + k (beta (v - S'^T k))^T
    o  = S^T q

The pool (models/kimi_linear.py init_state) keeps a row's layer as ``[N,
H * d_v]`` float32, N = d_k: the key channels ride the sublanes and what
is H * d_v wide a token (v, beta, o) lies along the lanes as the
projections make and take it (2 MiB at 32 heads of 128 x 128). Head h is
the lane block ``[:, h * d_v : (h + 1) * d_v]``; its q, k and decay are
COLUMNS ``[N, 1]`` of the ``[N, H]`` operands, broadcast over the block's
lanes; ``S'^T k`` and ``S^T q`` are sums over the sublanes. The delta
needs ``S'^T k`` of the WHOLE block before any element of S is final, so
a head's block is passed over twice, in VMEM (decay and reduce; update
and read out); HBM is read once and written once.

Neither of ops/selective_scan.py's kernels computes this: ``ssd_step``
has one decay a lane (a head), no reduction before its update and no
second operand on the sublanes' side.

Form: ``ssd_step``'s. The pool is an operand left in HBM and aliased to
the result; grid = (rows,), in order; a ring of three VMEM slots of one
row's ``[N, C]`` block; row i + 1 comes in and row i - 1 leaves while row
i is advanced in its slot. A row that does not advance (``dec`` = 1,
``beta`` = 0: padding, frozen by a stop) writes back the bits it read,
so such rows may share a slot (the engine's drop slot); rows that
advance hold distinct slots. A ``fresh`` row starts from zeros whatever
its slot held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "kda_step"               # the kernel's name in a device trace
_SLOTS = 3                      # the ring: in flight, computing, leaving


def _kernel(H: int,
            slots_ref, layer_ref, fresh_ref,
            # a row's q, k, exp(g) [1, N, H]; v and beta (a head's
            # repeated over its lanes) [1, 1, C]; the pool: whole, in HBM
            q_ref, k_ref, dec_ref, v_ref, beta_ref, pool_in,
            o_ref, pool_out, buf, sems):
    i = pl.program_id(0)
    steps = pl.num_programs(0)
    layer = layer_ref[0]

    def copy(j, out: bool):
        k = jax.lax.rem(j, _SLOTS)
        where = slots_ref[j], layer
        if out:
            return pltpu.make_async_copy(buf.at[k], pool_out.at[where],
                                         sems.at[1, k])
        return pltpu.make_async_copy(pool_in.at[where], buf.at[k],
                                     sems.at[0, k])

    @pl.when(i == 0)
    def _():
        copy(i, False).start()

    # row i - 2 left from the slot row i + 1 comes into
    @pl.when(i >= 2)
    def _():
        copy(i - 2, True).wait()

    @pl.when(i + 1 < steps)
    def _():
        copy(i + 1, False).start()

    copy(i, False).wait()
    slot = jax.lax.rem(i, _SLOTS)
    N, C = buf.shape[1:]
    dv = C // H

    # a chunk that starts a sequence starts from zeros, whatever the
    # slot held
    @pl.when(fresh_ref[i] != 0)
    def _():
        buf[slot] = jnp.zeros((N, C), buf.dtype)

    q, k, dec = q_ref[0], k_ref[0], dec_ref[0]          # [N, H]
    for h in range(H):      # unrolled: a head's columns are static lanes
        at = pl.ds(h * dv, dv)
        k_h = k[:, h:h + 1]                             # [N, 1]
        s = dec[:, h:h + 1] * buf[slot, :, at]          # S' [N, dv]
        u = jnp.sum(s * k_h, axis=0, keepdims=True)     # S'^T k [1, dv]
        s = s + k_h * (beta_ref[0, :, at] * (v_ref[0, :, at] - u))
        buf[slot, :, at] = s
        o_ref[0, :, at] = jnp.sum(s * q[:, h:h + 1], axis=0, keepdims=True)
    copy(i, True).start()

    @pl.when(i == steps - 1)
    def _():
        @pl.when(i >= 1)
        def _():
            copy(i - 1, True).wait()

        copy(i, True).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(pool: jax.Array, slots: jax.Array, layer: jax.Array,
             q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, fresh: jax.Array | None = None, *,
             interpret: bool = False):
    """One token of the gated delta rule for B rows whose state lies in
    ``pool[slots[b], layer]``, in place.

    pool: [S, M, N, H * dv] float32, N = d_k; slots: [B] int32; ``layer``
    a traced int32 scalar; q, k, g: [B, H, N] (g <= 0: the log of a key
    channel's decay); v: [B, H, dv]; beta: [B, H], all float32; ``fresh``
    [B] bool: rows that start from zeros. Returns (pool, o [B, H, dv]):
    models/kimi_linear.py _kda_step's arithmetic, float32 throughout. A
    row with g = 0 and beta = 0 leaves its state bit for bit; several
    such rows may share a slot. Rows that advance must hold distinct
    slots. jit-ted for the reason selective_scan_step is."""
    S, M, N, C = pool.shape
    B, H, dv = v.shape
    assert C == H * dv and q.shape == (B, H, N), (pool.shape, q.shape)
    if fresh is None:
        fresh = jnp.zeros((B,), jnp.int32)

    def row(i, *_):
        return (i, 0, 0)

    def columns(x):         # [B, H, N] -> [B, N, H]: channels to sublanes
        return jnp.swapaxes(x, 1, 2)

    o, pool = pl.pallas_call(
        functools.partial(_kernel, H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, N, H), row),
                      pl.BlockSpec((1, N, H), row),
                      pl.BlockSpec((1, N, H), row),
                      pl.BlockSpec((1, 1, C), row),
                      pl.BlockSpec((1, 1, C), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, 1, C), row),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((_SLOTS, N, C), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, _SLOTS))]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 8 (after the three prefetched scalars: q, k, dec, v,
        # beta, pool) IS result 1: the rows are written where they were
        # read
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_SLOTS * N * C * 4 + (16 << 20)),
        interpret=interpret,
        name=NAME,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), columns(q), columns(k),
      columns(jnp.exp(g)), v.reshape(B, 1, C),
      jnp.repeat(beta, dv, axis=-1)[:, None, :], pool)
    return pool, o.reshape(B, H, dv)
