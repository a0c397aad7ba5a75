"""Pallas TPU kernel: one token of a Mamba-1 selective scan on recurrent
state that STAYS IN ITS POOL.

The state of a sequence is float32 ``[N, d_inner]`` a layer (327,680
bytes at Jamba2-3B's widths), kept in one pool ``[slots, layers, N,
d_inner]`` that the engine owns (models/jamba.py init_state). A decode
step's recurrence reads and writes every live row's state once:

    s = exp(dt * A) * s + (dt * x) outer B        y = sum_n s * C

and nothing else: its time is the state's bytes. The XLA form of it
(models/jamba.py _ssm_step on gathered rows) moved those bytes three
more times a window: a gather of the rows out of the pool, a copy of the
gathered rows into the layer loops' carry, a scatter back (1.1 GB each
at 128 rows; PERF.md, Findings PR 36). Here the pool is an operand left
in HBM and aliased to the result; the kernel copies a row's ``[N,
d_inner]`` block from ``pool[slots[b], layer]`` into VMEM, advances it,
and copies it back to where it lay. No array of the pool's size or of
the gathered rows' is read or written by any op around it.

Form (ops/paged_attention.py _decode_kernel's, PR 32): grid = (groups of
G rows,), run in order; a ring of three VMEM slots of ``[G, N, d_inner]``;
a group's rows come in by one async copy each while the group before
computes, are advanced in place in the slot, and leave by one async copy
each while the next group computes. The copies are loops with a traced
bound (the last group may be short), not unrolled: the body is small
and lowers once whatever G is.

Rows that share a slot (the engine's padding rows all carry the drop
slot) are harmless as long as they do not advance: ``dt = 0`` writes
back the bits that were read, and a copy that races another copy of the
same bytes to the same place changes nothing. Live rows' slots are
distinct (one sequence a slot).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "selective_scan_step"    # the kernel's name in a device trace
# Rows a grid step takes. The kernel's time is its copies' (on a v5e at
# 128 rows 140 us a call with the arithmetic, 139 without it, 42 for the
# arithmetic alone; 8 / 16 / 32 rows a step within 1% of each other:
# tools/ssm_step_timing.py; PERF.md, PR 36), so the smallest whole
# block of sublanes, and the smallest ring, will do.
ROWS_PER_STEP = 8
_SLOTS = 3                      # the ring: in flight, computing, leaving


def _step_kernel(G: int,
                 # scalar prefetch
                 slots_ref, layer_ref, fresh_ref,
                 # a group's rows of dt, x [G, di] and B, C [G, N, 1];
                 # A [N, di]; the pool: whole, in HBM
                 dt_ref, x_ref, b_ref, c_ref, a_ref, pool_in,
                 y_ref, pool_out, buf, sems):
    i = pl.program_id(0)
    steps = pl.num_programs(0)
    B = slots_ref.shape[0]
    layer = layer_ref[0]

    def copies(j, do: str, out: bool):
        """Start (or wait for: ``do``) the copies of group j's rows, pool
        -> ring slot j % 3 or back; "start" and "wait" build the same
        descriptors."""
        k = jax.lax.rem(j, _SLOTS)

        def row(g, carry):
            where = slots_ref[j * G + g], layer
            if out:
                cp = pltpu.make_async_copy(buf.at[k, g], pool_out.at[where],
                                           sems.at[1, k])
            else:
                cp = pltpu.make_async_copy(pool_in.at[where], buf.at[k, g],
                                           sems.at[0, k])
            getattr(cp, do)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(B - j * G, G), row, 0)

    @pl.when(i == 0)
    def _():
        copies(i, "start", False)

    # group i - 2 left from the slot group i + 1 comes into
    @pl.when(i >= 2)
    def _():
        copies(i - 2, "wait", True)

    @pl.when(i + 1 < steps)
    def _():
        copies(i + 1, "start", False)

    copies(i, "wait", False)
    k = jax.lax.rem(i, _SLOTS)
    a = a_ref[...]                                      # [N, di]

    def row(g, carry):
        at = pl.ds(g, 1)

        # a chunk that starts a sequence starts from zeros, whatever
        # the slot held
        @pl.when(fresh_ref[i * G + g] != 0)
        def _():
            buf[k, g] = jnp.zeros(buf.shape[2:], buf.dtype)

        dt = dt_ref[at, :]                              # [1, di]
        s = (jnp.exp(dt * a) * buf[k, g]
             + (dt * x_ref[at, :]) * b_ref[g])          # [N, di]
        buf[k, g] = s
        y_ref[at, :] = jnp.sum(s * c_ref[g], axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(B - i * G, G), row, 0)
    copies(i, "start", True)

    @pl.when(i == steps - 1)
    def _():
        @pl.when(i >= 1)
        def _():
            copies(i - 1, "wait", True)

        copies(i, "wait", True)


@functools.partial(jax.jit, static_argnames=("interpret", "rows_per_step"))
def selective_scan_step(pool: jax.Array, slots: jax.Array, layer: jax.Array,
                        dt: jax.Array, x: jax.Array, b: jax.Array,
                        c: jax.Array, a_neg: jax.Array,
                        fresh: jax.Array | None = None, *,
                        interpret: bool = False,
                        rows_per_step: int | None = None):
    """One token of the recurrence for B rows whose state lies in
    ``pool[slots[b], layer]``, in place.

    pool: [S, M, N, di] float32; slots: [B] int32; ``layer`` a traced
    int32 scalar (a scalar-prefetch operand: one lowering for every layer
    of a ``lax.scan``); dt, x: [B, di]; b, c: [B, N]; a_neg: [N, di] =
    -exp(A_log).T, all float32; ``fresh`` [B] bool: rows that start from
    zeros instead of what their slot holds. Returns (pool, y [B, di]):
    models/jamba.py _ssm_step's arithmetic, float32 throughout. A row
    with dt = 0 leaves its state bit for bit; several such rows may share
    a slot (the engine's drop slot). Rows that advance must hold distinct
    slots.

    jit-ted so that every call site of a program shares ONE lowering of
    the kernel (a window calls it from three layer loops in each of its
    unrolled steps; a kernel instance costs 0.2-0.4 s to trace and lower
    at every start: PERF.md, Findings PR 32 and 34). ``rows_per_step`` is
    the handle of the tests and of tools/ssm_step_timing.py; the model
    code passes none."""
    S, M, N, di = pool.shape
    B = slots.shape[0]
    G = min(B, rows_per_step or ROWS_PER_STEP)
    if G < B:
        assert G % 8 == 0, (B, G)   # a block of rows is whole sublanes
    if fresh is None:
        fresh = jnp.zeros((B,), jnp.int32)

    def rows(i, *_):
        return (i, 0)

    def cols(i, *_):
        return (i, 0, 0)

    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(pl.cdiv(B, G),),
            in_specs=[pl.BlockSpec((G, di), rows),
                      pl.BlockSpec((G, di), rows),
                      # B and C ride sublanes, as the state's N does
                      pl.BlockSpec((G, N, 1), cols),
                      pl.BlockSpec((G, N, 1), cols),
                      pl.BlockSpec((N, di), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((G, di), rows),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((_SLOTS, G, N, di), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, _SLOTS))]),
        out_shape=[jax.ShapeDtypeStruct((B, di), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 8 (after the three prefetched scalars: dt, x, b, c, a,
        # pool) IS result 1: the rows are written where they were read
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            # groups in order: a group's copies are started by the one
            # before it and waited for by the ones after
            dimension_semantics=("arbitrary",),
            # the ring, and room for a row's temporaries and the blocks
            vmem_limit_bytes=_SLOTS * G * N * di * 4 + (8 << 20)),
        interpret=interpret,
        name=NAME,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), dt, x, b[:, :, None], c[:, :, None], a_neg,
      pool)
    return pool, y


# ------------------------------------------- a matrix of state a head
#
# Mamba-2 (models/granite.py): H heads of P channels, each head a
# [P, N] matrix of state with ONE scalar decay a head and B, C shared by
# all heads:
#
#     S[h] = exp(dt[h] A[h]) S[h] + dt[h] (x[h] outer B)     y[h] = S[h] C
#
# The pool keeps a row's layer as [N, H * P] float32 (4 MiB at
# granite-4.0-h-small's 128 x 64 x 128): the published [H, P, N] with N
# moved to the front, so that what is 8,192 wide a row (x, dt, y: H * P)
# lies along the lanes as it comes out of the projections and goes into
# the next, and what is 128 wide (B, C) is what crosses to the sublanes;
# y is a sum over sublanes. It is then the recurrence above with the
# decay a row of [1, H * P] that every state index shares, and the kernel
# below is _step_kernel's form at one row a grid step: a row is 4 MiB,
# so the ring of three slots is 12 MiB of VMEM and a copy is long enough
# to run at the memory's rate alone. The arithmetic runs over the row in
# lane chunks (nothing of a row's size is held beside the slot).
#
# With G groups (models/nemotron_h.py: 8) head h reads the B and C of
# group h // (H / G): B and C come as [N, G] a row, N on the sublanes as
# for one group and the groups along the lanes (a [G, N, 1] block would
# pad every group's column to 128 lanes: 1 MiB a row-step beside the 8
# MiB of state), and the lane chunks of group g's channels [g C / G,
# (g + 1) C / G) read column g. The groups are a Python loop at trace
# time; one group is the kernel as it was.

SSD_NAME = "ssd_step"           # the kernel's name in a device trace
_SSD_LANES = 512                # lanes of a row advanced at a time


def _ssd_kernel(slots_ref, layer_ref, fresh_ref,
                # a row's decay and dt * x [1, 1, C], B and C [1, N, 1]
                # ([1, N, G] by group); the pool: whole, in HBM
                dec_ref, dtx_ref, b_ref, c_ref, pool_in,
                y_ref, pool_out, buf, sems):
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
    k = jax.lax.rem(i, _SLOTS)
    N, C = buf.shape[1:]
    G = b_ref.shape[2]
    Cg = C // G                         # a group's channels
    W = math.gcd(Cg, _SSD_LANES)

    # a chunk that starts a sequence starts from zeros, whatever the
    # slot held
    @pl.when(fresh_ref[i] != 0)
    def _():
        buf[k] = jnp.zeros((N, C), buf.dtype)

    def advance(b, c, first):
        """The lane chunks of the channels [first, first + Cg) with b, c
        [N, 1]."""
        def lanes(j, carry):
            at = pl.ds(pl.multiple_of(first + j * W if first else j * W, W),
                       W)
            s = dec_ref[0, :, at] * buf[k, :, at] + dtx_ref[0, :, at] * b
            buf[k, :, at] = s                           # [N, W]
            y_ref[0, :, at] = jnp.sum(s * c, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, Cg // W, lanes, 0)

    if G == 1:
        advance(b_ref[0], c_ref[0], 0)
    else:
        for g in range(G):
            advance(b_ref[0, :, g:g + 1], c_ref[0, :, g:g + 1], g * Cg)
    copy(i, True).start()

    @pl.when(i == steps - 1)
    def _():
        @pl.when(i >= 1)
        def _():
            copy(i - 1, True).wait()

        copy(i, True).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step(pool: jax.Array, slots: jax.Array, layer: jax.Array,
             dec: jax.Array, dtx: jax.Array, b: jax.Array, c: jax.Array,
             fresh: jax.Array | None = None, *, interpret: bool = False):
    """One token of the head-matrix recurrence for B rows whose state
    lies in ``pool[slots[b], layer]``, in place.

    pool: [S, M, N, C] float32, C = heads x head channels; slots: [B]
    int32; ``layer`` a traced int32 scalar; dec = exp(dt * A) and dtx =
    dt * x, both [B, C] (a head's decay repeated over its channels); b,
    c: [B, N], or by group [B, G, N] (group g's are those of channels
    [g C / G, (g + 1) C / G)), all float32; ``fresh`` [B] bool: rows that
    start from zeros. Returns (pool, y [B, C]) with y[b, hp] = sum_n s[n,
    hp] c[n]:
    models/granite.py _ssd_step's arithmetic. A row with dec = 1 and dtx
    = 0 (dt = 0) leaves its state bit for bit; several such rows may
    share a slot. Rows that advance must hold distinct slots. jit-ted
    for the reason selective_scan_step is."""
    S, M, N, C = pool.shape
    B = slots.shape[0]
    if fresh is None:
        fresh = jnp.zeros((B,), jnp.int32)

    def row(i, *_):
        return (i, 0, 0)

    # B and C: a row's [N, 1], N on the sublanes; [N, G] by group
    bc = pl.BlockSpec((1, N, b.shape[1] if b.ndim == 3 else 1), row)

    def column(v):
        return v[:, :, None] if v.ndim == 2 else jnp.swapaxes(v, 1, 2)

    y, pool = pl.pallas_call(
        _ssd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((1, 1, C), row),
                      pl.BlockSpec((1, 1, C), row),
                      bc, bc,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, 1, C), row),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((_SLOTS, N, C), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, _SLOTS))]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (after the three prefetched scalars: dec, dtx, b, c,
        # pool) IS result 1: the rows are written where they were read
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_SLOTS * N * C * 4 + (8 << 20)),
        interpret=interpret,
        name=SSD_NAME,
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), dec[:, None, :], dtx[:, None, :],
      column(b), column(c), pool)
    return pool, y[:, 0]
