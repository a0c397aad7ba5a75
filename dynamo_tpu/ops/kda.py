"""Pallas TPU kernels of Kimi Delta Attention (a gated delta rule):
``kda_step``, one token on matrix state that STAYS IN ITS POOL (the
decode window's), and ``kda_chunk``, a prefill chunk's T tokens on the
gathered rows with the state and every table in VMEM (second half of
this file).

A KDA layer keeps, a head, a matrix ``S [d_k, d_v]`` float32. A token
decays it A KEY CHANNEL, corrects it by a rank-1 delta, and reads it:

    S' = Diag(exp(g)) S
    S  = S' + k (beta (v - S'^T k))^T
    o  = S^T q

**kda_step.** The pool (models/kimi_linear.py init_state) keeps a row's
layer as ``[N, H * d_v]`` float32, N = d_k: the key channels ride the
sublanes and what is H * d_v wide a token (v, beta, o) lies along the
lanes as the projections make and take it (2 MiB at 32 heads of 128 x
128). Head h is the lane block ``[:, h * d_v : (h + 1) * d_v]``; its q, k
and decay are COLUMNS ``[N, 1]`` of the ``[N, H]`` operands, broadcast
over the block's lanes; ``S'^T k`` and ``S^T q`` are sums over the
sublanes. The delta needs ``S'^T k`` of the WHOLE block before any
element of S is final, so a head's block is passed over twice, in VMEM
(decay and reduce; update and read out); HBM is read once and written
once.

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

**kda_chunk.** models/kimi_linear.py's chunked form (its docstring: for a
chunk of Q tokens entered with S_in, ``A_ts = beta_t sum_i k_t[i] k_s[i]
exp(G_t[i] - G_s[i])``, s < t, the same table P with q_t, s <= t, ``V~ =
(I + A)^-1 (beta v - (beta k exp(G)) S_in)``, ``o = (q exp(G)) S_in + P
V~``, ``S_out = Diag(exp(G_Q)) S_in + (k exp(G_Q - G))^T V~``) as ONE
kernel. Grid (rows, heads, T / TILE), the last axis in order: a
head's S (64 KiB) is read from ``s0``'s lane block at its first grid
step into VMEM (the block of the result, which does not move while the
head's steps run), advanced there through every chunk of the T tokens
and written once after its last; q, k, g, beta k, beta v come as
``[1, TILE, 128]`` blocks at lane block h of the ``[B, T, H * d]`` arrays the
projections make (beta multiplies rows only, so it rides in on k and v
and no operand has H on its lanes). A grid step makes the tables of its
TILE = 128 tokens, two chunks of Q = CHUNK = 64 side by side on the
diagonal of ``[128, 128]`` matrices, then runs the chunks in order.
Nothing of size Q x Q x d_k reaches HBM, which is what held the XLA form
to Q = 16.

* Decays: the log decays are summed within a sub-block of C = SUB = 16
  tokens only (log-step adds down the sublanes); every exponent the
  kernel takes is a sum of such partial sums and whole sub-blocks'
  totals, each <= 0, so nothing overflows whatever is drawn, and an
  exponent is as exact as its own size allows however strong the decay
  before it in the chunk was.
* Diagonal sub-blocks keep the exact differenced form ``exp(G_t - G_s)``,
  s <= t, on the vector unit, a column s at a time (``[8, 128]`` tiles, a
  lane reduction each). Off-diagonal sub-blocks are one matrix product a
  chunk of operands factored around the LATER sub-block's first token
  r: ``(x_t exp(G_t - G_r)) . (k_s exp(G_r - G_s))``, s < r <= t.
* The solve has no dependent step through HBM and no power of A. The 16
  x 16 unit-lower diagonal blocks are inverted by forward substitution
  on the vector unit, all eight of a tile in lock step (their columns
  are kept as columns: 15 multiply-subtracts of two vregs); the blocks
  are then merged by doubling, ``[[Y_a, 0], [-Y_b A_ba Y_a, Y_b]]``, two
  products a level whose left operands are the later halves' rows only.
* The three products with the state run at Q = 64 rows (XLA: 16).

Precision: float32 operands and state, every product
``lax.dot_general(..., precision=HIGHEST, preferred_element_type=
float32)``. Mosaic lowers that to ``tpu.matmul`` with
``#tpu.contract_precision<fp32>`` (jax/_src/pallas/mosaic/lowering.py
_dot_general_lowering_rule; any precision but DEFAULT and HIGHEST is
refused there), its float32 contraction; no operand is rounded to
bfloat16 by this file and no term is left out. Chip-side evidence: the
kernel against ``_kda_chunk`` (XLA, ``Precision.HIGHEST``) on the cell's
shapes reads 3e-6 of the largest value in state and output
(tools/kda_chunk_timing.py; tests/test_tpu_compile.py states the bound),
where one single-pass bfloat16 product reads 1e-2, and
tools/kimi_linear_long_context_check.py passes with it in. CHUNK and
SUB are set from the chip's timing (PERF.md, Findings PR 53). The loops
over sub-blocks and columns are unrolled in Python: the scheduler hides
the vector unit's tables under the products (in a ``fori_loop`` they
would run exposed), at the price of ~1 s to trace the kernel a program.
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


# ------------------------------------------------- a prefill chunk's scan

CHUNK_NAME = "kda_chunk"        # the second kernel's name in a device trace
TILE = 128      # tokens a grid step: every table is [TILE, TILE]
CHUNK = 64      # Q: tokens a pass of the state (tools/kda_chunk_timing.py)
SUB = 16        # C: the sub-block whose table keeps the differenced form
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = ((1,), (1,))      # a . b^T: both contracted over their lanes


def _dot(a, b, dims=((1,), (0,))):
    """a . b contracted over ``dims``, float32 operands multiplied at
    Precision.HIGHEST (Mosaic: #tpu.contract_precision<fp32>), float32
    accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _pieces(Q: int, C: int):
    """Where the off-diagonal pieces of a chunk's tables lie in the one
    product that makes them: for each sub-block j >= 1 of a chunk, (j,
    tile, offset): the keys of the chunk's tokens [0, j * C), taken
    around sub-block j's first token, are rows [offset, offset + j * C)
    of tile ``tile`` of the stacked right operand; no piece straddles a
    tile of TILE lanes. Returns (pieces, number of tiles)."""
    out, fill = [], []
    for j in range(1, Q // C):
        for tile, used in enumerate(fill):
            if used + j * C <= TILE:
                break
        else:
            tile, used = len(fill), 0
            fill.append(0)
        out.append((j, tile, used))
        fill[tile] = used + j * C
    return out, len(fill)


def _chunk_kernel(Q: int, C: int,
                  # a head's [1, TILE, d] blocks of q, k, the log decay,
                  # beta k and beta v; its entry state [1, dk, dv]
                  q_ref, k_ref, g_ref, bk_ref, bv_ref, s0_ref,
                  o_ref, s_ref):
    f32 = jnp.float32

    # the state stays in VMEM from the head's first token to its last:
    # its output block is the same at every grid step of the head, so it
    # is advanced there and written to HBM once, after the last
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[0] = s0_ref[0]

    q, k, g, bk, bv = q_ref[0], k_ref[0], g_ref[0], bk_ref[0], bv_ref[0]
    W, nb, dk = TILE, Q // C, g.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, W), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, W), 0)

    def of(i, n):           # which run of n (a power of two) holds token i
        return jax.lax.shift_right_logical(i, n.bit_length() - 1)

    def block(x, J):        # sub-block J's rows
        return x[J * C:(J + 1) * C]

    # --- the log decays, summed WITHIN a sub-block only (log-step adds of
    # the rows above, down the sublanes): G_t from its sub-block's first
    # token, ``rest`` from t to its last. Every exponent below is a sum of
    # these and of whole sub-blocks' totals, each <= 0, so it is as exact
    # as its own size allows however strong the decay before it was
    G, n = g, 1
    token = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) & (C - 1)
    while n < C:
        G = G + jnp.where(token >= n, pltpu.roll(G, n, 0), 0.0)
        n *= 2
    total = [G[J * C + C - 1:(J + 1) * C] for J in range(W // C)]   # [1, dk]
    rest = [jnp.minimum(total[J] - block(G, J), 0.0) for J in range(W // C)]

    def span(lo, hi):       # the decay over the whole sub-blocks [lo, hi)
        return sum(total[lo + 1:hi], total[lo]) if hi > lo else 0.0

    # --- the diagonal sub-blocks: the differenced form, a column at a
    # time. P's go to their lanes of the [W, W] table; A's columns are
    # kept AS COLUMNS, column s of every sub-block side by side over the
    # sub-blocks' lanes (a_col[s][t, (J, .)] = A_J[t, s], t > s), which
    # is what the substitution below multiplies by
    p_rows = []
    a_col = [[jnp.zeros((8, W), f32) for _ in range(C // 8)]
             for _ in range(C)]
    for J in range(W // C):
        Gj, kj, bkj, qj = (block(x, J) for x in (G, k, bk, q))
        p_acc = [jnp.zeros((8, W), f32) for _ in range(C // 8)]
        mine = of(lane, C) == J
        for s in range(C):
            here = lane == J * C + s
            # rows above the column's own eight lie above the diagonal
            for h in range(s // 8, C // 8):
                at = slice(8 * h, 8 * h + 8)
                # exp(G_t - G_s) k_s for t >= s; what lies above the
                # diagonal (t < s: clamped to exp(0)) is masked
                e = jnp.exp(jnp.minimum(Gj[at] - Gj[s:s + 1], 0.0)) \
                    * kj[s:s + 1]
                a = jnp.sum(e * bkj[at], axis=1, keepdims=True)
                p = jnp.sum(e * qj[at], axis=1, keepdims=True)
                below = mine & (sub + 8 * h > s) if h == s // 8 else mine
                a_col[s][h] = jnp.where(below, a, a_col[s][h])
                p_acc[h] = jnp.where(here, p, p_acc[h])
        p_rows.append(p_acc)

    # --- (I + D)^-1 of the diagonal sub-blocks D, all of them at once:
    # forward substitution by columns on the vector unit. x[t, (J, j)] =
    # X_J[t, j] starts as the identity; column s takes row s, final by
    # then, out of every row below it
    x = [jnp.where(sub + 8 * h == (lane & (C - 1)), 1.0, 0.0).astype(f32)
         for h in range(C // 8)]
    for s in range(C - 1):
        x_s = x[s // 8][s % 8:s % 8 + 1]
        for h in range(s // 8, C // 8):
            x[h] = x[h] - a_col[s][h] * x_s
    inv = jnp.where(of(row, C) == of(col, C),
                    jnp.concatenate(x * (W // C), axis=0), 0.0)

    # --- the off-diagonal sub-blocks: a product of factored operands a
    # chunk. Around the LATER sub-block's first token r: (x_t exp(G_t -
    # G_r)) . (k_s exp(G_r - G_s)), s < r <= t, both exponents <= 0
    pieces, tiles = _pieces(Q, C)
    l_rows = [[jnp.zeros((8, W), f32) for _ in range(C // 8)]
              for _ in range(W // C)]
    for c in range(W // Q if pieces else 0):
        J0, c0 = c * nb, c * Q
        later = slice(c0 + C, c0 + Q)       # the chunk but its first block
        e_in = jnp.exp(jnp.minimum(jnp.concatenate(
            [block(G, J) - block(G, J)[:1] for J in range(J0 + 1, J0 + nb)],
            axis=0), 0.0))
        right = [[] for _ in range(tiles)]
        for j, tile, _ in pieces:
            first = block(G, J0 + j)[:1]    # sub-block j's own first token
            right[tile] += [
                block(k, J0 + i) * jnp.exp(
                    rest[J0 + i] + (first + span(J0 + i + 1, J0 + j)))
                for i in range(j)]
        for t in right:
            n = sum(y.shape[0] for y in t)
            if n < W:
                t.append(jnp.zeros((W - n, dk), f32))
        off = _dot(jnp.concatenate([bk[later] * e_in, q[later] * e_in],
                                   axis=0),
                   jnp.concatenate([y for t in right for y in t], axis=0),
                   _NT)                         # [2 (Q - C), tiles * W]
        for j, tile, at in pieces:
            here = (lane >= c0) & (lane < c0 + j * C)
            for rows, base in ((l_rows, 0), (p_rows, Q - C)):
                # the piece's lanes [at, at + j C) to [c0, c0 + j C)
                y = pltpu.roll(off[base + (j - 1) * C:base + j * C,
                                   tile * W:(tile + 1) * W],
                               (c0 - at) % W, 1)
                for h in range(C // 8):
                    rows[J0 + j][h] = jnp.where(here, y[8 * h:8 * h + 8],
                                                rows[J0 + j][h])
    P = jnp.where(col <= row, jnp.concatenate(
        [y for acc in p_rows for y in acc], axis=0), 0.0)

    # --- (I + A)^-1, A = D + L with L the rest of a chunk's triangle, by
    # doubling: two inverted blocks Y_a, Y_b of n tokens and the block
    # A_ba under them make [[Y_a, 0], [-Y_b A_ba Y_a, Y_b]], every pair of
    # the tile in two products whose left operands are the LATER halves'
    # rows alone
    if pieces:
        L = jnp.concatenate([y for acc in l_rows for y in acc], axis=0)
    n = C
    while n < Q:
        later = [slice(b + n, b + 2 * n) for b in range(0, W, 2 * n)]
        a_ba = jnp.where(
            (of(row, 2 * n) == of(col, 2 * n)) & (of(col, n) & 1 == 0), L, 0.0)
        t = _dot(jnp.concatenate([a_ba[at] for at in later], axis=0), inv)
        zeros = jnp.zeros((n, W), f32)
        t = _dot(jnp.concatenate([inv[at] for at in later], axis=0),
                 jnp.concatenate([y for i in range(len(later))
                                  for y in (zeros, t[i * n:(i + 1) * n])],
                                 axis=0))
        inv = jnp.concatenate(
            [y for i, at in enumerate(later)
             for y in (inv[at.start - n:at.start],
                       inv[at] - t[i * n:(i + 1) * n])], axis=0)
        n *= 2

    # --- the chunks in order: three products with the state each
    for c in range(W // Q):
        J0, at = c * nb, slice(c * Q, (c + 1) * Q)

        def whole(y):       # a chunk's rows among zeros: [W, d]
            def zeros(n):
                return [jnp.zeros((n, y.shape[1]), f32)] if n else []
            return jnp.concatenate(
                zeros(c * Q) + [y] + zeros(W - (c + 1) * Q), axis=0)

        S = s_ref[0]                                    # [dk, dv]
        # exp(G_t) from the chunk's first token: what of S reaches token t
        e_in = jnp.exp(jnp.concatenate(
            [block(G, J0 + j) + span(J0, J0 + j) for j in range(nb)], axis=0))
        in_s = _dot(jnp.concatenate([bk[at] * e_in, q[at] * e_in], axis=0),
                    S)                                  # [2 Q, dv]
        vt = _dot(inv[at], whole(bv[at] - in_s[:Q]))    # [Q, dv]
        o_ref[0, at] = in_s[Q:] + _dot(P[at], whole(vt))
        # the keys as they leave the chunk (decayed from t to its last
        # token) and the chunk's whole decay, turned to columns in one
        # transpose: [dk, Q] and [dk, 1]
        k_out = jnp.concatenate(
            [block(k, J0 + j) * jnp.exp(
                rest[J0 + j] + span(J0 + j + 1, J0 + nb)) for j in range(nb)]
            + [jnp.broadcast_to(span(J0, J0 + nb), (W - Q, dk))], axis=0).T
        s_ref[0] = jnp.exp(k_out[:, Q:Q + 1]) * S + _dot(k_out[:, :Q], vt)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def kda_chunk(s0: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
              g: jax.Array, beta: jax.Array, *, chunk: int = CHUNK,
              sub: int = SUB, interpret: bool = False):
    """T tokens of the gated delta rule for B rows from their carried
    state: models/kimi_linear.py _kda_chunk's operands and results.

    s0: [B, N, H * dv] float32, N = d_k (the pool's layout); q, k, g: [B,
    T, H, dk] (g <= 0); v: [B, T, H, dv]; beta: [B, T, H], all float32, g
    and beta 0 at a token that does not count. Returns (s after each
    row's last counted token, o [B, T, H, dv]). ``chunk`` and ``sub`` are
    the timing tool's to vary."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    assert s0.shape == (B, dk, H * dv), (s0.shape, q.shape, v.shape)
    assert TILE % (2 * chunk) == 0 and chunk % sub == 0 and sub % 8 == 0 \
        and chunk & (chunk - 1) == 0 and sub & (sub - 1) == 0, (chunk, sub)
    pad = -T % TILE

    def lanes(x):           # [B, T, H, d] -> [B, T + pad, H * d]
        x = x.reshape(B, T, H * x.shape[-1])
        # tokens that do not count: they move no state
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def tokens(d):
        return pl.BlockSpec((1, TILE, d), lambda b, h, i: (b, i, h))

    def state():
        return pl.BlockSpec((1, dk, dv), lambda b, h, i: (b, 0, h))

    o, s = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk, sub),
        grid=(B, H, (T + pad) // TILE),
        in_specs=[tokens(dk), tokens(dk), tokens(dk), tokens(dk),
                  tokens(dv), state()],
        out_specs=[tokens(dv), state()],
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=CHUNK_NAME,
    )(lanes(q), lanes(k), lanes(g), lanes(beta[..., None] * k),
      lanes(beta[..., None] * v), s0)
    return s, o[:, :T].reshape(B, T, H, dv)
