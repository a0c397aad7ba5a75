"""Pallas TPU kernels: paged GQA attention, a decode step and a chunk of
queries (prefill), and latent (MLA) attention over its two pools.

The decode kernel is the serving hot loop. The XLA fallback
(models/llama.py _paged_attention) gathers every sequence's pages into a
dense [B, S, KV, hd] tensor each decode step — O(B·S) HBM traffic
through an intermediate buffer. This kernel instead walks the page
table (scalar-prefetched, read inside the body), copies each needed page
HBM→VMEM exactly once, and runs an online-softmax (flash) accumulation
on-chip for ALL heads of the sequence at once:

  grid = (batch,); per row a loop over its chunks of G pages (G by
  shape: _decode_pages_per_step), the pools left in HBM and a chunk's
  pages brought in by async copies while the chunk before it computes;
  per chunk: q·Kᵀ for every GQA group (MXU, batched over the leading KV
  axis — the pool layout [N, KV, ps, hd] is chosen so no in-kernel
  transpose is needed) → running max/sum rescale → acc += softmax·V,
  output written when the row's loop ends.

The loop runs over the pages that hold positions [lower, length) and no
others: a page past a row's end or before its sliding window costs no
copy, no grid step and no compute, and a padding row costs one empty
grid step. A call's time therefore follows the pages its rows own, not
the padded page-table width (one grid step a slot of the table, as this
kernel had it until PR 32, took 0.19 us a slot for the pipeline's
bookkeeping alone: 5-20% of the bytes' roofline in the benchmark's cells).

The prefill kernel (paged_attention_prefill, T > 1) is the same form
for a block of one row's queries: grid (batch, blocks of the chunk), the
pages between the first position the block's earliest query can see and
its last query's own, all KV heads a step. A padding row, a page past a
block's causal edge and a page wholly before its sliding window cost
nothing, where the XLA path gathers every slot of the table for every
row and makes float32 scores over all of it.

This is the role block_copy.cu + the engines' paged-attention CUDA
kernels play in the reference (SURVEY §2.3), expressed TPU-natively.

Correctness contract (tests/test_ops.py): exact match with the XLA gather
path in float32, masking by sequence length, page-0 padding convention
(page_table rows padded with 0s; rows with length 0 produce zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite "masked" value: keeps exp() NaN-free
NO_WINDOW = 1 << 30  # "infinite" effective sliding window (int32-safe)


def effective_window(window, is_sliding, B: int):
    """Per-row effective sliding window for the kernels: ``window`` on
    sliding layers, :data:`NO_WINDOW` on global ones. ``is_sliding`` is
    a traced scalar bool (Gemma-2 layer parity under lax.scan)."""
    return jnp.broadcast_to(
        jnp.where(is_sliding, jnp.int32(window), jnp.int32(NO_WINDOW)),
        (B,))


# Cached tokens a chunk of the decode kernel's row loop takes, and the
# VMEM a chunk's K and V may hold. One layer's call, device ms at 1 / 2 /
# 4 / 8 / 16 / 32 pages a chunk, then the parent's kernel (a grid step a
# slot of the page table) and the XLA gather arm
# (tools/paged_attn_timing.py, my chip run, PR 32; rows and contexts as
# the cells' traffic draws them; "-": past the chip's VMEM):
#   cell 1, B 32 x P 64, KV 8, 14 rows live, 93 pages:
#       0.073 0.056 0.052 0.053 0.057 -     | 0.520 | 3.51
#   cell 2, B 64 x P 32, KV 4, 64 rows, 443 pages:
#       0.242 0.161 0.119 0.105 0.102 0.120 | 0.645 | 1.84
#   cell 3, B 32 x P 64, KV 8, 14 rows, 381 pages:
#       0.254 0.177 0.152 0.154 0.156 -     | 0.652 | 3.51
#   cell 4, B 128 x P 32, KV 1, 128 rows, 1,667 pages:
#       0.818 0.497 0.328 0.236 0.204 0.200 | 1.389 | 0.80
# A copy costs about as much as a page of one KV head's bytes, so many
# small pages (KV 1) want many a chunk; past ~2,048 (token, KV head) rows
# a chunk nothing is gained and the masked positions of short rows cost.
DECODE_TOKENS_PER_STEP = 1024
_DECODE_VMEM_BYTES = 6 << 20


def _decode_pages_per_step(P: int, KV: int, ps: int, hd: int,
                           itemsize: int) -> int:
    """Pages a chunk: DECODE_TOKENS_PER_STEP tokens' worth, at most the
    table's width, halved while K and V of a chunk (two slots each in the
    pools' type, one float32 upcast each) pass _DECODE_VMEM_BYTES: 16 a
    chunk at KV 1, 8 at KV 4, 4 at KV 8 for bf16 pages of 64 x 128."""
    G = max(1, min(P, DECODE_TOKENS_PER_STEP // ps))
    while G > 1 and KV * G * ps * hd * (4 * itemsize + 8) > _DECODE_VMEM_BYTES:
        G //= 2
    return G


def _page_copies(do: str, n, source, ps: int, pools, bufs, sems, slot):
    """Start (or wait for: ``do``) the async copies of a chunk's first
    ``n`` pages, one a pool a page, into rows [g * ps, (g + 1) * ps) of
    ``bufs[i][slot]``; ``source(pool, g)`` is page g's slice of the pool
    in HBM. A loop, not unrolled copies: the program stays small, and
    "start" and "wait" build the same descriptors."""
    def page(g, carry):
        at = pl.ds(pl.multiple_of(g * ps, ps), ps)
        for i, (pool, buf) in enumerate(zip(pools, bufs)):
            getattr(pltpu.make_async_copy(
                source(pool, g), buf.at[slot, :, at, :],
                sems.at[i, slot]), do)()
        return carry

    jax.lax.fori_loop(0, n, page, 0)


def _reset_row(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _attend(q, k, v, pos0, lower, length, scale: float,
            softcap: float | None, m_ref, l_ref, acc_ref):
    """One online-softmax update of a row's running (m, l, acc) by T
    cached positions pos0 .. pos0 + T - 1, of which [lower, length) are
    visible. q: [KV, group, hd], k / v: [KV, T, hd], all float32."""
    KV, group, hd = q.shape
    H = KV * group
    # batched over the shared leading KV axis (MXU, no transposes)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale    # [KV, group, T]
    if softcap:  # Gemma-2 score softcap — BEFORE masking
        s = softcap * jnp.tanh(s / softcap)
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    valid = jnp.logical_and(pos >= lower, pos < length)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1].reshape(KV, group, 1)
    l_prev = l_ref[:, :1].reshape(KV, group, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                    # [KV, group, 1]
    # exp only where valid: an all-masked block (possible when the
    # sliding window empties the pool view) would otherwise compute
    # exp(NEG_INF - NEG_INF) = 1 and corrupt the running sum
    p_exp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_prev + jnp.sum(p_exp, axis=2, keepdims=True)
    pv = jax.lax.dot_general(
        p_exp, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # [KV, group, hd]
    acc_ref[...] = acc_ref[...] * alpha.reshape(H, 1) + pv.reshape(H, hd)
    m_ref[...] = jnp.broadcast_to(m_new.reshape(H, 1), m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new.reshape(H, 1), l_ref.shape)


def _write_row(o_ref, stats_out, m_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[:, :1], 1e-9)  # length-0 (padding) rows → 0
    o_ref[...] = (acc_ref[...] / l).reshape(o_ref.shape).astype(o_ref.dtype)
    if stats_out:
        stats_out[0][...] = m_ref[...]
        stats_out[1][...] = l_ref[...]


def _decode_kernel(ps: int, G: int, P: int, scale: float,
                   softcap: float | None,
                   # scalar prefetch
                   layer_ref, pt_ref, len_ref, lo_ref,
                   # q: one row's block; the pools: whole, in HBM
                   q_ref, k_hbm, v_hbm, o_ref, *rest):
    """One grid step = one ROW: a loop over the chunks of G pages that
    hold its positions [lower, length), each chunk's pages copied
    HBM->VMEM by G async copies a pool into one [KV, G * ps, hd] buffer
    (two slots: chunk j + 1, or the next row's first chunk, is in flight
    while chunk j computes), then ONE online-softmax update over the
    chunk's G * ps positions. A page past the row's end is not copied
    (its stale slot is masked), so time follows the pages rows own."""
    *stats_out, kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    B = pl.num_programs(0)
    layer = layer_ref[0]

    def span(r):
        # the row's pages [first, end) and its chunks, counted from first
        # (lax.div: nothing here is negative, and `//` on traced ints
        # lowers through a sign helper that costs more to trace than
        # the rest of the kernel)
        first = jax.lax.div(lo_ref[r], ps)
        end = jnp.minimum(jax.lax.div(len_ref[r] + (ps - 1), ps), P)
        return first, end, jax.lax.div(
            jnp.maximum(end - first, 0) + (G - 1), G)

    def copies(r, j, slot, do: str):
        # chunk j of row r: a copy a pool for each page it has
        first, end, _ = span(r)
        p0 = first + j * G
        _page_copies(do, jnp.clip(end - p0, 0, G),
                     lambda pool, g: pool.at[layer, pt_ref[r, p0 + g]],
                     ps, (k_hbm, v_hbm), (kbuf, vbuf), sems, slot)

    first, _, n = span(b)

    @pl.when(b == 0)
    def _():
        # a page that is not copied leaves its slot as it was: masked
        # scores may be anything, but 0 * v must not be NaN
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

    base = slot_ref[0]  # the slot the row's first chunk is copied to

    # the row before starts this row's first chunk beside its own last;
    # the first row, and one after a row with nothing to read, start it
    @pl.when(jnp.logical_or(b == 0, span(jnp.maximum(b - 1, 0))[2] == 0))
    def _():
        copies(b, 0, base, "start")

    _reset_row(m_ref, l_ref, acc_ref)
    q = q_ref[...].astype(jnp.float32)

    def chunk(j, carry):
        slot = (base + j) & 1
        more = j + 1 < n

        # in flight while this chunk computes: the row's next chunk, or
        # after its last the next row's first
        @pl.when(jnp.logical_or(more, b + 1 < B))
        def _():
            copies(jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0),
                   1 - slot, "start")

        copies(b, j, slot, "wait")
        _attend(q, kbuf[slot].astype(jnp.float32),
                vbuf[slot].astype(jnp.float32), (first + j * G) * ps,
                lo_ref[b], len_ref[b], scale, softcap, m_ref, l_ref, acc_ref)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)
    slot_ref[0] = (base + n) & 1
    _write_row(o_ref, stats_out, m_ref, l_ref, acc_ref)


def _decode_kernel_narrow(ps: int, scale: float, softcap: float | None,
                          layer_ref, pt_ref, len_ref, lo_ref,
                          q_ref, k_ref, v_ref, o_ref, *rest):
    """Head dims that are no multiple of 128 lanes (64: run.py --model
    1b): the chip's compiler refuses every slice of such a pool in HBM
    ("Slice shape along dimension 4 must be aligned to tiling (128)"), so
    no copy of _decode_kernel's can be written. Here the pipeline brings
    the pages: grid (B, P), one page a step as a block whose index map
    reads the table, steps outside [lower, length) skipped."""
    del layer_ref, pt_ref
    *stats_out, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        _reset_row(m_ref, l_ref, acc_ref)

    @pl.when(jnp.logical_and(p * ps < len_ref[b], (p + 1) * ps > lo_ref[b]))
    def _():
        _attend(q_ref[...].astype(jnp.float32),
                k_ref[...].astype(jnp.float32),
                v_ref[...].astype(jnp.float32), p * ps, lo_ref[b],
                len_ref[b], scale, softcap, m_ref, l_ref, acc_ref)

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        _write_row(o_ref, stats_out, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "return_stats",
                                    "softcap"))
def paged_attention_decode(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, scale: float | None = None,
                           interpret: bool = False,
                           return_stats: bool = False,
                           softcap: float | None = None,
                           lower: jax.Array | None = None):
    """One decode step of paged GQA attention.

    q: [B, H, hd]; k_pages/v_pages: [num_pages, KV, ps, hd];
    page_table: [B, P] int32 (pad with 0 — page 0 is reserved);
    lengths: [B] int32 — tokens of context per row INCLUDING the one just
    written (rows with length 0 are padding and return zeros).
    Returns [B, H, hd] in q.dtype; with ``return_stats`` also the online-
    softmax running stats (m, l) as float32 [B, H] so a caller can merge
    this result with attention over extra keys outside the pool (the fused
    decode window's in-flight buffer — models/llama.py
    _pool_window_attention_pallas).
    """
    # thin wrapper: a 4-D pool is the layered kernel with L=1 (the [None]
    # reshape is metadata-only — no copy)
    return paged_attention_decode_layered(
        q, k_pages[None], v_pages[None], jnp.zeros((), jnp.int32),
        page_table, lengths, scale=scale, interpret=interpret,
        return_stats=return_stats, softcap=softcap, lower=lower)


def paged_attention_decode_sharded(q: jax.Array, k_pools: jax.Array,
                                   v_pools: jax.Array, layer: jax.Array,
                                   page_table: jax.Array,
                                   lengths: jax.Array, *, mesh,
                                   scale: float | None = None,
                                   interpret: bool = False,
                                   return_stats: bool = True,
                                   softcap: float | None = None,
                                   lower: jax.Array | None = None):
    """Tensor-parallel wrapper: runs the layered kernel per model-shard
    via shard_map over the head axis. The KV pool is sharded
    [L, pages, KV@model, ps, hd] (parallel/mesh.py kv_cache_pspec) and q
    heads follow their kv heads (GQA groups never straddle shards while
    num_kv_heads % tp == 0), so each shard's kernel call is the ordinary
    single-chip kernel on its local heads — no collectives inside; the
    surrounding GSPMD program keeps the output head-sharded into wo.
    Batch rows ride the "data" axis. Replaces r2's allow_pallas=False
    fallback that dropped the kernel the moment TP was on (VERDICT r2
    weak #5). With ``return_stats`` (the fused-window caller's merge
    input) returns (out, m, l); without, just ``out`` — the K=1 decode
    path skips the two [B, H, 128] f32 stat outputs per call."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if lower is None:
        lower = jnp.zeros_like(lengths)

    def local(q_, k_, v_, l_, t_, ln_, lo_):
        return paged_attention_decode_layered(
            q_, k_, v_, l_, t_, ln_, scale=scale, interpret=interpret,
            return_stats=return_stats, softcap=softcap, lower=lo_)

    out_specs = (P("data", "model", None), P("data", "model"),
                 P("data", "model")) if return_stats \
        else P("data", "model", None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P("data", "model", None),
                  P(None, None, "model", None, None),
                  P(None, None, "model", None, None),
                  P(), P("data", None), P("data"), P("data")),
        out_specs=out_specs,
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )(q, k_pools, v_pools, jnp.asarray(layer, jnp.int32), page_table,
      lengths, lower)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "return_stats",
                                    "softcap", "pages_per_step"))
def paged_attention_decode_layered(q: jax.Array, k_pools: jax.Array,
                                   v_pools: jax.Array, layer: jax.Array,
                                   page_table: jax.Array,
                                   lengths: jax.Array, *,
                                   scale: float | None = None,
                                   interpret: bool = False,
                                   return_stats: bool = False,
                                   softcap: float | None = None,
                                   lower: jax.Array | None = None,
                                   pages_per_step: int | None = None):
    """paged_attention_decode against ONE layer of the stacked pools.

    k_pools/v_pools: [L, num_pages, KV, ps, hd]; ``layer`` a traced int32
    scalar. The layer rides as a scalar-prefetch operand and the pools
    stay in HBM: the kernel copies pages of that layer straight out of
    the stacked pool — no [num_pages, ...] layer slice is ever
    materialized. That matters because XLA materializes `pool[l]`
    (≈200 MB/layer at serving sizes) when it feeds a pallas_call, and a
    K-step fused decode window would pay that copy L·K times per window
    (measured: ~30 ms/step at B=32 — 4x the whole model's weight
    bandwidth); this variant makes the pool read O(live pages) as the
    kernel intends.

    ``pages_per_step`` (exactly that many, up to the table's width) is
    the handle of the tests and of tools/paged_attn_timing.py; the model
    code passes none and runs _decode_pages_per_step's rule by shape.
    A head dim that is no multiple of 128 runs _decode_kernel_narrow
    (the same arithmetic, a page a grid step; the handle means nothing
    there)."""
    B, H, hd = q.shape
    L, _, KV, ps, _ = k_pools.shape
    P = page_table.shape[1]
    group = H // KV
    if scale is None:
        scale = hd ** -0.5
    q4 = q.reshape(B, KV, group, hd)
    if lower is None:
        lower = jnp.zeros_like(lengths)

    def row(b, *_):
        return (b, 0, 0, 0)

    out_shape = [jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype)]
    out_specs = [pl.BlockSpec((None, KV, group, hd), row)]
    if return_stats:
        out_shape += [jax.ShapeDtypeStruct((B, H, 128), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((None, H, 128),
                                   lambda b, *_: (b, 0, 0))] * 2
    running = [pltpu.VMEM((H, 128), jnp.float32),
               pltpu.VMEM((H, 128), jnp.float32),
               pltpu.VMEM((H, hd), jnp.float32)]
    if hd % 128 == 0:
        G = min(P, pages_per_step) if pages_per_step else \
            _decode_pages_per_step(P, KV, ps, hd, k_pools.dtype.itemsize)
        kernel = functools.partial(_decode_kernel, ps, G, P, scale, softcap)
        grid = (B,)
        # rows in order: a row's last chunk starts the next row's first
        semantics = ("arbitrary",)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((2, KV, G * ps, hd), k_pools.dtype),
                   pltpu.VMEM((2, KV, G * ps, hd), v_pools.dtype),
                   pltpu.SemaphoreType.DMA((2, 2)),
                   pltpu.SMEM((1,), jnp.int32)] + running
    else:
        def page_index(b, p, l, pt, ln, lo):
            # pages outside [lower, length) re-point at the first NEEDED
            # page (index unchanged between steps: no fetch)
            needed = jnp.logical_and(p * ps < ln[b], (p + 1) * ps > lo[b])
            first = jnp.minimum(lo[b] // ps, P - 1)
            return (l[0], jnp.where(needed, pt[b, p], pt[b, first]),
                    0, 0, 0)

        kernel = functools.partial(_decode_kernel_narrow, ps, scale, softcap)
        grid = (B, P)
        semantics = ("parallel", "arbitrary")
        pool_spec = pl.BlockSpec((None, None, KV, ps, hd), page_index)
        scratch = running

    res = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=[pl.BlockSpec((None, KV, group, hd), row),
                      pool_spec, pool_spec],
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        # the name a device trace shows the kernel under: what
        # benchmark/harness/trace.py DECODE_KERNEL_OP matches
        name="paged_attention_decode_layered",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      lower.astype(jnp.int32),
      q4, k_pools, v_pools)
    out = res[0].reshape(B, H, hd)
    if return_stats:
        return out, res[1][:, :, 0], res[2][:, :, 0]
    return out


# ------------------------------------------------ latent (MLA) attention

# Cached tokens a chunk of the decode kernel's row loop takes (and a grid
# step of the blocked kernel, which is prefill's), and query rows (tokens x
# heads) a block of the blocked kernel holds. One layer's decode call,
# device ms at 4 / 8 / 16 (/ 32) pages a chunk, then the blocked kernel at
# 1,024 tokens a step (this entry's form until PR 39) and the XLA arm
# (tools/latent_attn_timing.py, my chip run, PR 39; B 64 rows of 8,257-8,833
# tokens, 32 heads, bf16; the bytes' floor 0.78 ms, 0.37 with 34 rows empty):
#   pages of 128, 64 rows live:  1.157 1.003 1.033       | 1.378 | 1.527
#   pages of 128, 30 rows live:  0.573 0.504 0.531       | 1.094 | 1.527
#   pages of  64, 64 rows live:  1.774 1.304 1.091 1.037 | 2.160 | 1.625
#   pages of  64, 30 rows live:  0.857 0.639 0.540 0.533 | 1.861 | 1.625
# A turn of the row loop costs ~0.3 us beside its arithmetic (4 -> 8 pages
# of 128: 512 turns fewer, 0.15 ms), a copy's issue 0.01-0.02 us (pages of
# 64 against 128 at the same tokens a chunk); past 1,024 tokens a chunk the
# last chunk's masked tail costs what the turns save (a row of 8.5k tokens
# computes 10,240 positions at 2,048 a chunk, 9,216 at 1,024). Pages of 64
# would want 2,048; the cell with this family has pages of 128.
LATENT_TOKENS_PER_STEP = 1024
LATENT_BLOCK_ROWS = 1024
DECODE_NAME = "latent_attention_decode_layered"
PREFILL_NAME = "latent_attention_prefill_layered"


def _latent_kernel(ps: int, G: int, scale: float,
                   l_ref, pt_ref, len_ref,
                   ql_ref, qr_ref, *refs):
    """One grid step: G pages of one row against one block of query rows
    (all heads of one token in decode, heads x tokens of a chunk in
    prefill). refs: G latent pages [ps, r], G rope pages [ps, dw], then
    acc / m / l and the scratch (m, l, acc)."""
    del l_ref, pt_ref
    c_refs, r_refs = refs[:G], refs[G:2 * G]
    o_ref, m_out, l_out, m_ref, l_ref2, acc_ref = refs[2 * G:]
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref2[...] = jnp.zeros_like(l_ref2)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # a step wholly past the row's context: no compute, and its index
    # maps name the blocks the row's last live step fetched (no traffic)
    @pl.when(s * G * ps < length)
    def _():
        # the step's pages as ONE [G * ps, *] block: one matmul a product
        ccat = jnp.concatenate([c[...] for c in c_refs], axis=0)
        rcat = jnp.concatenate([r[...] for r in r_refs], axis=0)
        nt = (((1,), (1,)), ((), ()))                   # a . b^T
        sc = (jax.lax.dot_general(ql_ref[...], ccat, nt,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_ref[...], rcat, nt,
                                    preferred_element_type=jnp.float32)
              ) * scale                                 # [M, G * ps]
        pos = s * G * ps + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        valid = pos < length
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_ref[:, :1]                           # [M, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref2[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(ccat.dtype), ccat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [M, r]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref2[...] = jnp.broadcast_to(l_new, l_ref2.shape)

    @pl.when(s == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...]
        m_out[...] = m_ref[...]
        l_out[...] = l_ref2[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "pages_per_step", "block_rows",
                                             "name"))
def latent_attention_layered(q_lat: jax.Array, q_rope: jax.Array,
                             c_pools: jax.Array, r_pools: jax.Array,
                             layer: jax.Array, page_table: jax.Array,
                             lengths: jax.Array, *, scale: float,
                             interpret: bool = False,
                             pages_per_step: int | None = None,
                             block_rows: int = LATENT_BLOCK_ROWS,
                             name: str = PREFILL_NAME):
    """Latent (MLA, absorbed) attention of M query rows a batch row
    against what ONE layer of the stacked latent and rope pools holds
    of that row: its positions < lengths[b], the same for all M.

    q_lat: [B, M, r] (q_nope . W_UK); q_rope: [B, M, dw]; M is tokens x
    heads of a prefill chunk, whose queries all see the whole cached
    prefix (or the heads of one token: the tests, the timing tool).
    c_pools: [L, pages, 1, ps, r]; r_pools: [L, pages, 1, ps, dw];
    ``layer`` a traced int32 scalar (a scalar-prefetch operand, as
    paged_attention_decode_layered has it); page_table: [B, P];
    lengths: [B] (0: nothing in the pool).

    There is ONE latent "KV head": every page is read once for all the
    rows of a block, the score is q_lat . c + q_rope . k_r, and the value
    is the latent itself (a prefix of the key). Returns a PART, for the
    caller's merge with the attention over the program's own tokens:
    acc [B, M, r] float32 = sum_j exp(s_j - m) c_j (NOT divided by l),
    m [B, M] the maximum and l [B, M] the sum of exp(s_j - m); a row
    with nothing in the pool gives (0, NEG_INF, 0). W_UV is applied
    outside. Operands go to the MXU in the pools' type with float32
    accumulation; softmax runs in float32.

    ``pages_per_step`` and ``block_rows`` are the handles of the tests
    (steps that divide nothing, several blocks at a small size) and of
    tools/latent_attn_timing.py; latent_attention_prefill_layered, which
    is what models/mla.py calls, takes neither and runs the measured
    constants. A decode step has an entry and a kernel of its own
    (latent_attention_decode_layered).

    Grid (B, M / block_rows, ceil(P / G)): a step takes G =
    ``pages_per_step`` pages of the row (default: LATENT_TOKENS_PER_STEP
    tokens' worth), each pool passed G times with an index map of its
    own, so a page does not cost a grid step. Steps past a row's context
    re-point at the blocks its last live step fetched and are skipped;
    they still cost their operands' bookkeeping: about 0.1 us an operand
    a grid step, 2 a page, whatever the page's size (my chip runs,
    PR 31). A block of 1,024 query rows hides that behind its matmuls;
    the 32 rows of a decode step did not (1.38 ms a layer at B 64 x 8.5k
    tokens and pages of 128, 2.27 at pages of 64, for 0.78 ms of bytes),
    which is why decode left this form (PR 39)."""
    B, M, r = q_lat.shape
    dw = q_rope.shape[-1]
    ps = c_pools.shape[3]
    P = page_table.shape[1]
    G = max(1, min(pages_per_step or LATENT_TOKENS_PER_STEP // ps, P))
    steps = (P + G - 1) // G
    mb = min(block_rows, M)
    assert M % mb == 0, (M, mb)

    def page_index(j):
        def index(b, q, s, l, pt, ln):
            n = (ln[b] + ps - 1) // ps                  # live pages
            last = jnp.maximum(n - 1, 0)
            p = jnp.minimum(jnp.minimum(s, last // G) * G + j, last)
            return (l[0], pt[b, jnp.minimum(p, P - 1)], 0, 0, 0)
        return index

    def rows(b, q, s, l, pt, ln):
        return (b, q, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M // mb, steps),
        in_specs=[pl.BlockSpec((None, mb, r), rows),
                  pl.BlockSpec((None, mb, dw), rows)]
        + [pl.BlockSpec((None, None, None, ps, r), page_index(j))
           for j in range(G)]
        + [pl.BlockSpec((None, None, None, ps, dw), page_index(j))
           for j in range(G)],
        out_specs=[pl.BlockSpec((None, mb, r), rows),
                   pl.BlockSpec((None, mb, 128), rows),
                   pl.BlockSpec((None, mb, 128), rows)],
        scratch_shapes=[pltpu.VMEM((mb, 128), jnp.float32),
                        pltpu.VMEM((mb, 128), jnp.float32),
                        pltpu.VMEM((mb, r), jnp.float32)],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_kernel, ps, G, scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, M, r), jnp.float32),
                   jax.ShapeDtypeStruct((B, M, 128), jnp.float32),
                   jax.ShapeDtypeStruct((B, M, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # a block of 1,024 rows holds its scores, its probabilities
            # and three [rows, r] float32 buffers: past the default limit
            vmem_limit_bytes=(64 << 20) if mb > 128 else None),
        interpret=interpret,
        # the name a device trace shows the kernel under (what
        # benchmark/metrics/latent_attn_roofline.py matches)
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat.astype(c_pools.dtype), q_rope.astype(r_pools.dtype),
      *([c_pools] * G), *([r_pools] * G))
    return acc, m[:, :, 0], l[:, :, 0]


def _latent_decode_kernel(ps: int, G: int, P: int, scale: float,
                          # scalar prefetch
                          layer_ref, pt_ref, len_ref,
                          # q: one row's blocks; the pools: whole, in HBM
                          ql_ref, qr_ref, c_hbm, r_hbm, o_ref, m_out, l_out,
                          cbuf, rbuf, sems, slot_ref, m_ref, l_ref, acc_ref):
    """One grid step = one ROW, in _decode_kernel's form: a loop over the
    chunks of G pages that hold its positions < length, each chunk's pages
    copied HBM->VMEM by G async copies a pool into one [1, G * ps, *]
    buffer (two slots: chunk j + 1, or the next row's first chunk, is in
    flight while chunk j computes), then ONE online-softmax update over
    the chunk's G * ps positions, in _latent_kernel's arithmetic. A page
    past the row's end is not copied (its stale slot is masked), and a
    row with nothing in the pool costs one empty grid step."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    layer = layer_ref[0]

    def span(r):
        # the row's pages [0, end) and its chunks (lax.div: see
        # _decode_kernel)
        end = jnp.minimum(jax.lax.div(len_ref[r] + (ps - 1), ps), P)
        return end, jax.lax.div(end + (G - 1), G)

    def copies(r, j, slot, do: str):
        # chunk j of row r: a copy a pool for each page it has
        p0 = j * G
        _page_copies(do, jnp.clip(span(r)[0] - p0, 0, G),
                     lambda pool, g: pool.at[layer, pt_ref[r, p0 + g]],
                     ps, (c_hbm, r_hbm), (cbuf, rbuf), sems, slot)

    n = span(b)[1]

    @pl.when(b == 0)
    def _():
        # a page that is not copied leaves its slot as it was: masked
        # scores may be anything, but the latent is the VALUE too, and
        # 0 * c must not be NaN
        cbuf[...] = jnp.zeros_like(cbuf)
        slot_ref[0] = 0

    base = slot_ref[0]  # the slot the row's first chunk is copied to

    # the row before starts this row's first chunk beside its own last;
    # the first row, and one after a row with nothing to read, start it
    @pl.when(jnp.logical_or(b == 0, span(jnp.maximum(b - 1, 0))[1] == 0))
    def _():
        copies(b, 0, base, "start")

    _reset_row(m_ref, l_ref, acc_ref)
    length = len_ref[b]

    def chunk(j, carry):
        slot = (base + j) & 1
        more = j + 1 < n

        # in flight while this chunk computes: the row's next chunk, or
        # after its last the next row's first
        @pl.when(jnp.logical_or(more, b + 1 < B))
        def _():
            copies(jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0),
                   1 - slot, "start")

        copies(b, j, slot, "wait")
        ccat, rcat = cbuf[slot, 0], rbuf[slot, 0]       # [G * ps, *]
        nt = (((1,), (1,)), ((), ()))                   # a . b^T
        sc = (jax.lax.dot_general(ql_ref[...], ccat, nt,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_ref[...], rcat, nt,
                                    preferred_element_type=jnp.float32)
              ) * scale                                 # [H, G * ps]
        pos = j * (G * ps) + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        valid = pos < length
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_ref[:, :1]                           # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(ccat.dtype), ccat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [H, r]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)
    slot_ref[0] = (base + n) & 1
    o_ref[...] = acc_ref[...]
    m_out[...] = m_ref[...]
    l_out[...] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "pages_per_step"))
def latent_attention_decode_layered(q_lat: jax.Array, q_rope: jax.Array,
                                    c_pools: jax.Array, r_pools: jax.Array,
                                    layer: jax.Array, page_table: jax.Array,
                                    lengths: jax.Array, *, scale: float,
                                    interpret: bool = False,
                                    pages_per_step: int | None = None):
    """One decode step: q_lat [B, H, r], q_rope [B, H, dw], the heads of
    one token a row; 32 heads against the one latent head. Pools, table,
    lengths and the PART returned (acc [B, H, r] float32 not divided by
    l, m, l; a row with nothing in the pool (0, NEG_INF, 0)) are
    latent_attention_layered's, and so is the arithmetic.

    The form is paged_attention_decode_layered's (PR 32): grid (B,), the
    pools passed once and left in HBM, the kernel copying each row's own
    pages in chunks of G = LATENT_TOKENS_PER_STEP tokens' worth
    (``pages_per_step``: the handle of the tests and of
    tools/latent_attn_timing.py; the model code passes none). Blocked
    operands (latent_attention_layered, this entry's form until PR 39)
    cost ~0.1 us each a grid step whatever they move, 2 a page: as much
    as the bytes of a 128-token page."""
    B, H, r = q_lat.shape
    dw = q_rope.shape[-1]
    ps = c_pools.shape[3]
    P = page_table.shape[1]
    G = max(1, min(pages_per_step or LATENT_TOKENS_PER_STEP // ps, P))

    def row(b, *_):
        return (b, 0, 0)

    acc, m, l = pl.pallas_call(
        functools.partial(_latent_decode_kernel, ps, G, P, scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[pl.BlockSpec((None, H, r), row),
                      pl.BlockSpec((None, H, dw), row),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, H, r), row),
                       pl.BlockSpec((None, H, 128), row),
                       pl.BlockSpec((None, H, 128), row)],
            scratch_shapes=[pltpu.VMEM((2, 1, G * ps, r), c_pools.dtype),
                            pltpu.VMEM((2, 1, G * ps, dw), r_pools.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, r), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, r), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 128), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # rows in order: a row's last chunk starts the next row's first
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the name a device trace shows the kernel under (what
        # benchmark/metrics/latent_attn_roofline.py matches)
        name=DECODE_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat.astype(c_pools.dtype), q_rope.astype(r_pools.dtype),
      c_pools, r_pools)
    return acc, m[:, :, 0], l[:, :, 0]


def latent_attention_prefill_layered(q_lat, q_rope, c_pools, r_pools, layer,
                                     page_table, lengths, *, scale,
                                     interpret=False):
    """A prefill chunk against its cached prefix: q_lat [B, T, H, r],
    q_rope [B, T, H, dw], every query of row b seeing the pool's
    positions < lengths[b] (the chunk's own tokens are the caller's).
    Blocks of LATENT_BLOCK_ROWS (token, head) rows keep their scores on
    the chip and fill the MXU's rows, which the heads of one token do
    not; a padding row (length 0) costs its grid steps and nothing
    else."""
    B, T, H, r = q_lat.shape
    acc, m, l = latent_attention_layered(
        q_lat.reshape(B, T * H, r), q_rope.reshape(B, T * H, -1), c_pools,
        r_pools, layer, page_table, lengths, scale=scale,
        interpret=interpret, name=PREFILL_NAME)
    return acc.reshape(B, T, H, r), m.reshape(B, T, H), l.reshape(B, T, H)


# ------------------------------------------------------- prefill kernel

# A chunk of queries (T > 1) against the pages its rows own: the decode
# kernel's form, for a block of queries. Cached tokens a chunk of the row
# loop takes, query rows (tokens x group) a KV head a block holds, and the
# VMEM a block's buffers may take. One layer's call, device ms of the whole
# program (the two transposes around the kernel included), at <tokens a
# block> x <pages a chunk> 32x8 / 64x8 / 128x8 / 256x8 / 128x4 / 128x16,
# then the rule below, the kernel this one replaced (a grid step a slot of
# the table for every KV head, float32 operands; "-": past the chip's VMEM)
# and the XLA gather arm (tools/paged_attn_timing.py --arms prefill, my
# chip run, PR 34; the cells' largest prefill batch, live rows as
# (first position, tokens), the other rows padding):
#   cell 1, PB 4 x T 512, P 64, KV 8 x 4, rows (0, 410) (0, 512):
#       0.224 0.224 0.185 0.179 0.202 0.245 | 0.179 | 0.919 | 8.54
#   cell 2, PB 8 x T 256, P 32, KV 4 x 8, rows (0, 226) (0, 166):
#       0.130 0.105 0.108 0.102 0.096 0.133 | 0.108 | 0.375 | 4.08
#   cell 3, PB 4 x T 512, P 64, KV 8 x 4, rows (1536, 167) (1536, 177):
#       0.311 0.303 0.282 0.268 0.353 0.247 | 0.268 | 2.047 | 8.54
#   cell 4, PB 8 x T 512, P 32, KV 1 x 20, four rows of 331-495 from 0:
#       0.351 0.352 0.342 0.349 0.346 0.398 | 0.351 | -     | 3.14
#   cell 6, PB 4 x T 256, P 64, KV 4 x 8 (packed), three rows of 167-227
#   after 3,072: 0.721 0.499 0.553 0.555 0.756 0.492 | 0.553 | 2.462 | 3.97
#   every row live: cell 1 0.340 (XLA 8.54), cell 2 0.264 (4.08).
# Short contexts want chunks of 4-8 pages (a chunk's masked tail costs),
# 3k cached tokens 8-16; a block re-reads the row's pages, so few large
# blocks, up to what VMEM holds beside the lane-wide statistics. Cell 6 is
# bound by the arithmetic (0.15 ms at the chip's bf16 peak), the others by
# moving q and the output of every row of the bucket, live or not.
PREFILL_TOKENS_PER_STEP = 512
PREFILL_BLOCK_ROWS = 1024
_PREFILL_VMEM_BYTES = 40 << 20
_PREFILL_VMEM_LIMIT = 64 << 20


def _prefill_vmem_bytes(tq: int, G: int, group: int, KV: int, ps: int,
                        hd: int, itemsize: int) -> int:
    """What a block of tq tokens and chunks of G pages hold in VMEM: q and
    the output (two slots each, the pipeline's), acc and the lane-wide
    (m, l) in float32, the positions' column, K and V of a chunk (two
    slots each), and a KV head's scores, mask and probabilities."""
    R, C = tq * group, G * ps
    return (KV * R * (hd * (4 * itemsize + 4) + 2 * 128 * 4) + 2 * R * 128 * 4
            + 4 * KV * C * hd * itemsize + R * C * (12 + itemsize))


def _prefill_sizes(T: int, group: int, KV: int, P: int, ps: int, hd: int,
                   itemsize: int) -> tuple:
    """(tokens a query block, pages a chunk) by shape: the chunk of
    PREFILL_TOKENS_PER_STEP tokens, at most the table; the block halved
    from the whole chunk of queries while a KV head has more than
    PREFILL_BLOCK_ROWS rows in it or the buffers pass _PREFILL_VMEM_BYTES
    (rows stay a multiple of 16 sublanes): 256 tokens at group 4, 128 at
    group 8, 32 at group 20 (640 rows) for T 512."""
    G = max(1, min(P, PREFILL_TOKENS_PER_STEP // ps))
    tq = T
    while (tq % 2 == 0 and (tq // 2 * group) % 16 == 0
           and (tq * group > PREFILL_BLOCK_ROWS
                or _prefill_vmem_bytes(tq, G, group, KV, ps, hd, itemsize)
                > _PREFILL_VMEM_BYTES)):
        tq //= 2
    return tq, G


def _prefill_kernel(ps: int, G: int, scale: float, softcap: float | None,
                    block: int,
                    # scalar prefetch
                    pt_ref, first_ref, end_ref, win_ref,
                    # one block of one row's queries; the pools: whole, HBM
                    q_ref, qpos_ref, k_hbm, v_hbm, o_ref,
                    kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref):
    """One grid step = one BLOCK of one row's queries, all KV heads: a
    loop over the chunks of G pages that hold the positions the block can
    see (pages [first, end) of the row's table, worked out per block
    outside), each chunk's pages copied HBM->VMEM as _decode_kernel
    copies them (two slots: the next chunk, or the next step's first, is
    in flight while one computes), then one online-softmax update a KV
    head over the chunk's G * ps positions. Operands go to the MXU in the
    pools' type; scores, statistics and the accumulator are float32. Rows
    are a KV head's (token, group-head) pairs, flattened outside. kv slot
    j of table entry p holds position p * ps + j, visible to a query at
    position t iff t - window < p * ps + j <= t; with ``block`` > 1 (the
    block mask of generation by diffusion: llama._visible) the upper
    edge is the end of t's block of ``block`` positions, not t."""
    KV, R, hd = q_ref.shape
    C = G * ps
    b, nq = pl.program_id(0), pl.num_programs(1)
    step = b * nq + pl.program_id(1)
    steps = pl.num_programs(0) * nq

    def chunks(s):
        # lax.div: nothing here is negative (see _decode_kernel)
        return jax.lax.div(
            jnp.maximum(end_ref[s] - first_ref[s], 0) + (G - 1), G)

    def copies(s, j, slot, do: str):
        # chunk j of step s: a copy a pool for each page it has
        row = jax.lax.div(s, nq)
        p0 = first_ref[s] + j * G
        _page_copies(do, jnp.clip(end_ref[s] - p0, 0, G),
                     lambda pool, g: pool.at[pt_ref[row, p0 + g]],
                     ps, (k_hbm, v_hbm), (kbuf, vbuf), sems, slot)

    n = chunks(step)

    @pl.when(step == 0)
    def _():
        # a page that is not copied leaves its slot as it was: masked
        # scores may be anything, but 0 * v must not be NaN
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

    base = slot_ref[0]  # the slot the step's first chunk is copied to

    # the step before starts this step's first chunk beside its own last;
    # the first step, and one after a step with nothing to read, start it
    @pl.when(jnp.logical_or(step == 0, chunks(jnp.maximum(step - 1, 0)) == 0))
    def _():
        copies(step, 0, base, "start")

    @pl.when(n == 0)
    def _():  # padding: nothing to read, zeros out
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _():
        _reset_row(m_ref, l_ref, acc_ref)
        q_pos = qpos_ref[...]                              # [R, 1]
        seen = q_pos - win_ref[b]                          # window's edge
        if block > 1:
            # the last position of the query's own block (lax.rem: a
            # padding query at -1 keeps seeing nothing)
            q_pos = jnp.where(
                q_pos >= 0,
                q_pos - jax.lax.rem(q_pos, block) + (block - 1), -1)

        def chunk(j, carry):
            slot = (base + j) & 1
            more = j + 1 < n

            @pl.when(jnp.logical_or(more, step + 1 < steps))
            def _():
                copies(jnp.where(more, step, step + 1),
                       jnp.where(more, j + 1, 0), 1 - slot, "start")

            copies(step, j, slot, "wait")
            kv_pos = (first_ref[step] + j * G) * ps \
                + jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)

            def head(kv, carry):
                s = jax.lax.dot_general(
                    q_ref[kv], kbuf[slot, kv], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [R, C]
                if softcap:  # Gemma-2 score softcap — BEFORE masking
                    s = softcap * jnp.tanh(s / softcap)
                valid = jnp.logical_and(kv_pos <= q_pos, kv_pos > seen)
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[kv][:, :1]                  # [R, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # exp only where valid: a query that sees nothing of the
                # chunk would otherwise add exp(0) = 1 a position
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_new = alpha * l_ref[kv][:, :1] \
                    + jnp.sum(p, axis=1, keepdims=True)
                v = vbuf[slot, kv]
                acc_ref[kv] = acc_ref[kv] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)    # [R, hd]
                m_ref[kv] = jnp.broadcast_to(m_new, (R, 128))
                l_ref[kv] = jnp.broadcast_to(l_new, (R, 128))
                return carry

            jax.lax.fori_loop(0, KV, head, 0)
            return carry

        jax.lax.fori_loop(0, n, chunk, 0)
        # a query that saw nothing (padding inside a live row) -> 0
        l = jnp.maximum(l_ref[:, :, :1], 1e-9)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)

    slot_ref[0] = (base + n) & 1


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "softcap",
                                             "block_tokens",
                                             "pages_per_step", "block"))
def paged_attention_prefill(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            q_positions: jax.Array, *,
                            scale: float | None = None,
                            interpret: bool = False,
                            softcap: float | None = None,
                            eff_win: jax.Array | None = None,
                            block_tokens: int | None = None,
                            pages_per_step: int | None = None,
                            block: int = 1) -> jax.Array:
    """A chunk of queries against the paged pool (flash form): what
    models/llama.py _attention runs for T > 1 on a TPU.

    q: [B, T, H, hd] (the current chunk); k_pages/v_pages:
    [num_pages, KV, ps, hd], the chunk's K/V already written;
    page_table: [B, P]; q_positions: [B, T] absolute (-1 padding);
    ``eff_win`` [B]: the per-row effective sliding window. Returns
    [B, T, H, hd] in q.dtype: llama._paged_attention's arithmetic
    (operands in the pool's type, float32 accumulation and statistics,
    probabilities cast to V's type, softcap before the mask), without its
    dense [B, P * ps, KV, hd] gather and its float32 scores over every
    slot of the table. A query at -1 gives zeros.

    Grid (B, T / block): a step a block of one row's queries, which reads
    the pages between the first position its earliest query can see and
    its last query's own, and no others: time follows the live rows and
    what they hold. ``block_tokens`` (a divisor of T) and
    ``pages_per_step`` are the handles of the tests and of
    tools/paged_attn_timing.py; the model code passes neither and runs
    _prefill_sizes' rule by shape. ``block`` > 1: the mask is causal
    across blocks of that many positions and bidirectional inside one, so
    a query sees to the end of its own block (whose K/V the caller has
    written with the chunk's) and a block of queries reads up to L - 1
    positions further."""
    B, T, H, hd = q.shape
    _, KV, ps, _ = k_pages.shape
    P = page_table.shape[1]
    group = H // KV
    if scale is None:
        scale = hd ** -0.5
    tq, G = _prefill_sizes(T, group, KV, P, ps, hd, k_pages.dtype.itemsize)
    tq = block_tokens or tq
    G = min(P, pages_per_step or G)
    assert T % tq == 0, (T, tq)
    nq, R = T // tq, tq * group
    # kernel rows: a KV head's (token, group-head) pairs, token-major,
    # so every block is 2-D a head with the head dim in lanes
    q4 = q.astype(k_pages.dtype).reshape(B, T, KV, group, hd).transpose(
        0, 2, 1, 3, 4).reshape(B, KV, T * group, hd)
    pos = q_positions.astype(jnp.int32)
    # a query's position as a column (positions ride sublanes, like the
    # score rows they mask)
    qpos = jnp.repeat(pos, group, axis=1)[:, :, None]  # [B, T * group, 1]
    if eff_win is None:
        eff_win = jnp.full((B,), jnp.int32(NO_WINDOW))
    eff_win = eff_win.astype(jnp.int32)
    # a block's pages: from the first position its earliest live query
    # can see to its last query's own; a block of padding has none
    blocks = pos.reshape(B, nq, tq)
    hi = jnp.max(blocks, axis=2) + 1
    if block > 1:
        hi = jnp.where(hi > 0, (hi + block - 1) // block * block, hi)
    lo = jnp.min(jnp.where(blocks >= 0, blocks, NO_WINDOW), axis=2) + 1 \
        - eff_win[:, None]
    first = jnp.clip(lo, 0, hi) // ps
    end = jnp.minimum((hi + ps - 1) // ps, P)

    def rows(b, i, *_):
        return (b, 0, i, 0)

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, ps, G, scale, softcap, block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, nq),
            in_specs=[pl.BlockSpec((None, KV, R, hd), rows),
                      pl.BlockSpec((None, R, 1), lambda b, i, *_: (b, i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, KV, R, hd), rows),
            scratch_shapes=[pltpu.VMEM((2, KV, G * ps, hd), k_pages.dtype),
                            pltpu.VMEM((2, KV, G * ps, hd), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((KV, R, 128), jnp.float32),
                            pltpu.VMEM((KV, R, 128), jnp.float32),
                            pltpu.VMEM((KV, R, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KV, T * group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # steps in order: a step's last chunk starts the next's first
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT),
        interpret=interpret,
        # the name a device trace shows the kernel under; NOT what
        # benchmark/harness/trace.py DECODE_KERNEL_OP matches
        name="paged_attention_prefill",
    )(page_table.astype(jnp.int32), first.reshape(-1), end.reshape(-1),
      eff_win, q4, qpos, k_pages, v_pages)
    return out.reshape(B, KV, T, group, hd).transpose(
        0, 2, 1, 3, 4).reshape(B, T, H, hd)


def paged_attention_prefill_sharded(q: jax.Array, k_pages: jax.Array,
                                    v_pages: jax.Array,
                                    page_table: jax.Array,
                                    q_positions: jax.Array, *, mesh,
                                    scale: float | None = None,
                                    interpret: bool = False,
                                    softcap: float | None = None,
                                    eff_win: jax.Array | None = None
                                    ) -> jax.Array:
    """Tensor-parallel chunked-prefill kernel: shard_map over the head
    ("model") and batch ("data") axes, same decomposition as
    paged_attention_decode_sharded — each shard runs the ordinary kernel
    on its local KV heads (q heads follow their kv heads; GQA groups
    never straddle shards while num_kv_heads % tp == 0) and local batch
    rows. No collectives inside: softmax is per-head, so the output
    stays head-sharded into wo."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if eff_win is None:
        eff_win = jnp.full((q.shape[0],), jnp.int32(NO_WINDOW))

    def local(q_, k_, v_, t_, qp_, win_):
        return paged_attention_prefill(q_, k_, v_, t_, qp_, scale=scale,
                                       interpret=interpret,
                                       softcap=softcap, eff_win=win_)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P("data", None, "model", None),
                  P(None, "model", None, None),
                  P(None, "model", None, None),
                  P("data", None), P("data", None), P("data")),
        out_specs=P("data", None, "model", None),
        check_vma=False,  # pallas_call outputs carry no vma annotation
    )(q, k_pages, v_pages, page_table, q_positions, eff_win)
