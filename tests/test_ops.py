"""Pallas kernels vs their XLA reference paths (interpret mode on CPU —
SURVEY §4 TPU test plan: sharding/kernels CI-testable without hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import _paged_attention
from dynamo_tpu.ops.paged_attention import (paged_attention_decode,
                                            paged_attention_decode_layered)


def _random_pages(key, num_pages, ps, KV, hd, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    shape = (num_pages, KV, ps, hd)  # kv-head-major pool layout
    return (jax.random.normal(k1, shape, dtype),
            jax.random.normal(k2, shape, dtype))


# pages a chunk of the decode kernel's row loop: the module's own rule,
# one page, one that divides the tables below (P 4, 2), one that does
# not, one larger than the table
PAGES_PER_STEP = [None, 1, 2, 3, 8]


def _decode(q, k_pages, v_pages, table, lengths, pages_per_step, **kw):
    """The decode kernel in interpret mode: the 4-D wrapper at the
    module's own rule, the layered entry where a test picks the pages."""
    if pages_per_step is None:
        return paged_attention_decode(q, k_pages, v_pages, table, lengths,
                                      interpret=True, **kw)
    return paged_attention_decode_layered(
        q, k_pages[None], v_pages[None], jnp.int32(0), table, lengths,
        interpret=True, pages_per_step=pages_per_step, **kw)


def _tables(rng, lengths, P, ps, num_pages):
    table = np.zeros((len(lengths), P), np.int32)
    for b, n in enumerate(lengths):
        npages = -(-int(n) // ps)
        table[b, :npages] = rng.choice(
            np.arange(1, num_pages), npages, replace=False)
    return table


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
@pytest.mark.parametrize("KV,group,hd,ps", [(2, 4, 64, 8), (2, 1, 32, 16),
                                            (2, 4, 128, 8), (4, 1, 128, 16),
                                            (1, 20, 128, 128)])
def test_decode_kernel_matches_gather(KV, group, hd, ps, pages_per_step):
    H = KV * group
    B, P, num_pages = 6, 4, 32
    key = jax.random.PRNGKey(0)
    kq, kp, kt = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    k_pages, v_pages = _random_pages(kp, num_pages, ps, KV, hd)

    # distinct random page tables + varied lengths: inside a page, ending
    # exactly on a page (ps, 3 ps), on a chunk of two pages (2 ps), on
    # the table's end
    lengths = np.array([1, ps, ps + 3, 2 * ps, P * ps, 3 * ps], np.int32)
    table = _tables(np.random.RandomState(3), lengths, P, ps, num_pages)

    scale = hd ** -0.5
    got = _decode(q, k_pages, v_pages, jnp.asarray(table),
                  jnp.asarray(lengths), pages_per_step, scale=scale)

    # XLA gather path: q positions are length-1 (the just-written token)
    positions = jnp.asarray(lengths - 1)[:, None]
    want = _paged_attention(q[:, None], k_pages, v_pages, jnp.asarray(table),
                            positions, scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
@pytest.mark.parametrize("hd", [32, 128])
def test_decode_kernel_padding_rows_zero(hd, pages_per_step):
    """length-0 rows (batch padding) must come out as zeros, not NaN:
    first, last, and between live rows (a live row's first chunk is
    started by the row before it, whatever that row holds)."""
    B, H, KV, ps, P = 6, 4, 2, 8, 2
    q = jnp.ones((B, H, hd), jnp.float32)
    k_pages, v_pages = _random_pages(jax.random.PRNGKey(1), 8, ps, KV, hd)
    table = jnp.asarray([[0, 0], [1, 0], [0, 0], [0, 0], [2, 3], [0, 0]],
                        jnp.int32)
    lengths = jnp.asarray([0, 5, 0, 0, 16, 0], jnp.int32)
    out, m, l = _decode(q, k_pages, v_pages, table, lengths, pages_per_step,
                        return_stats=True)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    for b in (0, 2, 3, 5):
        np.testing.assert_array_equal(out[b], 0.0)
        np.testing.assert_array_equal(np.asarray(l)[b], 0.0)
        np.testing.assert_array_equal(np.asarray(m)[b], np.float32(-1e30))
    want = _paged_attention(q[:, None], k_pages, v_pages, table,
                            (lengths - 1)[:, None], hd ** -0.5)[:, 0]
    for b in (1, 4):
        np.testing.assert_allclose(out[b], np.asarray(want)[b],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
@pytest.mark.parametrize("hd", [64, 128])
def test_decode_kernel_bf16(hd, pages_per_step):
    B, H, KV, ps, P = 2, 8, 4, 8, 2
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, hd), jnp.bfloat16)
    k_pages, v_pages = _random_pages(jax.random.PRNGKey(3), 8, ps, KV, hd,
                                     jnp.bfloat16)
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lengths = jnp.asarray([11, 8], jnp.int32)
    got = _decode(q, k_pages, v_pages, table, lengths, pages_per_step)
    assert got.dtype == jnp.bfloat16
    positions = (lengths - 1)[:, None]
    want = _paged_attention(q[:, None], k_pages, v_pages, table, positions,
                            hd ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
def test_decode_kernel_stats_merge_matches_xla(pages_per_step):
    """(out, m, l) of the kernel at any pages a chunk, merged with the
    in-flight window buffer as _pool_window_attention_pallas merges them,
    is the XLA path's attention over pool + buffer: the statistics are
    those of the WHOLE pool view, whatever chunks it was read in."""
    from dynamo_tpu.models.llama import _pool_window_attention

    B, H, KV, hd, ps, P, K, i = 4, 8, 4, 128, 8, 4, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    k_pool, v_pool = _random_pages(ks[0], 24, ps, KV, hd)
    q = jax.random.normal(ks[1], (B, 1, H, hd), jnp.float32)
    wk = jax.random.normal(ks[2], (B, K, KV, hd), jnp.float32)
    wv = jax.random.normal(ks[3], (B, K, KV, hd), jnp.float32)
    # mid-page, a chunk of two pages exactly, the whole table, empty pool
    start = np.array([13, 16, 32, 0], np.int32)
    table = jnp.asarray(_tables(np.random.RandomState(11), start, P, ps, 24))
    scale = hd ** -0.5
    out_p, m_p, l_p = (np.asarray(x, np.float64) for x in _decode(
        q[:, 0], k_pool, v_pool, table, jnp.asarray(start), pages_per_step,
        scale=scale, return_stats=True))
    qg = np.asarray(q, np.float64).reshape(B, KV, H // KV, hd)
    sw = np.einsum("bkgh,bwkh->bkgw", qg, np.asarray(wk, np.float64)) * scale
    sw = np.where(np.arange(K) <= i, sw, -1e30)
    m_w = sw.max(-1)
    p_w = np.exp(sw - m_w[..., None])
    out_w = np.einsum("bkgw,bwkh->bkgh", p_w, np.asarray(wv, np.float64))
    m_p, l_p = m_p.reshape(B, KV, -1), l_p.reshape(B, KV, -1)
    m_t = np.maximum(m_p, m_w)
    a_p, a_w = np.exp(m_p - m_t) * l_p, np.exp(m_w - m_t)
    got = ((out_p.reshape(B, KV, -1, hd) * a_p[..., None]
            + out_w * a_w[..., None])
           / (a_p + a_w * p_w.sum(-1))[..., None]).reshape(B, 1, H, hd)
    want = _pool_window_attention(q, k_pool, v_pool, table,
                                  jnp.asarray(start), wk, wv, i, scale)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


def test_pool_window_merge_matches_xla():
    """The fused-window pool attention (Pallas kernel w/ stats + online-
    softmax merge against the in-flight window buffer) must match the XLA
    concat path — including rows with an empty pool (start=0) and padding
    rows (start=-1). This is the only exercise the stats/merge path gets
    off-TPU (interpret mode)."""
    from dynamo_tpu.models.llama import (_pool_window_attention,
                                         _pool_window_attention_pallas)

    B, H, KV, hd, ps, P, L, K = 4, 8, 4, 64, 8, 3, 2, 4
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    k_pools = jax.random.normal(ks[0], (L, 16, KV, ps, hd), jnp.float32)
    v_pools = jax.random.normal(ks[1], (L, 16, KV, ps, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, 1, H, hd), jnp.float32)
    wk = jax.random.normal(ks[3], (B, K, KV, hd), jnp.float32)
    wv = jax.random.normal(ks[4], (B, K, KV, hd), jnp.float32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9], [1, 0, 0]],
                        jnp.int32)
    # row 0: mid-pool; row 1: page-boundary; row 2: empty pool (start=0);
    # row 3: padding (start=-1)
    start = jnp.asarray([13, 16, 0, -1], jnp.int32)
    scale = hd ** -0.5
    for i in (0, K - 1):
        for l in range(L):
            got = _pool_window_attention_pallas(
                q, k_pools, v_pools, jnp.int32(l), table, start, wk, wv,
                i, scale, interpret=True)
            want = _pool_window_attention(
                q, k_pools[l], v_pools[l], table, start, wk, wv, i, scale)
            np.testing.assert_allclose(np.asarray(got)[:3],
                                       np.asarray(want)[:3],
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group,hd,T", [(2, 16, 8), (4, 32, 16)])
def test_prefill_kernel_matches_gather(group, hd, T):
    """Flash prefill over pages == the XLA gather path: chunk starting
    mid-sequence (prefix already cached), per-row distinct positions,
    padding rows, trailing invalid pages."""
    import numpy as np

    from dynamo_tpu.models.llama import _paged_attention
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    rng = np.random.RandomState(0)
    B, KV, ps, N, P = 3, 2, 4, 32, 6
    H = KV * group
    q = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    k_pages = jnp.asarray(rng.randn(N, KV, ps, hd), jnp.float32)
    v_pages = jnp.asarray(rng.randn(N, KV, ps, hd), jnp.float32)
    table = np.zeros((B, P), np.int32)
    table[0, :4] = [3, 7, 2, 9]          # 2 prefix pages + chunk pages
    table[1, :2] = [11, 4]
    # row 2: padding row (all positions -1)
    positions = np.full((B, T), -1, np.int32)
    positions[0] = np.arange(8, 8 + T)   # chunk starts at position 8
    positions[1] = np.arange(T)
    q_pos = jnp.asarray(positions)

    want = _paged_attention(q, k_pages, v_pages, jnp.asarray(table),
                            q_pos, 0.3)
    got = paged_attention_prefill(q, k_pages, v_pages, jnp.asarray(table),
                                  q_pos, scale=0.3, interpret=True)
    # padding rows: XLA path masks everything -> softmax over -inf gives
    # uniform garbage; the kernel returns zeros. Compare live rows only,
    # and assert the kernel's padding rows are exactly zero.
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got[2]) == 0.0)


def test_prefill_kernel_bf16():
    import numpy as np

    from dynamo_tpu.models.llama import _paged_attention
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    rng = np.random.RandomState(1)
    B, KV, group, ps, hd, N, P, T = 2, 2, 2, 4, 16, 16, 4, 8
    H = KV * group
    q = jnp.asarray(rng.randn(B, T, H, hd), jnp.bfloat16)
    k_pages = jnp.asarray(rng.randn(N, KV, ps, hd), jnp.bfloat16)
    v_pages = jnp.asarray(rng.randn(N, KV, ps, hd), jnp.bfloat16)
    table = np.zeros((B, P), np.int32)
    table[0, :3] = [1, 5, 9]
    table[1, :2] = [2, 8]
    positions = np.stack([np.arange(4, 4 + T), np.arange(T)])
    want = _paged_attention(q, k_pages, v_pages, jnp.asarray(table),
                            jnp.asarray(positions), 0.25)
    got = paged_attention_prefill(q, k_pages, v_pages, jnp.asarray(table),
                                  jnp.asarray(positions), scale=0.25,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def _prefill_case(seed, KV, group, hd, ps, P, T, starts, counts,
                  dtype=jnp.float32, num_pages=None):
    """A chunk of T queries a row: row b holds ``counts[b]`` live queries
    at positions ``starts[b]`` .. (0: a padding row, all -1), its table
    the pages those positions need, the rest 0. Returns (q, k_pages,
    v_pages, table, positions)."""
    rng = np.random.RandomState(seed)
    B = len(starts)
    lengths = [s + c if c else 0 for s, c in zip(starts, counts)]
    num_pages = num_pages or 1 + sum(-(-n // ps) for n in lengths) + 3
    q = jnp.asarray(rng.randn(B, T, KV * group, hd), dtype)
    k_pages = jnp.asarray(rng.randn(num_pages, KV, ps, hd), dtype)
    v_pages = jnp.asarray(rng.randn(num_pages, KV, ps, hd), dtype)
    table = _tables(rng, lengths, P, ps, num_pages)
    positions = np.full((B, T), -1, np.int32)
    for b, (s, c) in enumerate(zip(starts, counts)):
        positions[b, :c] = s + np.arange(c)
    return q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(positions)


def _check_prefill(case, tol=2e-5, **kw):
    """The kernel against the XLA arm at every live query; zeros at
    every query of position -1 (the arm gives a uniform average there)."""
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    q, k_pages, v_pages, table, positions = case
    scale = q.shape[-1] ** -0.5
    window = kw.pop("window", None)
    arm = {} if window is None else {"window": window, "is_sliding": True}
    if window is not None:
        kw["eff_win"] = jnp.full((q.shape[0],), window, jnp.int32)
    want = _paged_attention(q, k_pages, v_pages, table, positions, scale,
                            softcap=kw.get("softcap"),
                            block=kw.get("block", 1), **arm)
    kw.setdefault("interpret", True)
    got = paged_attention_prefill(q, k_pages, v_pages, table, positions,
                                  scale=scale, **kw)
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[~live], 0.0)
    return got


# (tokens a query block, pages a chunk): the module's own rule, then one
# block and chunks of a page, several blocks and a chunk that divides
# nothing, a chunk wider than the table
PREFILL_SIZES = [(None, None), (16, 1), (8, 3), (4, 16)]


@pytest.mark.parametrize("block_tokens,pages_per_step", PREFILL_SIZES)
def test_prefill_kernel_rows_of_different_lengths(block_tokens,
                                                  pages_per_step):
    """One call, ps 8, T 16: a padding row first, between and last (each
    hands the next step's first chunk on); a row from position 0 that
    fills the chunk; a row whose chunk starts after three cached pages (a
    prefix hit: positions from an offset on a page's edge); a chunk that
    starts inside a page and straddles the next; a row of one token; a
    row that ends on the table's last slot."""
    starts = [0, 0, 24, 0, 13, 5, 0, 48, 0]
    counts = [0, 16, 9, 0, 16, 1, 0, 16, 0]
    case = _prefill_case(11, 2, 4, 32, 8, 8, 16, starts, counts)
    _check_prefill(case, block_tokens=block_tokens,
                   pages_per_step=pages_per_step)


def test_prefill_kernel_all_rows_padding():
    case = _prefill_case(12, 2, 2, 32, 8, 4, 8, [0, 0], [0, 0])
    got = _check_prefill(case)
    assert got.shape == case[0].shape


def test_prefill_kernel_one_kv_head_group_20():
    """Jamba's attention at its prefill length: KV 1, group 20, T 512
    over pages of 64, by the module's own rule (32 tokens = 640 rows a
    block): a cold prompt, a chunk after 300 cached tokens, padding."""
    from dynamo_tpu.ops.paged_attention import _prefill_sizes

    assert _prefill_sizes(512, 20, 1, 32, 64, 128, 2) == (32, 8)
    case = _prefill_case(13, 1, 20, 128, 64, 16, 512, [0, 300, 0],
                         [512, 200, 0])
    _check_prefill(case, tol=5e-5)


def test_prefill_kernel_packed_lanes():
    """LFM2's packed pool: two KV heads of 64 share a 128-lane row and a
    query sits in its KV head's lanes, zeros in the other's (models/
    lfm2.py _qkv). The kernel on the packed arrays equals the XLA arm on
    them, and each head's own lanes equal attention over heads of 64."""
    rng = np.random.RandomState(14)
    B, T, H, KV, hd, ps, P, pack = 2, 16, 8, 4, 64, 8, 6, 2
    starts, counts = [20, 0], [16, 11]
    _, k64, v64, table, positions = _prefill_case(
        14, KV, H // KV, hd, ps, P, T, starts, counts)
    q64 = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    lanes = jax.nn.one_hot((np.arange(H) // (H // KV)) % pack, pack)
    q = (q64[..., None, :] * lanes[:, :, None]).reshape(B, T, H, pack * hd)
    N = k64.shape[0]

    def packed(x):  # [N, KV, ps, hd] -> [N, KV / pack, ps, pack * hd]
        return x.reshape(N, KV // pack, pack, ps, hd).transpose(
            0, 1, 3, 2, 4).reshape(N, KV // pack, ps, pack * hd)
    scale = hd ** -0.5
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill
    got = paged_attention_prefill(q, packed(k64), packed(v64), table,
                                  positions, scale=scale, interpret=True)
    live = np.asarray(positions) >= 0
    want = _paged_attention(q, packed(k64), packed(v64), table, positions,
                            scale)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    own = jnp.sum(got.reshape(B, T, H, pack, hd) * lanes[:, :, None], axis=3)
    want64 = _paged_attention(q64, k64, v64, table, positions, scale)
    np.testing.assert_allclose(np.asarray(own)[live],
                               np.asarray(want64)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_tokens,pages_per_step", PREFILL_SIZES)
@pytest.mark.parametrize("window", [5, 20])
def test_prefill_kernel_softcap_and_window_by_row(window, block_tokens,
                                                  pages_per_step):
    """Softcap + sliding window over rows at different offsets: at a
    window of 5 a block's view starts pages past the row's first (whole
    chunks slid past: neither copied nor computed) and the chunk's early
    queries see less than a page; at 20 it starts inside a page."""
    case = _prefill_case(15, 2, 2, 32, 8, 8, 16, [0, 40, 0, 17],
                         [16, 16, 0, 12])
    _check_prefill(case, softcap=12.0, window=window,
                   block_tokens=block_tokens, pages_per_step=pages_per_step)


@pytest.mark.parametrize("block_tokens,pages_per_step",
                         [(16, 1), (8, 2), (4, 3), (16, 8)])
def test_prefill_kernel_copies_in_tpu_interpreter(block_tokens,
                                                  pages_per_step):
    """The kernel's own copies under the TPU interpreter, as
    test_decode_kernel_copies_in_tpu_interpreter: buffers start as NaN
    and a copy's bytes arrive only when it is waited for, so a chunk
    computed before its wait, a wait that names another slot or a stale
    page that leaks through the mask shows as a wrong row."""
    from jax.experimental.pallas import tpu as pltpu

    interp = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    starts = [0, 0, 24, 0, 0, 13, 48, 0]
    counts = [0, 16, 9, 0, 0, 16, 16, 0]
    case = _prefill_case(16, 2, 4, 128, 8, 8, 16, starts, counts)
    _check_prefill(case, interpret=interp, block_tokens=block_tokens,
                   pages_per_step=pages_per_step)


@pytest.mark.parametrize("block", [2, 4, 8])
@pytest.mark.parametrize("block_tokens,pages_per_step", PREFILL_SIZES)
def test_prefill_kernel_block_edge_matches_gather(block_tokens,
                                                  pages_per_step, block):
    """The block mask of generation by diffusion (causal across blocks of
    ``block`` positions, bidirectional inside one): a query sees to the
    end of its own block, so a block of queries reads up to block - 1
    positions past its last query: into the next page where a chunk ends
    on a page's last block (row 1: 24 + 8 = 32), and not past the row's
    last whole block (rows end on a block's end, as the engine's chunks
    do). Against the XLA arm with the same mask."""
    case = _prefill_case(21, 2, 2, 32, 8, 8, 16, [0, 24, 0, 8],
                         [16, 8, 0, 16])
    _check_prefill(case, block=block, block_tokens=block_tokens,
                   pages_per_step=pages_per_step)


def test_prefill_kernel_block_edge_in_tpu_interpreter():
    """The block edge under the TPU interpreter (copies arrive when
    waited for, buffers start as NaN): the page a block of queries now
    reads beyond its last query's own is copied before it is computed."""
    from jax.experimental.pallas import tpu as pltpu

    interp = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    case = _prefill_case(22, 2, 4, 128, 8, 8, 16, [0, 0, 24, 8],
                         [0, 16, 8, 16])
    _check_prefill(case, interpret=interp, block=4, block_tokens=8,
                   pages_per_step=2)


def test_prefill_kernel_block_one_is_the_causal_kernel():
    """block=1 is the kernel as it was: the same lowered text with and
    without the argument (the branch is taken at trace time)."""
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    q, k_pages, v_pages, table, positions = _prefill_case(
        23, 2, 2, 128, 8, 4, 16, [0, 8], [16, 8])
    low = [paged_attention_prefill.lower(
        q, k_pages, v_pages, table, positions, scale=0.1, interpret=True,
        **kw).as_text() for kw in ({}, {"block": 1})]
    assert low[0] == low[1]
    other = paged_attention_prefill.lower(
        q, k_pages, v_pages, table, positions, scale=0.1, interpret=True,
        block=4).as_text()
    assert other != low[0]


def _block_window_case(L, G, hd, blocks):
    """Pools, a table, ``blocks`` * L queries a row and a buffer of three
    blocks for four rows: no pool, a pool that ends inside a page, one
    that ends on a page's end, a padding row."""
    KV, ps, P, num_pages, B, layers = 2, 8, 4, 24, 4, 2
    rng = np.random.RandomState(31)
    pooled = np.array([0, 8 + L, 3 * ps, -1], np.int32)
    table = _tables(rng, np.maximum(pooled, 0), P, ps, num_pages)
    pools = [jnp.asarray(rng.randn(layers, num_pages, KV, ps, hd),
                         jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.randn(B, blocks * L, KV * G, hd), jnp.float32)
    wk, wv = (jnp.asarray(rng.randn(B, 3 * L, KV, hd), jnp.float32)
              for _ in range(2))

    def attend(wk, wv, skip, end, arm, q=q):
        from dynamo_tpu.models.llama import _block_window_attention
        return np.asarray(_block_window_attention(
            q, *pools, jnp.int32(1), jnp.asarray(table),
            jnp.asarray(pooled), wk, wv, jnp.asarray(skip, jnp.int32),
            end, L, hd ** -0.5, use_pallas=arm, interpret=True))

    return q, wk, wv, attend


@pytest.mark.parametrize("L,G,hd", [(4, 2, 128), (4, 8, 128), (2, 4, 64),
                                    (8, 1, 128)])
def test_block_window_attention_kernel_arm_matches_gather(L, G, hd):
    """The block window's attention with the L queries of a block folded
    into the decode kernel's group axis (G x L rows a KV head, interpret
    mode) and merged with the window buffer by the kernel's statistics,
    against its XLA arm (one gather, one softmax): rows at different
    pool extents (none, inside a page, on a page's end), a padding row,
    a buffer with two and with three visible blocks (the end a number
    or a traced scalar), rows that use the buffer's first block beside
    rows that do not."""
    q, wk, wv, attend = _block_window_case(L, G, hd, 1)
    skip = [0, L, 0, L]
    for end in (2 * L, jnp.int32(3 * L)):
        got, want = (attend(wk, wv, skip, end, arm)
                     for arm in (True, False))
        np.testing.assert_allclose(got[:3], want[:3], rtol=2e-5, atol=2e-5)
    # what the buffer's later block holds must not matter to the second,
    # nor the first block's to a row that skips it
    first = attend(wk.at[:, 2 * L:].set(9.0), wv.at[:, 2 * L:].set(9.0),
                   skip, 2 * L, True)
    again = attend(wk, wv, skip, 2 * L, True)
    np.testing.assert_array_equal(first[:3], again[:3])
    other = attend(wk.at[:, :L].set(9.0), wv.at[:, :L].set(9.0), skip,
                   2 * L, True)
    np.testing.assert_array_equal(other[1], again[1])
    assert np.abs(other[0] - again[0]).max() > 1e-3


@pytest.mark.parametrize("arm", [True, False], ids=["kernel", "xla"])
@pytest.mark.parametrize("L,G,hd", [(4, 8, 128), (4, 2, 128), (2, 4, 64)])
def test_two_blocks_of_queries_are_two_calls_of_one(L, G, hd, arm):
    """2L queries in ONE call (G x 2L rows a KV head in the kernel's
    group axis: the row's pages are read once), the first block's seeing
    the buffer up to its own end and the second's one block further:
    each half equals the call on that block alone, on either arm and for
    every kind of row."""
    q, wk, wv, attend = _block_window_case(L, G, hd, 2)
    skip = [0, L, 0, L]
    for at in (L, 2 * L):
        both = attend(wk, wv, skip, at + L, arm)
        for c in range(2):
            alone = attend(wk, wv, skip, at + c * L, arm,
                           q=q[:, c * L:(c + 1) * L])
            np.testing.assert_allclose(
                both[:3, c * L:(c + 1) * L], alone[:3], rtol=2e-5,
                atol=2e-5)


@pytest.mark.parametrize("pages_per_step", PAGES_PER_STEP)
@pytest.mark.parametrize("hd,window", [(32, 6), (128, 6), (128, 20)])
def test_decode_kernel_softcap_and_window_match_gather(hd, window,
                                                       pages_per_step):
    """Gemma-2 semantics in the decode kernel: tanh score softcap and a
    per-row lower bound (sliding window) match the XLA path — including
    the degenerate all-masked-page case the valid-mask guards. At a
    window of 6 the view of the longest row begins in its last page (at
    two pages a chunk: a whole chunk slid past); at 20, inside its
    second page."""
    from dynamo_tpu.models.llama import _paged_attention

    KV, group, ps = 2, 2, 8
    H = KV * group
    B, P, num_pages = 4, 4, 32
    key = jax.random.PRNGKey(7)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)
    k_pages, v_pages = _random_pages(kp, num_pages, ps, KV, hd)

    lengths = np.array([ps + 3, 2 * ps, P * ps, 5], np.int32)
    table = _tables(np.random.RandomState(7), lengths, P, ps, num_pages)

    scale = hd ** -0.5
    softcap = 15.0
    eff = np.full(B, window, np.int32)
    lower = np.clip(lengths - eff, 0, np.maximum(lengths - 1, 0))
    got = _decode(q, k_pages, v_pages, jnp.asarray(table),
                  jnp.asarray(lengths), pages_per_step, scale=scale,
                  softcap=softcap, lower=jnp.asarray(lower))

    positions = jnp.asarray(lengths - 1)[:, None]
    want = _paged_attention(q[:, None], k_pages, v_pages,
                            jnp.asarray(table), positions, scale,
                            softcap=softcap, window=window,
                            is_sliding=True)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_empty_window_view():
    """A window that slid past the whole pool (lower == length, as the
    fused window's later steps give it): zeros and (m, l) = (NEG_INF, 0),
    whether the view ends inside a page or on one."""
    KV, group, hd, ps, P = 2, 2, 128, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(5), (3, KV * group, hd))
    k_pages, v_pages = _random_pages(jax.random.PRNGKey(6), 16, ps, KV, hd)
    lengths = jnp.asarray([13, 16, 7], jnp.int32)
    table = jnp.asarray(_tables(np.random.RandomState(5), [13, 16, 7], P,
                                ps, 16))
    out, m, l = _decode(q, k_pages, v_pages, table, lengths, 2,
                        return_stats=True,
                        lower=jnp.asarray([13, 16, 0], jnp.int32))
    for b in (0, 1):
        np.testing.assert_array_equal(np.asarray(out)[b], 0.0)
        np.testing.assert_array_equal(np.asarray(l)[b], 0.0)
        np.testing.assert_array_equal(np.asarray(m)[b], np.float32(-1e30))
    assert np.abs(np.asarray(out)[2]).sum() > 0


def test_prefill_kernel_softcap_and_window_match_gather():
    """Gemma-2 semantics in the paged prefill kernel: softcap + per-row
    effective window (with page skipping below the window) match the XLA
    gather path over a chunk longer than the window."""
    from dynamo_tpu.models.llama import _paged_attention
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    KV, group, hd, ps, T = 2, 2, 32, 8, 24
    H = KV * group
    B, P, num_pages = 2, 4, 32
    key = jax.random.PRNGKey(8)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, T, H, hd), jnp.float32)
    k_pages, v_pages = _random_pages(kp, num_pages, ps, KV, hd)

    rng = np.random.RandomState(8)
    table = np.zeros((B, P), np.int32)
    for b in range(B):
        table[b] = rng.choice(np.arange(1, num_pages), P, replace=False)
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))

    scale = hd ** -0.5
    window, softcap = 7, 12.0
    got = paged_attention_prefill(
        q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(positions),
        scale=scale, interpret=True, softcap=softcap,
        eff_win=jnp.full((B,), window, jnp.int32))
    want = _paged_attention(q, k_pages, v_pages, jnp.asarray(table),
                            jnp.asarray(positions), scale,
                            softcap=softcap, window=window,
                            is_sliding=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_prefill_kernel_window_second_chunk_page_skip():
    """Chunked prefill whose second chunk starts past the window: pages
    wholly below the window's reach are skipped by the lower-bound guard
    yet the output still matches the XLA path."""
    from dynamo_tpu.models.llama import _paged_attention
    from dynamo_tpu.ops.paged_attention import paged_attention_prefill

    KV, group, hd, ps, T = 1, 2, 32, 4, 8
    H = KV * group
    B, P, num_pages = 1, 8, 32
    key = jax.random.PRNGKey(9)
    kq, kp = jax.random.split(key)
    q = jax.random.normal(kq, (B, T, H, hd), jnp.float32)
    k_pages, v_pages = _random_pages(kp, num_pages, ps, KV, hd)
    table = np.arange(1, P + 1, dtype=np.int32)[None]
    # chunk covers positions 20..27; window 6 → nothing below pos 15 is
    # visible, so pages 0..2 (positions 0..11) are skippable
    positions = (20 + np.arange(T, dtype=np.int32))[None]

    scale = hd ** -0.5
    window = 6
    got = paged_attention_prefill(
        q, k_pages, v_pages, jnp.asarray(table), jnp.asarray(positions),
        scale=scale, interpret=True,
        eff_win=jnp.full((B,), window, jnp.int32))
    want = _paged_attention(q, k_pages, v_pages, jnp.asarray(table),
                            jnp.asarray(positions), scale,
                            window=window, is_sliding=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_step", [1, 2, 4, 6])
def test_decode_kernel_copies_in_tpu_interpreter(pages_per_step):
    """The kernel's own copies under the TPU interpreter: buffers start
    as NaN and a copy's bytes arrive only when it is WAITED for, so a
    chunk computed before its wait, a wait that names another slot, or a
    stale page that leaks through the mask shows as a wrong row. Padding
    rows first, between and last: each hands the next row's first chunk
    on."""
    from jax.experimental.pallas import tpu as pltpu

    interp = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    rng = np.random.RandomState(0)
    B, KV, group, hd, ps, P, N = 8, 2, 4, 128, 8, 6, 40
    q = jnp.asarray(rng.randn(B, KV * group, hd), jnp.float32)
    k = jnp.asarray(rng.randn(1, N, KV, ps, hd), jnp.float32)
    v = jnp.asarray(rng.randn(1, N, KV, ps, hd), jnp.float32)
    lengths = np.array([0, 5, 0, P * ps, 0, 0, 2 * ps, 3 * ps + 1], np.int32)
    table = jnp.asarray(_tables(rng, lengths, P, ps, N))
    got = paged_attention_decode_layered(
        q, k, v, jnp.int32(0), table, jnp.asarray(lengths),
        interpret=interp, pages_per_step=pages_per_step)
    want = _paged_attention(q[:, None], k[0], v[0], table,
                            jnp.asarray(lengths - 1)[:, None],
                            hd ** -0.5)[:, 0]
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got)[~live], 0.0)
