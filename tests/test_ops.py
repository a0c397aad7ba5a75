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
    """Gemma-2 semantics in the flash prefill kernel: softcap + per-row
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
