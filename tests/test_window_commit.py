"""The decode window's whole-page commit (models/llama.py commit_window)
against the row scatter it replaced, bit for bit on the whole pool.

The reference is the commit both windows made until PR 30: a
`jax.vmap(_scatter_pages)` of single token rows at `(page, :, offset)`.
On the TPU that form costs eight relayout copies of the pool a window
(the compiler scatters only along a major axis); on the CPU it is simply
the plain statement of what a commit writes. tests/test_tpu_compile.py
holds the compiled window to "no pool-sized copy"; this file holds the
new commit to the old one's bytes, touched and untouched pages alike.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import DROP_SLOT, _scatter_pages, commit_window

L, PS, HD = 2, 32, 8
P = 4                       # page_table columns
NUM_PAGES = 40


@jax.jit
def _row_scatter_commit(kv, w, page_table, start, pos):
    """The old commit, verbatim: one dropped-or-placed row per (b, i)."""
    B, k_steps = w.shape[1:3]
    ps = kv.shape[3]
    wpos = start[:, None] + jnp.arange(k_steps)[None, :]
    page = page_table[jnp.arange(B)[:, None],
                      jnp.clip(wpos // ps, 0, page_table.shape[1] - 1)]
    valid = jnp.logical_and(start[:, None] >= 0, wpos < pos[:, None])
    flat = jnp.where(valid, page * ps + wpos % ps, DROP_SLOT)
    return jax.vmap(_scatter_pages)(
        kv, w, jnp.broadcast_to(flat, (kv.shape[0],) + flat.shape))


def _batch(off: int, k_steps: int):
    """Seven rows in one batch; `off` is every live row's start % ps.

    0 a padding row; 1 froze before the window; 2 freezes at step 2;
    3 starts in the LAST column of page_table, so its second page slot
    falls past the table (its budget ends with the table, as the
    engine's max length makes it); 4-6 run the whole window from columns
    0, 1 and 2 (they straddle two pages whenever off + k_steps > ps).
    Rows 5 and 6 share their first column's page read-only (a prefix
    hit): nobody writes it."""
    col = np.array([0, 1, 2, P - 1, 0, 1, 2])
    start = col * PS + off
    start[0] = -1
    pos = start + k_steps
    pos[0] = -1
    pos[1] = start[1]
    pos[2] = start[2] + 2
    pos[3] = min(pos[3], P * PS)
    rng = np.random.default_rng(off * 16 + k_steps)
    ids = rng.permutation(np.arange(1, NUM_PAGES))[:7 * P].reshape(7, P)
    ids[6, 0] = ids[5, 0]
    ids[0] = 0                               # padding rows point at page 0
    return (jnp.asarray(ids, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("off", [0, 17, PS - 1, PS - 2, "ps-k"])
@pytest.mark.parametrize("k_steps", [4, 8])
@pytest.mark.parametrize("kv_heads", [1, 4, 8])
def test_whole_page_commit_matches_row_scatter(kv_heads, k_steps, off):
    off = PS - k_steps if off == "ps-k" else off
    page_table, start, pos = _batch(off, k_steps)
    B = start.shape[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(off + 100 * k_steps))
    kv = jax.random.normal(k1, (L, NUM_PAGES, kv_heads, PS, HD),
                           jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (L, B, k_steps, kv_heads, HD),
                          jnp.float32).astype(jnp.bfloat16)
    want = _row_scatter_commit(kv, w, page_table, start, pos)
    got = jax.jit(commit_window)(kv, w, page_table, start, pos)
    assert got.dtype == kv.dtype and got.shape == kv.shape
    bits = lambda x: np.asarray(x).view(np.uint16)  # noqa: E731
    np.testing.assert_array_equal(bits(got), bits(want))
    # the batch did what its docstring says: rows 0 and 1 wrote nothing,
    # row 2 two rows, the others a whole window (row 3 up to the table)
    changed = (bits(want) != bits(kv)).any(axis=(0, 2, 4))   # [pages, ps]
    n3 = min(k_steps, P * PS - int(start[3]))
    assert changed.sum() == 2 + n3 + 3 * k_steps
    assert not changed[np.asarray(page_table[:2]).ravel()].any()
    assert not changed[int(page_table[5, 0])].any()


def test_page_ids_outside_the_pool_are_dropped():
    """A table that names a page past the pool (or a negative id) writes
    nothing there and nothing into the next layer's pages."""
    k_steps = 4
    page_table = jnp.asarray([[NUM_PAGES + 3, 1], [-1, 2], [5, 6]],
                             jnp.int32)
    start = jnp.asarray([3, 7, PS - 2], jnp.int32)
    pos = start + k_steps
    kv = jnp.ones((L, NUM_PAGES, 2, PS, HD), jnp.bfloat16)
    w = jnp.full((L, 3, k_steps, 2, HD), 2.0, jnp.bfloat16)
    got = np.asarray(jax.jit(commit_window)(kv, w, page_table, start, pos),
                     np.float32)
    changed = (got != 1.0).any(axis=(2, 4))                  # [L, pages, ps]
    assert changed.sum() == L * k_steps
    assert changed[:, 5, PS - 2:].all() and changed[:, 6, :2].all()
