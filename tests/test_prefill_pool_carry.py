"""llama.forward with the K/V pools as its scan's CARRY against the form
it replaced, bit for bit on both pools and on the hidden states.

The reference, `_ref_forward_xs`, is forward as it stood until PR 49:
the pools as scanned xs, the written layers as stacked ys. On the chip
that form slices every layer's pool out, updates it, stacks the results
and copies them over the donated buffers, six pool-sized ops a program
(tests/test_tpu_compile.py holds the compiled programs to none); here it
is the plain statement of what a program writes. The carried form sees a
pool as [L * pages, ...] and shifts every page id by the layer's offset,
so what has to hold besides is that a dropped slot of layer l stays
dropped and does not become page 0 of layer l + 1.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT

L, PS, PAGES, P = 3, 4, 12, 4          # layers, page size, pool, table
SPEC = llama.KVCacheSpec(num_pages=PAGES, page_size=PS)

CONFIGS = {
    "dense": dict(),
    "moe": dict(num_experts=4, num_experts_per_tok=2),
    # one pool under a mask: layers 0 and 2 are held to the window
    "sliding": dict(sliding_window=6),
    "block4": dict(block_length=4),
}


def _ref_forward_xs(params, cfg, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots, page_slots=None):
    """forward at PR 47, verbatim but for the names it imports: a layer
    is handed its own pool [pages, KV, ps, hd] and returns it."""
    inv_freq = llama.rope_freqs(cfg)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    B, T = tokens.shape
    h = llama.embed_tokens(params, cfg, tokens)
    safe_pos = jnp.maximum(positions, 0)
    layer_params = {k: params[k] for k in llama._layer_keys(cfg)}
    moe_in_place = cfg.num_experts > 0 and llama._moe_use_blocked(
        None, B * T, cfg.num_experts, cfg.num_experts_per_tok)
    experts = live = None
    if moe_in_place:
        experts = [layer_params.pop(k) for k in ("w_gate", "w_up", "w_down")]
        live = positions >= 0

    def layer(h, xs):
        lp, l_idx, k_layer, v_layer = xs
        x = llama.rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                           cfg.norm_unit_offset)
        q = (x @ lp["wq"]).reshape(B, T, H, hd)
        k = (x @ lp["wk"]).reshape(B, T, KV, hd)
        v = (x @ lp["wv"]).reshape(B, T, KV, hd)
        q, k = llama._qk_headnorm(q, k, lp, cfg)
        q = llama.apply_rope(q, safe_pos, inv_freq)
        k = llama.apply_rope(k, safe_pos, inv_freq)
        if cfg.block_length > 1:
            k, v = llama._block_kv(k, v, k_layer.dtype)
        if page_slots is not None:
            k_layer = llama._scatter_pages_paged(k_layer, k, page_slots)
            v_layer = llama._scatter_pages_paged(v_layer, v, page_slots)
        else:
            k_layer = llama._scatter_pages(k_layer, k, flat_slots)
            v_layer = llama._scatter_pages(v_layer, v, flat_slots)
        attn = llama._attention(q, k_layer, v_layer, page_table, positions,
                                cfg.attn_scale, allow_pallas=False,
                                softcap=cfg.attn_logit_softcap,
                                window=cfg.sliding_window,
                                is_sliding=llama._window_flag(cfg, l_idx),
                                block=cfg.block_length)
        h = llama._residual_add(h, attn.reshape(B, T, H * hd) @ lp["wo"],
                                lp, "ln_attn_post", cfg)
        h = llama._layer_ff(h, lp, cfg, None, experts, live, l_idx)
        return h, (k_layer, v_layer)

    h, (new_k, new_v) = lax.scan(
        layer, h, (layer_params, jnp.arange(cfg.num_layers), kv_k, kv_v))
    h = llama.rms_norm(h, params["ln_final"], cfg.rms_norm_eps,
                       cfg.norm_unit_offset)
    return h, new_k, new_v


def _batch(form: str):
    """Three rows: 0 and 1 live, 2 a padding row (positions -1, its table
    at page 0, every slot dropped). Page 0 is nobody's, as in the engine:
    it has to come back untouched in EVERY layer, which is what a slot
    that leaked from the layer before would break. Returns (tokens,
    positions, table, flat_slots, page_slots or None, ref_page_slots)."""
    rng = np.random.default_rng(sum(map(ord, form)))
    ids = rng.permutation(np.arange(1, PAGES))[:2 * P].reshape(2, P)
    table = np.zeros((3, P), np.int32)
    table[:2] = ids
    # row 0 starts at 0, row 1 after one cached page (a prefix hit); the
    # unaligned forms start mid-page and end mid-page
    T, start = {"aligned": (8, (0, PS)), "unaligned": (6, (1, PS + 2)),
                "decode": (1, (5, 2 * PS)), "verify": (3, (3, PS + 3)),
                "drop_negative": (8, (0, PS)),
                "drop_past": (8, (0, PS))}[form]
    positions = np.full((3, T), -1, np.int32)
    flat = np.full((3, T), DROP_SLOT, np.int32)
    for b in range(2):
        pos = start[b] + np.arange(T)
        positions[b] = pos
        flat[b] = table[b, pos // PS] * PS + pos % PS
    if form == "unaligned":
        positions[1, -1] = -1           # a chunk's padded tail
        flat[1, -1] = DROP_SLOT
    tokens = rng.integers(1, 500, (3, T)).astype(np.int32)
    pslots = ref_pslots = None
    if form in ("aligned", "drop_negative", "drop_past"):
        pslots = np.full((3, T // PS), PAGES, np.int32)
        for b in range(2):
            first = start[b] // PS
            pslots[b] = table[b, first:first + T // PS]
        ref_pslots = pslots.copy()
        if form == "drop_negative":
            # the xs form WRAPPED a negative page to the layer's last (no
            # caller sent one); the shared write drops it, as
            # _forward_by_kind's has: the reference is told "dropped"
            pslots[0, 1], ref_pslots[0, 1] = -1, PAGES
        if form == "drop_past":
            # PAGES itself is layer l + 1's page 0 in the flat view, and
            # PAGES + 1 a live row's page there
            pslots[0, 1] = ref_pslots[0, 1] = PAGES
            pslots[1, 0] = ref_pslots[1, 0] = PAGES + 1
    a = jnp.asarray
    return (a(tokens), a(positions), a(table), a(flat),
            None if pslots is None else a(pslots),
            None if ref_pslots is None else a(ref_pslots))


def _pools(cfg, seed):
    k, v = llama.init_kv_cache(cfg, SPEC)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, k.shape, jnp.float32).astype(k.dtype),
            jax.random.normal(k2, v.shape, jnp.float32).astype(v.dtype))


def _bits(x):
    return np.asarray(x).view(np.uint32)


FORMS = ["aligned", "unaligned", "decode", "verify", "drop_negative",
         "drop_past"]
# a configuration that generates by blocks has no single-step arm and no
# verify program: its prefill forms alone
CASES = [(name, form) for name in CONFIGS for form in FORMS
         if not (name == "block4" and form in ("decode", "verify"))]


@pytest.mark.parametrize("name,form", CASES)
def test_carried_pools_match_the_scanned_form(name, form):
    cfg = ModelConfig.tiny(num_layers=L, **CONFIGS[name])
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens, positions, table, flat, pslots, ref_pslots = _batch(form)
    init_k, init_v = (np.asarray(x) for x in _pools(cfg, 1))

    ref = jax.jit(partial(_ref_forward_xs, cfg=cfg), donate_argnames=(
        "kv_k", "kv_v"))
    want_h, want_k, want_v = ref(
        params, tokens=tokens, positions=positions, kv_k=jnp.asarray(init_k),
        kv_v=jnp.asarray(init_v), page_table=table, flat_slots=flat,
        page_slots=ref_pslots)

    fwd = jax.jit(partial(llama.forward, cfg=cfg, allow_pallas=False),
                  donate_argnames=("kv_k", "kv_v"))
    got_h, got_k, got_v = fwd(
        params, tokens=tokens, positions=positions, kv_k=jnp.asarray(init_k),
        kv_v=jnp.asarray(init_v), page_table=table, flat_slots=flat,
        page_slots=pslots)
    assert got_k.shape == init_k.shape and got_k.dtype == init_k.dtype
    np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
    np.testing.assert_array_equal(_bits(got_v), _bits(want_v))
    live = np.asarray(positions) >= 0
    np.testing.assert_array_equal(_bits(got_h)[live], _bits(want_h)[live])
    # something was written, and nobody's page 0 in no layer
    assert (_bits(got_k) != _bits(init_k)).any()
    np.testing.assert_array_equal(_bits(got_k)[:, 0], _bits(init_k)[:, 0])
    np.testing.assert_array_equal(_bits(got_v)[:, 0], _bits(init_v)[:, 0])

    # the jitted entry point of the form, as the engine calls it
    pools = (jnp.asarray(init_k), jnp.asarray(init_v))
    prefill_step, decode_step = llama.make_step_fns(cfg, allow_pallas=False)
    if form == "decode":
        logits, k2, v2 = decode_step(params, tokens[:, 0], positions[:, 0],
                                     *pools, table, flat[:, 0])
        want = llama.logits_at(params, cfg, want_h, jnp.zeros(3, jnp.int32))
    elif form == "verify":
        logits, k2, v2 = llama.make_verify_fn(cfg, allow_pallas=False)(
            params, tokens, positions, *pools, table, flat)
        want = llama.project_logits(params, cfg, want_h)
    else:
        # each row's last live position (the padded tail is not one)
        last = jnp.asarray(np.maximum(live.sum(axis=1) - 1, 0), jnp.int32)
        logits, k2, v2 = prefill_step(params, tokens, positions, *pools,
                                      table, flat, last, pslots)
        want = llama.logits_at(params, cfg, want_h, last)
    np.testing.assert_array_equal(_bits(k2), _bits(want_k))
    np.testing.assert_array_equal(_bits(v2), _bits(want_v))
    if cfg.block_length > 1:
        assert logits is None       # its prefill has no head
    else:
        np.testing.assert_allclose(np.asarray(logits)[:2],
                                   np.asarray(want)[:2], rtol=1e-6, atol=1e-6)
