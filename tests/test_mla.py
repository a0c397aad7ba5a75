"""MLA (DeepSeek-style multi-head latent attention) model family: the
absorbed/paged path must match the materialized full-attention oracle, and
the engine must serve it end-to-end through the registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec


def tiny_mla(**over):
    base = dict(model_type="deepseek_v2", vocab_size=512, hidden_size=64,
                intermediate_size=128, num_layers=2, num_heads=4,
                num_kv_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=0,
                rope_theta=10000.0, dtype="float32")
    base.update(over)
    return ModelConfig(**base)


@pytest.mark.parametrize("q_lora", [0, 24])
def test_mla_paged_prefill_matches_reference(q_lora):
    cfg = tiny_mla(q_lora_rank=q_lora)
    params = mla.init_params(cfg, jax.random.PRNGKey(0))
    B, T, ps = 2, 16, 8
    tokens = jnp.asarray(np.random.RandomState(0).randint(1, 500, (B, T)),
                         jnp.int32)
    ref = mla.reference_forward(params, cfg, tokens)

    kv_c, kv_r = mla.init_kv_cache(cfg, KVCacheSpec(num_pages=8,
                                                    page_size=ps))
    prefill, _ = mla.make_step_fns(cfg)
    table = np.zeros((B, 4), np.int32)
    slots = np.zeros((B, T), np.int32)
    for b in range(B):
        table[b, :2] = [1 + 2 * b, 2 + 2 * b]
        for t in range(T):
            slots[b, t] = table[b, t // ps] * ps + t % ps
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    logits, kv_c, kv_r = prefill(params, tokens, jnp.asarray(positions),
                                 kv_c, kv_r, jnp.asarray(table),
                                 jnp.asarray(slots),
                                 jnp.full((B,), T - 1, np.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_mla_decode_matches_reference_continuation():
    cfg = tiny_mla()
    params = mla.init_params(cfg, jax.random.PRNGKey(1))
    B, T, ps = 1, 8, 8
    rng = np.random.RandomState(1)
    tokens = rng.randint(1, 500, (B, T + 4)).astype(np.int32)
    prefill, decode = mla.make_step_fns(cfg)
    kv_c, kv_r = mla.init_kv_cache(cfg, KVCacheSpec(num_pages=8,
                                                    page_size=ps))
    table = np.asarray([[1, 2]], np.int32)
    slots = np.asarray([[ps + t for t in range(T)]], np.int32)
    positions = np.arange(T, dtype=np.int32)[None]
    logits, kv_c, kv_r = prefill(
        params, jnp.asarray(tokens[:, :T]), jnp.asarray(positions),
        kv_c, kv_r, jnp.asarray(table), jnp.asarray(slots),
        jnp.asarray([T - 1], np.int32))
    # decode the next 4 (teacher-forced) tokens one at a time
    for i in range(4):
        pos = T + i
        slot = np.asarray([table[0, pos // ps] * ps + pos % ps], np.int32)
        logits, kv_c, kv_r = decode(
            params, jnp.asarray(tokens[:, pos]),
            jnp.asarray([pos], np.int32), kv_c, kv_r,
            jnp.asarray(table), jnp.asarray(slot))
    ref = mla.reference_forward(params, cfg, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, -1]),
                               rtol=3e-4, atol=3e-4)


def test_mla_cache_is_compact():
    """The latent cache must be far smaller than an equivalent GQA cache
    (the point of MLA on HBM-bound decode)."""
    cfg = tiny_mla()
    spec = KVCacheSpec(num_pages=8, page_size=8)
    lat, rope = mla.cache_shapes(cfg, spec)
    mla_bytes = np.prod(lat) + np.prod(rope)
    gqa_bytes = 2 * np.prod((cfg.num_layers, 8, cfg.num_kv_heads, 8,
                             cfg.qk_nope_head_dim))
    assert mla_bytes < gqa_bytes


def test_engine_serves_mla(run_async):
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    cfg = tiny_mla()
    ecfg = EngineConfig(page_size=8, num_pages=64, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(32,),
                        batch_buckets=(4,), page_buckets=(8,))
    engine = JaxEngine(cfg, ecfg, seed=0)

    async def scenario():
        req = PreprocessedRequest(
            token_ids=list(range(1, 20)), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        # determinism under greedy: same prompt, same continuation
        toks2 = []
        async for out in engine.generate(req, Context()):
            toks2.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks, toks2

    toks, toks2 = run_async(scenario())
    assert len(toks) == 8 and toks == toks2


def test_mla_tp_sharding_compiles():
    """MLA params shard over the model axis and one prefill step executes
    on an 8-device mesh."""
    from dynamo_tpu.parallel.mesh import MeshSpec, shard_params

    cfg = tiny_mla()
    mesh = MeshSpec(model=2, data=4).build()
    params = shard_params(mla.init_params(cfg, jax.random.PRNGKey(0)),
                          cfg, mesh)
    prefill, _ = mla.make_step_fns(cfg)
    B, T, ps = 4, 8, 8
    kv_c, kv_r = mla.init_kv_cache(cfg, KVCacheSpec(num_pages=16,
                                                    page_size=ps))
    from dynamo_tpu.parallel.mesh import shard_kv_cache

    kv_c, kv_r = shard_kv_cache(kv_c, kv_r, cfg, mesh)
    tokens = np.random.RandomState(0).randint(1, 500, (B, T)).astype(np.int32)
    table = np.zeros((B, 2), np.int32)
    slots = np.full((B, T), DROP_SLOT, np.int32)
    for b in range(B):
        table[b, 0] = 1 + b
        slots[b] = [(1 + b) * ps + t for t in range(T)]
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    logits, kv_c, kv_r = prefill(
        params, jnp.asarray(tokens), jnp.asarray(positions), kv_c, kv_r,
        jnp.asarray(table), jnp.asarray(slots),
        jnp.full((B,), T - 1, np.int32))
    assert np.isfinite(np.asarray(logits)).all()


def test_mla_ring_long_prefill_matches_reference():
    """Latent-only ring exchange (VERDICT r3 task 7): the MLA
    sequence-parallel prefill on a seq=4 mesh matches the materialized
    full-attention oracle's last-position logits, and its c/r streams
    match the paged prefill pools."""
    from dynamo_tpu.parallel.mesh import MeshSpec
    from dynamo_tpu.parallel.ring_attention import make_mla_long_prefill_fn

    cfg = tiny_mla()
    params = mla.init_params(cfg, jax.random.PRNGKey(3))
    B, T = 1, 32
    tokens = np.random.RandomState(3).randint(1, 500, (B, T)).astype(np.int32)
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    ref = mla.reference_forward(params, cfg, jnp.asarray(tokens))

    mesh = MeshSpec(seq=4).build()
    fn = make_mla_long_prefill_fn(cfg, mesh)
    with jax.set_mesh(mesh):
        logits, c_all, r_all = fn(params, jnp.asarray(tokens),
                                  jnp.asarray(pos))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, -1]),
                               rtol=2e-4, atol=2e-4)
    assert c_all.shape == (cfg.num_layers, B, T, 1, cfg.kv_lora_rank)
    # (as wide as the rope pool: on a TPU padded to whole lanes)
    assert r_all.shape == (cfg.num_layers, B, T, 1, mla.rope_width(cfg))

    # the ring-produced streams equal what the paged prefill writes
    ps = 8
    kv_c, kv_r = mla.init_kv_cache(cfg, KVCacheSpec(num_pages=8,
                                                    page_size=ps))
    prefill, _ = mla.make_step_fns(cfg)
    table = np.zeros((B, 4), np.int32)
    slots = np.zeros((B, T), np.int32)
    for b in range(B):
        table[b] = np.arange(1 + 4 * b, 5 + 4 * b)
        for t in range(T):
            slots[b, t] = table[b, t // ps] * ps + t % ps
    _, kv_c, kv_r = prefill(params, jnp.asarray(tokens), jnp.asarray(pos),
                            kv_c, kv_r, jnp.asarray(table),
                            jnp.asarray(slots),
                            jnp.full((B,), T - 1, np.int32))
    for t in range(T):
        page, off = table[0][t // ps], t % ps
        np.testing.assert_allclose(np.asarray(c_all[:, 0, t, 0]),
                                   np.asarray(kv_c[:, page, 0, off]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(r_all[:, 0, t, 0]),
                                   np.asarray(kv_r[:, page, 0, off]),
                                   rtol=2e-5, atol=2e-5)


def test_mla_long_prompt_takes_ring_path(run_async):
    """MLA engine on a seq mesh routes long prompts through the latent
    ring prefill and the continuation is token-identical to the ordinary
    chunked-prefill MLA engine."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.parallel.mesh import MeshSpec
    from dynamo_tpu.runtime.engine import Context

    cfg = tiny_mla()
    params = mla.init_params(cfg, jax.random.PRNGKey(4))
    prompt = [(i * 13) % 200 + 1 for i in range(40)]

    async def gen(engine):
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    base_ecfg = dict(page_size=4, num_pages=64, max_batch=4,
                     prefill_chunk=32, prefill_buckets=(32,),
                     batch_buckets=(4,), page_buckets=(16,))
    want = run_async(gen(JaxEngine(cfg, EngineConfig(**base_ecfg),
                                   params=params)))

    mesh = MeshSpec(seq=4).build()
    engine = JaxEngine(cfg, EngineConfig(long_prefill_threshold=16,
                                         **base_ecfg),
                       params=params, mesh=mesh)
    got = run_async(gen(engine))
    assert engine.long_prefills_total == 1, "ring path not taken"
    assert got == want


# ------------------------------------------- PR 31: read-only pools, the
# latent decode kernel, the window, the deepseek_v3 router, prefix hits


def tiny_v3(**over):
    """Kanana-2 / DeepSeek-V3 in small: no query LoRA, one leading dense
    layer, sigmoid router with a selection bias, shared experts."""
    base = dict(model_type="deepseek_v3", num_layers=3, num_experts=8,
                num_experts_per_tok=3, moe_router="deepseek_v3",
                n_shared_experts=2, first_k_dense_replace=1,
                moe_intermediate_size=32, routed_scaling_factor=2.448,
                n_group=1, topk_group=1, norm_topk_prob=True)
    base.update(over)
    return tiny_mla(**base)


def v3_params(cfg, seed=0, bias=0.05):
    params = mla.init_params(cfg, jax.random.PRNGKey(seed))
    params["router_bias"] = bias * jax.random.normal(
        jax.random.PRNGKey(seed + 100), params["router_bias"].shape)
    return params


def _pool_case(lengths, P, ps=8, H=4, r=16, dr=8, L=2, seed=0, table=None):
    """Random pools, a page table of distinct pages a row (or ``table``:
    rows of page ids, padded to P with 0), queries."""
    rng = np.random.RandomState(seed)
    B = len(lengths)
    NP = 1 + B * P
    c_pool = jnp.asarray(rng.randn(L, NP, 1, ps, r), jnp.float32)
    r_pool = jnp.asarray(rng.randn(L, NP, 1, ps, dr), jnp.float32)
    if table is None:
        table = np.zeros((B, P), np.int32)
        for b, n in enumerate(lengths):
            live = -(-n // ps)
            table[b, :live] = 1 + b * P + rng.permutation(P)[:live]
    else:
        table = np.asarray([list(row) + [0] * (P - len(row))
                            for row in table], np.int32)
    q_lat = jnp.asarray(rng.randn(B, 1, H, r), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, 1, H, dr), jnp.float32)
    return (q_lat, q_rope, c_pool, r_pool, jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


# pages of 8 tokens; G pages a chunk of the kernel's row loop. ``table``:
# the rows' page ids as given (unused slots 0, or what the case puts there)
@pytest.mark.parametrize("lengths,P,G,table", [
    ([13, 8, 1, 40], 5, 8, None),      # ends inside a page, on one, one token
    ([0, 21, 0, 3], 4, 2, None),       # rows with nothing in the pool
    ([5], 1, 8, None),                 # a one-page table
    ([64, 37, 9, 0, 50], 8, 3, None),  # G divides neither P nor live pages
    # the row loop's hand-over: a row with nothing to read first, last,
    # between two live rows, two in a row, every row
    ([0, 30, 17], 4, 2, None),
    ([30, 17, 0], 4, 2, None),
    ([30, 0, 17], 4, 2, None),
    ([26, 0, 0, 9, 0], 4, 2, None),
    ([0, 0, 0], 4, 2, None),
    # a length on a chunk's edge (G 2 x 8 tokens), one short, one past,
    # and on the second chunk's edge
    ([16, 15, 17, 32, 31, 33], 5, 2, None),
    # G divides neither row's pages (7 and 5 pages by 3), then does (6)
    ([56, 40, 48], 8, 3, None),
    # one chunk holds the whole table, and more than it
    ([40, 3, 24], 5, 5, None),
    ([40, 3, 24], 5, 16, None),
    # two rows with the SAME page ids (prefix hits), a third sharing its
    # first pages; ids in descending order
    ([22, 22, 19], 4, 2, [[3, 7, 5], [3, 7, 5], [3, 7, 9]]),
    ([40, 17], 5, 2, [[10, 9, 7, 4, 2], [8, 6, 1]]),
    # a table wider than any row's pages, garbage in the unused slots
    # (ids of other rows' pages and of pages nobody wrote)
    ([20, 0, 9], 12, 4, [[5, 2, 8] + [30, 31, 1, 5, 2, 36, 7, 7, 3],
                          [9, 9, 9, 33, 4, 4, 4, 4, 4, 4, 4, 4],
                          [6, 1] + [35] * 10]),
], ids=["inside-page", "empty-rows", "one-page", "uneven-steps",
        "empty-first", "empty-last", "empty-between", "empty-runs",
        "all-empty", "chunk-edges", "uneven-chunks", "one-chunk",
        "chunk-past-table", "shared-pages", "descending-pages",
        "wide-table-garbage"])
def test_latent_kernel_matches_xla_arm(lengths, P, G, table):
    """The Pallas latent decode kernel (interpret mode) against the XLA
    arm on the same pools: the un-normalised p . c, the running maximum
    and the sum, per row and head; a row with nothing in the pool is
    (0, NEG_INF, 0) on both."""
    from dynamo_tpu.ops.paged_attention import (
        NEG_INF, latent_attention_decode_layered)

    q_lat, q_rope, c_pool, r_pool, table, lens = _pool_case(
        lengths, P, table=table)
    for layer in (0, 1):
        acc, m, l = latent_attention_decode_layered(
            q_lat[:, 0], q_rope[:, 0], c_pool, r_pool, jnp.int32(layer),
            table, lens, scale=0.2, interpret=True, pages_per_step=G)
        ref_acc, ref_m, ref_l = mla._attend_pool_xla(
            q_lat, q_rope, c_pool, r_pool, jnp.int32(layer), table, lens,
            0.2)
        np.testing.assert_allclose(acc, ref_acc[:, 0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(l, ref_l[:, 0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(m, ref_m[:, 0], rtol=1e-6, atol=1e-6)
        empty = np.asarray(lens) == 0
        assert (np.asarray(m)[empty] == NEG_INF).all()
        assert not np.asarray(acc)[empty].any()
        assert not np.asarray(l)[empty].any()


@pytest.mark.parametrize("G", [1, 2, 3, 8])
def test_latent_decode_kernel_copies_in_tpu_interpreter(G):
    """The kernel's own copies under the TPU interpreter, as
    tests/test_ops.py test_decode_kernel_copies_in_tpu_interpreter:
    buffers start as NaN and a copy's bytes arrive only when it is WAITED
    for, so a chunk computed before its wait, a wait that names another
    slot, a stale page that leaks through the mask or a latent buffer
    that was never zeroed (the latent is the value: 0 x NaN) shows as a
    wrong row. Rows with nothing to read first, between (two in a row)
    and last: each hands the next row's first chunk on."""
    from jax.experimental.pallas import tpu as pltpu

    from dynamo_tpu.ops.paged_attention import (
        NEG_INF, latent_attention_decode_layered)

    interp = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan")
    lengths = [0, 5, 0, 48, 0, 0, 16, 25, 0]
    q_lat, q_rope, c_pool, r_pool, table, lens = _pool_case(
        lengths, 6, r=128, dr=128)
    acc, m, l = latent_attention_decode_layered(
        q_lat[:, 0], q_rope[:, 0], c_pool, r_pool, jnp.int32(1), table, lens,
        scale=0.05, interpret=interp, pages_per_step=G)
    ref_acc, ref_m, ref_l = mla._attend_pool_xla(
        q_lat, q_rope, c_pool, r_pool, jnp.int32(1), table, lens, 0.05)
    np.testing.assert_allclose(acc, ref_acc[:, 0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(l, ref_l[:, 0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(m, ref_m[:, 0], rtol=1e-6, atol=1e-6)
    empty = np.asarray(lens) == 0
    assert (np.asarray(m)[empty] == NEG_INF).all()
    assert not np.asarray(acc)[empty].any()


@pytest.mark.parametrize("T,block_rows", [(6, 8), (16, 64), (4, 1024)])
def test_latent_prefill_kernel_matches_xla_arm(T, block_rows):
    """The same kernel over blocks of (token, head) rows, as a prefill
    chunk calls it: T queries a row against the row's cached prefix
    (rows with nothing cached among them), several blocks of rows a
    batch row and one."""
    from dynamo_tpu.ops.paged_attention import latent_attention_layered

    lengths = [21, 0, 40, 8]
    _, _, c_pool, r_pool, table, lens = _pool_case(lengths, 5)
    rng = np.random.RandomState(T)
    q_lat = jnp.asarray(rng.randn(4, T, 4, 16), jnp.float32)
    q_rope = jnp.asarray(rng.randn(4, T, 4, 8), jnp.float32)
    want = mla._attend_pool_xla(q_lat, q_rope, c_pool, r_pool,
                                jnp.int32(1), table, lens, 0.2)
    got = latent_attention_layered(
        q_lat.reshape(4, T * 4, 16), q_rope.reshape(4, T * 4, 8), c_pool,
        r_pool, jnp.int32(1), table, lens, scale=0.2, interpret=True,
        pages_per_step=2, block_rows=block_rows, name="test")
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g).reshape(w.shape), w,
                                   rtol=2e-5, atol=2e-5)
    via = mla._attend_pool(q_lat, q_rope, c_pool, r_pool, jnp.int32(1),
                           table, lens, 0.2, True)
    for g, w in zip(via, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def test_latent_parts_merge_to_full_attention():
    """A pool part and a buffer part merged by their statistics are one
    softmax over both: against a dense softmax over the concatenation."""
    q_lat, q_rope, c_pool, r_pool, table, lens = _pool_case([19, 0, 8], 3)
    rng = np.random.RandomState(5)
    c_loc = jnp.asarray(rng.randn(3, 4, 16), jnp.float32)
    r_loc = jnp.asarray(rng.randn(3, 4, 8), jnp.float32)
    seen = jnp.asarray(np.arange(4)[None, None, :] <= 2).repeat(3, 0)
    out = mla._merge(
        mla._attend_pool_xla(q_lat, q_rope, c_pool, r_pool, jnp.int32(1),
                             table, lens, 0.2),
        mla._attend_local(q_lat, q_rope, c_loc, r_loc, seen, 0.2))
    for b, n in enumerate([19, 0, 8]):
        pages = np.asarray(table)[b]
        c = np.concatenate([np.asarray(c_pool)[1, pages, 0].reshape(-1, 16)
                            [:n], np.asarray(c_loc)[b, :3]])
        kr = np.concatenate([np.asarray(r_pool)[1, pages, 0].reshape(-1, 8)
                             [:n], np.asarray(r_loc)[b, :3]])
        s = (np.asarray(q_lat)[b, 0] @ c.T
             + np.asarray(q_rope)[b, 0] @ kr.T) * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ c
        np.testing.assert_allclose(out[b, 0], want, rtol=2e-5, atol=2e-5)


def test_v3_router_bias_selects_and_never_weighs():
    """_deepseek_gate against a hand-written top-k: a bias that lifts a
    low-scored expert into the chosen set changes WHO is chosen; the
    weights are the chosen experts' unbiased sigmoid scores,
    renormalised and scaled, so a bias that changes no choice changes
    nothing at all."""
    cfg = tiny_v3(num_experts_per_tok=2)
    E = 8
    logits = np.array([2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0, -3.0],
                      np.float32)
    # x = one-hot rows through w_router = rows of logits: two tokens
    x = jnp.eye(2, 64, dtype=jnp.float32)[None]
    w_router = jnp.zeros((64, E), jnp.float32).at[0].set(logits).at[1].set(
        logits[::-1])
    sig = 1.0 / (1.0 + np.exp(-logits))

    def gate(bias):
        w, idx = mla._deepseek_gate(x, w_router, jnp.asarray(bias), cfg)
        return np.asarray(w)[0], np.asarray(idx)[0]

    w0, i0 = gate(np.zeros(E, np.float32))
    assert sorted(i0[0]) == [0, 1] and sorted(i0[1]) == [6, 7]
    # a bias too small to reorder anything: same choice, same weights
    small = np.linspace(0.0, 0.01, E).astype(np.float32)
    w1, i1 = gate(small)
    assert (i1 == i0).all()
    np.testing.assert_array_equal(w1, w0)
    # +0.5 on expert 5 (sigmoid 0.269 -> 0.769 > expert 1's 0.731):
    # token 0 now takes experts {0, 5}, weighted by sigmoid alone
    bias = np.zeros(E, np.float32)
    bias[5] = 0.5
    w2, i2 = gate(bias)
    assert sorted(i2[0]) == [0, 5]
    got = dict(zip(i2[0].tolist(), w2[0].tolist()))
    tot = sig[0] + sig[5]
    assert got[0] == pytest.approx(2.448 * sig[0] / tot, rel=1e-5)
    assert got[5] == pytest.approx(2.448 * sig[5] / tot, rel=1e-5)
    # token 1 (reversed logits) scores expert 5 at 0.622 + 0.5: chosen
    # beside expert 7 (0.881), expert 6 (0.731) dropped
    assert sorted(i2[1]) == [5, 7]


def _window_logprobs(cfg, params, prompt, k_steps, windows, interpret, ps=8,
                     pages=6):
    """Chunked prefill of ``prompt`` (one row beside a padding row), then
    ``windows`` fused windows of ``k_steps`` greedy steps: the tokens and
    the log-probabilities the window reports for them."""
    prefill, _ = mla.make_step_fns(cfg)
    window = mla.make_decode_window_fn(cfg, True, 8,
                                       pallas_interpret=interpret)
    kv_c, kv_r = mla.init_kv_cache(cfg, KVCacheSpec(num_pages=16,
                                                    page_size=ps))
    B, n = 2, len(prompt)
    table = np.zeros((B, pages), np.int32)
    table[0] = 3 + np.arange(pages)
    chunk = 16
    for start in range(0, n, chunk):
        part = prompt[start:start + chunk]
        toks = np.zeros((B, chunk), np.int32)
        pos = np.full((B, chunk), -1, np.int32)
        toks[0, :len(part)] = part
        pos[0, :len(part)] = np.arange(start, start + len(part))
        pslots = np.full((B, chunk // ps), 16, np.int32)
        npg = -(-len(part) // ps)
        pslots[0, :npg] = table[0, start // ps:start // ps + npg]
        logits, kv_c, kv_r = prefill(
            params, jnp.asarray(toks), jnp.asarray(pos), kv_c, kv_r,
            jnp.asarray(table), jnp.full((B, chunk), DROP_SLOT, jnp.int32),
            jnp.asarray([len(part) - 1, 0], jnp.int32), jnp.asarray(pslots))
    first = int(np.argmax(np.asarray(logits)[0]))
    tok = jnp.asarray([first, 0], jnp.int32)
    pos = jnp.asarray([n, -1], jnp.int32)
    done = jnp.asarray([False, True])
    steps = jnp.zeros(B, jnp.int32)
    remaining = jnp.asarray([1000, 0], jnp.int32)
    out, lps = [first], []
    for _ in range(windows):
        toks, emitted, aux, carry, kv_c, kv_r = window(
            params, tok, pos, done, steps, remaining, kv_c, kv_r,
            jnp.asarray(table), jnp.zeros(B), jnp.zeros(B, jnp.int32),
            jnp.ones(B), jnp.zeros(B, jnp.uint32),
            jnp.full((B, 2), -1, jnp.int32), None, k_steps=k_steps,
            logprobs_topn=2)
        tok, pos, done, steps, remaining = carry
        assert int(emitted[0]) == k_steps and int(emitted[1]) == 0
        out += np.asarray(toks)[0].tolist()
        lps += np.asarray(aux[0])[0].tolist()
    return out, lps, (kv_c, kv_r)


@pytest.mark.parametrize("interpret,padded", [
    (False, False), (True, False), (True, True)],
    ids=["xla", "kernel", "kernel-padded"])
def test_mla_window_decodes_through_the_latent_cache(interpret, padded,
                                                     monkeypatch):
    """Chunked prefill (whole pages, a prompt that ends inside a page)
    then two fused windows of 4 greedy steps, which cross a page
    boundary, on the dense-then-routed stack with a live selection
    bias: each step's chosen-token log-probability against the full
    non-absorbed forward over prompt + the tokens so far; both decode
    arms (XLA, the Pallas kernel in interpret mode), and the kernel
    with the rope pool as wide as a TPU holds it (whole lanes)."""
    if padded:
        monkeypatch.setattr(mla, "rope_width", lambda cfg: 128)
    if interpret:       # the chunked prefill through the kernel as well
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = tiny_v3()
    params = v3_params(cfg)
    prompt = np.random.RandomState(3).randint(1, 500, 27).tolist()
    toks, lps, _ = _window_logprobs(cfg, params, prompt, 4, 2, interpret)
    assert len(toks) == 9 and len(lps) == 8
    seq = prompt + toks
    ref = jax.nn.log_softmax(
        mla.reference_forward(params, cfg, jnp.asarray([seq]))[0], axis=-1)
    ref = np.asarray(ref)
    # toks[0] is the prefill's greedy choice at the last prompt position
    assert toks[0] == int(np.argmax(ref[len(prompt) - 1]))
    for j, lp in enumerate(lps):
        at = len(prompt) + j            # position whose logits chose toks[j+1]
        assert toks[j + 1] == int(np.argmax(ref[at]))
        assert lp == pytest.approx(ref[at, toks[j + 1]], abs=3e-4)


def test_mla_window_commit_equals_prefill_of_the_same_tokens():
    """What two windows leave in the pools is what a prefill of prompt +
    generated tokens writes: latents and rope keys, page by page."""
    cfg = tiny_v3()
    params = v3_params(cfg, seed=2)
    prompt = np.random.RandomState(4).randint(1, 500, 21).tolist()
    toks, _, (kv_c, kv_r) = _window_logprobs(cfg, params, prompt, 4, 2,
                                             False)
    seq = prompt + toks[:-1]            # the last token was never an input
    _, _, (want_c, want_r) = _window_logprobs(cfg, params, seq, 1, 0, False)
    n = len(seq)
    for got, want in ((kv_c, want_c), (kv_r, want_r)):
        got = np.asarray(got)[:, 3:9, 0].reshape(cfg.num_layers, -1,
                                                 got.shape[-1])[:, :n]
        want = np.asarray(want)[:, 3:9, 0].reshape(cfg.num_layers, -1,
                                                   want.shape[-1])[:, :n]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_engine_takes_the_mla_window_and_a_prefix_hit_changes_nothing(
        run_async):
    """JaxEngine serves a latent-attention model through
    mla.make_decode_window_fn, and a second request over the same 3-page
    prefix takes the latent pages from the prefix cache and reads the
    same log-probabilities as a cold engine gives it."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                                 PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.runtime.engine import Context

    cfg = tiny_v3()
    params = v3_params(cfg, seed=7)
    ecfg = EngineConfig(page_size=8, num_pages=64, max_batch=4,
                        prefill_chunk=32, prefill_buckets=(16, 32),
                        batch_buckets=(4,), page_buckets=(8,),
                        decode_steps=4)
    rng = np.random.RandomState(11)
    prefix = rng.randint(1, 500, 24).tolist()
    ask_a = prefix + rng.randint(1, 500, 9).tolist()
    ask_b = prefix + rng.randint(1, 500, 5).tolist()

    async def ask(engine, prompt):
        req = PreprocessedRequest(
            token_ids=prompt, sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
            output=OutputOptions(logprobs=3), eos_token_ids=[])
        toks, tops = [], []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            tops.extend(out.top_logprobs or [])
            if out.finish_reason:
                break
        return toks, tops

    async def scenario():
        warm = JaxEngine(cfg, ecfg, params=params, seed=0)
        await ask(warm, ask_a)
        hits0 = warm.stats()["prefix_hit_tokens_total"]
        got = await ask(warm, ask_b)
        hits = warm.stats()["prefix_hit_tokens_total"] - hits0
        await warm.stop()
        cold = JaxEngine(cfg, ecfg, params=params, seed=0)
        want = await ask(cold, ask_b)
        assert cold.stats()["prefix_hit_tokens_total"] == 0
        await cold.stop()
        return got, want, hits

    (toks, tops), (toks_c, tops_c), hits = run_async(scenario())
    assert hits == 24                      # three whole pages of 8
    assert toks == toks_c and len(toks) == 6
    for a, b in zip(tops, tops_c):
        assert a.keys() == b.keys()
        for k in a:
            assert a[k] == pytest.approx(b[k], abs=2e-4)
