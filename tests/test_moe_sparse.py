"""Sparse MoE dispatch (models/llama.py moe_experts_blocked): parity
with the dense-over-experts einsum under a ``live`` mask, the blocks it
runs (``moe_block_plan``: work follows the live pairs, not the bucket),
quantized-weight interplay, reading ``w[layer, expert]`` in place, the
shape rule that picks the form, and serving-path engagement. Reference
analog: vLLM's fused_moe dispatch, which the reference's flagship
Mixtral/DeepSeek configs serve through."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models import llama


def _routing(key, N, E, k):
    logits = jax.random.normal(key, (N, E))
    w, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(w, axis=-1), idx


def _dense_ref(x, w, idx, wg, wu, wd):
    E = wg.shape[0]
    gate = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                   * w[..., None], axis=-2)           # [N, E]
    ge = jnp.einsum("nd,edi->nei", x, wg)
    up = jnp.einsum("nd,edi->nei", x, wu)
    act = jax.nn.silu(ge) * up
    down = jnp.einsum("nei,eid->ned", act, wd)
    return jnp.einsum("ned,ne->nd", down, gate)


def _weights(key, E, D, I):
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / np.sqrt(D)
    return (jax.random.normal(k1, (E, D, I)) * s,
            jax.random.normal(k2, (E, D, I)) * s,
            jax.random.normal(k3, (E, I, D)) / np.sqrt(I))


def _case(N, E, k, D=32, I=48, seed=0):
    wg, wu, wd = _weights(jax.random.PRNGKey(seed), E, D, I)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (N, D), jnp.float32)
    w, idx = _routing(jax.random.PRNGKey(seed + 2), N, E, k)
    return x, w, idx, wg, wu, wd


@pytest.mark.parametrize("N,E,k,block", [
    (512, 8, 2, 256),
    (300, 16, 4, 64),   # N*k not a block multiple; many experts
    (256, 4, 1, 256),   # k=1
])
def test_blocked_matches_dense(N, E, k, block):
    args = _case(N, E, k)
    ref = _dense_ref(*args)
    got = llama.moe_experts_blocked(*args, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("routing", ["top_k", "dead_to_one_expert"])
@pytest.mark.parametrize("share", [0.0, 0.13, 0.57, 1.0])
@pytest.mark.parametrize("E,k", [(8, 2), (128, 8)])
def test_blocked_live_mask_matches_dense(E, k, share, routing):
    """Padding rows make no pair: live rows agree with the dense form,
    dead rows are exact zeros — also when every dead row is the same row
    routed to the same experts, which is what a padded prefill holds."""
    N = 256
    x, w, idx, wg, wu, wd = _case(N, E, k, seed=3)
    live = jax.random.uniform(jax.random.PRNGKey(9), (N,)) < share
    if routing == "dead_to_one_expert":
        x = jnp.where(live[:, None], x, x[:1])
        idx = jnp.where(live[:, None], idx, jnp.arange(k)[None, :] + 1)
        w = jnp.where(live[:, None], w, 1.0 / k)
    ref = np.asarray(_dense_ref(x, w, idx, wg, wu, wd))
    got = np.asarray(llama.moe_experts_blocked(
        x, w, idx, wg, wu, wd, llama.moe_block(N, k, wg.shape), live=live))
    keep = np.asarray(live)
    np.testing.assert_allclose(got[keep], ref[keep], rtol=2e-4, atol=2e-4)
    assert not got[~keep].any()


def test_blocked_skewed_routing_no_drops():
    """Every token routed to ONE expert — that expert's run of blocks
    must absorb the full N*k load without dropping tokens (the
    correctness property capacity-based dispatches give up)."""
    N, E, k, D, I = 257, 8, 2, 16, 24
    wg, wu, wd = _weights(jax.random.PRNGKey(3), E, D, I)
    x = jax.random.normal(jax.random.PRNGKey(4), (N, D), jnp.float32)
    idx = jnp.full((N, k), 3, jnp.int32)
    w = jnp.full((N, k), 0.5, jnp.float32)
    ref = _dense_ref(x, w, idx, wg, wu, wd)
    got = llama.moe_experts_blocked(x, w, idx, wg, wu, wd, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("counts,block", [
    ([0, 0, 0, 0], 32),                 # nothing live: no block runs
    ([1, 0, 0, 300], 128),              # empty experts own no block
    ([128, 128, 128, 128], 128),        # exact multiples: no overhang
    ([129, 1, 127, 0, 256, 3], 128),
    ([17] * 128, 128),                  # Qwen3-MoE at ~13% live
    ([5, 70, 33, 64, 65, 0, 1, 12], 32),
])
def test_block_plan_runs_only_blocks_that_hold_a_pair(counts, block):
    """The executed-block count is sum_e ceil(count_e / block), and the
    blocks tile each expert's run of the sorted pairs in order."""
    E, total = len(counts), sum(counts)
    n_max = -(-total // block) + E
    n_blocks, block_e, row0, row_end = (
        np.asarray(a) for a in llama.moe_block_plan(
            jnp.asarray(counts, jnp.int32), block, n_max))
    assert n_blocks == sum(-(-c // block) for c in counts) <= n_max
    want = [(e, start + b * block, start + c)
            for e, (c, start) in enumerate(
                zip(counts, np.cumsum(counts) - counts))
            for b in range(-(-c // block))]
    got = list(zip(block_e[:n_blocks], row0[:n_blocks], row_end[:n_blocks]))
    assert got == want


@pytest.mark.parametrize("share,most", [(0.13, 0.13), (1.0, 0.5)])
def test_blocked_work_follows_live_pairs(share, most):
    """Row-MLPs computed (blocks run x block) against the dense form's
    N*E, at Qwen3-MoE's routing shape: a PB 8 bucket holding one prompt
    pays for the prompt, a full bucket at most half of dense (the old
    static scan paid for 192 blocks of 256 rows, 3/16 of N*E, at any fill)."""
    N, E, k = 2048, 128, 8
    block = llama.moe_block(N, k, (E, 2048, 768))
    _, idx = _routing(jax.random.PRNGKey(11), N, E, k)
    live = np.arange(N) < int(share * N)
    counts = np.bincount(np.asarray(idx)[live].reshape(-1), minlength=E)
    n_blocks = int(llama.moe_block_plan(
        jnp.asarray(counts, jnp.int32), block, N * k // block + E)[0])
    assert n_blocks * block <= most * N * E


@pytest.mark.parametrize("in_place", [False, True],
                         ids=["layer_stack", "in_place"])
def test_blocked_with_quantized_experts(in_place):
    """_dyn_expert slices the int8 stack THEN dequantizes — parity with
    quantize→dense within matmul tolerance; in place it slices layer and
    expert out of the whole [L, E, ...] parameter in one step."""
    from dynamo_tpu.models.quant import QuantInt8, quantize_int8

    N, E, k = 300, 8, 2
    x, w, idx, wg, wu, wd = _case(N, E, k, seed=8)
    qs = [quantize_int8(a) for a in (wg, wu, wd)]
    ref = _dense_ref(x, w, idx, *(q.dequant(jnp.float32) for q in qs))
    layer = None
    if in_place:
        layer = jnp.int32(1)
        qs = [QuantInt8(jnp.stack([q.q * 0, q.q, -q.q]),
                        jnp.stack([q.s, q.s, q.s])) for q in qs]
    got = jax.jit(lambda *a: llama.moe_experts_blocked(
        *a, 64, layer=layer))(x, w, idx, *qs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("E,k", [(8, 2), (128, 8)])
@pytest.mark.parametrize("N,use_sorted", [
    (1, False), (4, False), (8, False), (32, False), (64, False),
    (128, False),               # every decode window, PB 1 x T 128
    (512, True), (2048, True),  # PB 1 x T 512, PB 4 x T 512, PB 8 x T 256
])
def test_shape_rule(N, E, k, use_sorted):
    """The form by what the shape says about the chip, at the shapes the
    benchmark's cells run (Mixtral 8 top-2, Qwen3-MoE 128 top-8)."""
    assert llama._moe_use_blocked(None, N, E, k) is use_sorted


def test_shape_rule_keeps_dense_on_a_mesh():
    from dynamo_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(data=2, model=2, expert=2).build()
    assert not llama._moe_use_blocked(mesh, 4096, 8, 2)


@pytest.mark.parametrize("N,k,w_shape,block", [
    (2048, 8, (128, 2048, 768), 128),    # Qwen3-MoE, PB 8 x T 256
    (512, 8, (128, 2048, 768), 32),      # few pairs an expert: the floor
    (8192, 8, (128, 2048, 768), 256),    # many: the ridge is the ceiling
    (2048, 2, (8, 4096, 14336), 256),    # Mixtral, PB 4 x T 512
    (512, 2, (3, 8, 4096, 14336), 256),  # Mixtral, PB 1 x T 512, in place:
                                         # the weights outweigh the rows
])
def test_block_comes_from_the_shapes(N, k, w_shape, block):
    assert llama.moe_block(N, k, w_shape) == block


def test_moe_mlp_paths_agree():
    """_moe_mlp on 512 rows (the sorted form, by the rule) == the same
    rows in four pieces of 128 (the dense form, by the rule) — strategy
    is a pure execution detail."""
    cfg = ModelConfig.tiny(num_experts=8, num_experts_per_tok=2,
                           model_type="mixtral")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    wr, wg, wu, wd = (params[k][0] for k in
                      ("w_router", "w_gate", "w_up", "w_down"))
    big = jax.random.normal(jax.random.PRNGKey(1),
                            (1, 512, cfg.hidden_size), jnp.bfloat16)
    assert llama._moe_use_blocked(None, 512, 8, 2)
    assert not llama._moe_use_blocked(None, 128, 8, 2)
    out_blocked = llama._moe_mlp(big, wr, wg, wu, wd, 2)
    out_dense = jnp.concatenate(
        [llama._moe_mlp(big[:, i:i + 128], wr, wg, wu, wd, 2)
         for i in range(0, 512, 128)], axis=1)
    np.testing.assert_allclose(
        np.asarray(out_blocked, np.float32),
        np.asarray(out_dense, np.float32),
        rtol=5e-2, atol=5e-2)  # bf16 inputs; different summation orders


def _prefill_setup(seed, num_pages=64, ps=8):
    cfg = ModelConfig.tiny(num_experts=8, num_experts_per_tok=2,
                           model_type="mixtral")
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    kv = llama.init_kv_cache(cfg, llama.KVCacheSpec(num_pages, ps))
    table = jnp.arange(num_pages, dtype=jnp.int32).reshape(1, num_pages)
    return cfg, params, kv, table


def _chunk(table, tokens, start, n_live, T, ps=8):
    """One padded prefill chunk as the engine builds it: positions -1
    and dropped cache writes past the live tokens."""
    pos = jnp.arange(start, start + T)
    live = jnp.arange(T) < n_live
    tok = jnp.where(live, jnp.pad(tokens[start:start + n_live],
                                  (0, T - n_live)), 0)
    flat = jnp.where(live, table[0, pos // ps] * ps + pos % ps,
                     llama.DROP_SLOT)
    return (tok[None], jnp.where(live, pos, -1)[None], flat[None])


def test_moe_serving_prefill_blocked_matches_dense():
    """End-to-end through llama.forward (paged prefill): 300 tokens in
    one T=512 chunk (the sorted form, in place, 212 padding rows) == the
    same tokens in chunks of T=128 (the dense form) continuing on the
    cache."""
    cfg, params, (kv_k, kv_v), table = _prefill_setup(2)
    n = 300
    tokens = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, 500)

    tok, pos, flat = _chunk(table, tokens, 0, n, 512)
    blocked_h, _, _ = llama.forward(params, cfg, tok, pos, kv_k, kv_v,
                                    table, flat)
    parts = []
    for start in range(0, n, 128):
        tok, pos, flat = _chunk(table, tokens, start,
                                min(128, n - start), 128)
        h, kv_k, kv_v = llama.forward(params, cfg, tok, pos, kv_k, kv_v,
                                      table, flat)
        parts.append(h[0, :min(128, n - start)])
    np.testing.assert_allclose(
        np.asarray(blocked_h[0, :n], np.float32),
        np.asarray(jnp.concatenate(parts), np.float32),
        rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("T,slices_a_layer", [(512, False), (128, True)],
                         ids=["sorted", "dense"])
def test_prefill_program_slices_no_layers_expert_stack(T, slices_a_layer):
    """Lowered text of the prefill program: in the sorted form no slice
    the size of a layer's [E, D, I] expert stack (the layer scan does not
    carry the stacks as xs; the block loop reads w[layer, expert] from
    the parameter), only one expert's; the dense form, which reads every
    expert anyway, still takes its layer's stack from the scan — the
    check can see one."""
    cfg, params, (kv_k, kv_v), table = _prefill_setup(4)
    E, D, I = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    prefill, _ = llama.make_step_fns(cfg, allow_pallas=False)
    i32 = jax.ShapeDtypeStruct((1, T), jnp.int32)
    text = prefill.lower(
        params, i32, i32, kv_k, kv_v, table, i32,
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    sliced = re.findall(r"dynamic_slice.*-> tensor<([0-9x]+)x\w+>", text)
    assert sliced
    stack = {"1x%dx%dx%d" % (E, D, I), "1x%dx%dx%d" % (E, I, D)}
    one = {"1x1x%dx%d" % (D, I), "1x1x%dx%d" % (I, D)}
    assert bool(stack & set(sliced)) == slices_a_layer
    assert bool(one & set(sliced)) != slices_a_layer
