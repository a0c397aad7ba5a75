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
    (128, False),               # every decode window of a token a
                                # step, PB 1 x T 128: under the ridge
    (256, True),                # the ridge (240 FLOP a byte) passed: a
                                # PB 1 x T 256 chunk, the block window's
                                # [64, 4] forward
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


@pytest.mark.parametrize("N,k,w_shape,width,block", [
    (2048, 8, (128, 2048, 768), None, 128),   # Qwen3-MoE, PB 8 x T 256
    (512, 8, (128, 2048, 768), None, 32),     # few pairs an expert: the
                                              # floor
    (256, 8, (128, 2048, 768), None, 32),     # SDAR's [64, 4] forward
    (256, 4, (64, 2048, 1536), None, 32),     # LFM2, PB 1 x T 256
    (8192, 8, (128, 2048, 768), None, 256),   # many: the ceiling
    (2048, 2, (8, 4096, 14336), None, 256),   # Mixtral, PB 4 x T 512
    (512, 2, (3, 8, 4096, 14336), None, 256),  # Mixtral, PB 1 x T 512, in
                                               # place: its matrices stream
                                               # in tiles, again a block
    (1024, 4, (64, 2048, 1536), None, 64),    # LFM2, PB 4 x T 256: held
    (2048, 4, (64, 2048, 1536), None, 128),   # whole, so by the pairs
    (512, 10, (36, 4096, 768), 72, 128),      # granite, PB 1 x T 512: half
    (2048, 10, (36, 4096, 768), 72, 256),     # the pairs stay on this chip
    (512, 10, (36, 4096, 768), None, 256),
])
def test_block_comes_from_the_shapes(N, k, w_shape, width, block):
    assert llama.moe_block(N, k, w_shape, width) == block


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


# ---- the dense arm: one contraction over (e, i), the gate inside (PR 59)


_ABSENT = 7     # experts the gate scores beyond the E held, with ``first``


def _dense_arm_case(shape, first, E=5, k=3, D=16, I=24, seed=59):
    """x [B, T, D], a gate over ``E`` experts (``first`` None) or over
    E + _ABSENT of which the stacks hold ``[first, first + E)``, stacks
    in bfloat16 as a cell stores them. With ``first`` the first token's
    experts are all absent and the second's all held."""
    rng = np.random.default_rng(seed)
    B, T = shape
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    wg, wu, wd = (f(E, D, I) * D ** -0.5, f(E, D, I) * D ** -0.5,
                  f(E, I, D) * I ** -0.5)
    wg, wu, wd = (w.astype(jnp.bfloat16) for w in (wg, wu, wd))
    width = E if first is None else E + _ABSENT
    w, idx = _routing(jax.random.PRNGKey(seed), B * T, width, k)
    if first is not None:
        idx = idx.at[0].set(jnp.asarray([0, width - 1, first + E]))
        idx = idx.at[1].set(first + jnp.arange(k))
    return (f(B, T, D), w.reshape(B, T, k), idx.reshape(B, T, k), wg, wu, wd)


def _expert_loop(x, w, idx, wg, wu, wd, first, act):
    """A pair at a time, float32 at the highest precision: the part of
    the sum the held experts make."""
    hi = jax.lax.Precision.HIGHEST
    B, T, D = x.shape
    E = wg.shape[0]
    wg, wu, wd = (np.asarray(v, np.float32) for v in (wg, wu, wd))
    want = np.zeros((B, T, D), np.float32)
    for b in range(B):
        for t in range(T):
            for j in range(idx.shape[-1]):
                e = int(idx[b, t, j]) - (first or 0)
                if 0 <= e < E:
                    h = (act(jnp.matmul(x[b, t], wg[e], precision=hi))
                         * jnp.matmul(x[b, t], wu[e], precision=hi))
                    want[b, t] += float(w[b, t, j]) * np.asarray(
                        jnp.matmul(h, wd[e], precision=hi))
    return want


@pytest.mark.parametrize("shape", [(6, 1), (1, 7), (3, 4)],
                         ids=["N_1_D", "1_T_D", "B_4_D"])
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("act", [jax.nn.silu, jax.nn.relu],
                         ids=["silu", "relu"])
@pytest.mark.parametrize("first", [None, 4], ids=lambda f: "first_%s" % f)
def test_dense_arm_matches_a_loop_over_the_held_pairs(first, act, out_dtype,
                                                      shape):
    """llama.moe_experts' dense arm (the gate folded into the down
    product: one contraction over (e, i) on the [E, I, D] stack as
    stored) against a loop over the pairs by hand, in the three input
    forms the programs hand it (a decode step's [N, 1, D], a prefill
    chunk's [1, T, D], a block window's [B, 4, D]). A pair whose expert
    is not held adds EXACTLY zero: a token with none of its experts
    here reads 0.0 in every element, whatever the result's type."""
    x, w, idx, wg, wu, wd = _dense_arm_case(shape, first)
    got = llama.moe_experts(x, w, idx, wg, wu, wd, False, first=first,
                            act=act, out_dtype=out_dtype)
    assert got.dtype == out_dtype and got.shape == x.shape
    want = _expert_loop(x, w, idx, wg, wu, wd, first, act)
    # float32 reads 4e-7 here; bfloat16 is the result's own rounding
    # (7e-3 on values up to 2.8)
    tol = 1e-5 if out_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)
    if first is not None:
        assert (np.asarray(got, np.float32).reshape(-1, x.shape[-1])[0]
                == 0).all()
        assert np.abs(want.reshape(-1, x.shape[-1])[1]).max() > 0.05


@pytest.mark.parametrize("shape", [(6, 1), (1, 7), (3, 4)],
                         ids=["N_1_D", "1_T_D", "B_4_D"])
@pytest.mark.parametrize("first", [None, 4], ids=lambda f: "first_%s" % f)
def test_dense_arm_matches_the_sorted_arm(first, shape):
    """The two arms of llama.moe_experts on one routing, inside the
    tolerance this file holds the sorted form to against the dense
    reference (2e-4; float32 on the CPU reads 7e-7: the arms differ in
    where the gate's weight multiplies and in the order of the sum)."""
    x, w, idx, wg, wu, wd = _dense_arm_case(shape, first)
    dense = llama.moe_experts(x, w, idx, wg, wu, wd, False, first=first)
    width = None if first is None else wg.shape[0] + _ABSENT
    srt = llama.moe_experts(x, w, idx, wg, wu, wd, True, first=first,
                            width=width)
    np.testing.assert_allclose(np.asarray(srt), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


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


# ---- the blocks as one grouped-matmul kernel (ops/moe_grouped.py) ----
#
# Under the Pallas interpreter, which the hook of the other kernels turns
# on off the TPU. The interpreter hands out result buffers filled with
# NaN: a slot no block wrote reads as NaN if anyone reads it.

@pytest.fixture
def kernel_form(monkeypatch):
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DYN_DISABLE_PALLAS", raising=False)


def _calls(monkeypatch):
    """Count the kernel's calls from moe_experts_blocked."""
    seen = []
    real = llama.moe_grouped_mlp

    def counted(*a, **kw):
        seen.append(kw["block"])
        return real(*a, **kw)

    monkeypatch.setattr(llama, "moe_grouped_mlp", counted)
    return seen


@pytest.mark.parametrize("N,E,k,D,I,block", [
    (300, 16, 4, 256, 128, 32),    # thin experts: I < D, many of them
    (512, 8, 2, 128, 512, 256),    # wide: I > D, few
    (256, 4, 1, 128, 128, 32),     # k = 1; every expert owns two blocks
    (130, 8, 8, 128, 256, 64),     # k = E: every expert takes every row
    (512, 128, 8, 128, 128, 32),   # Qwen3-MoE's routing shape
])
def test_kernel_matches_dense(kernel_form, monkeypatch, N, E, k, D, I, block):
    seen = _calls(monkeypatch)
    args = _case(N, E, k, D, I, seed=5)
    ref = _dense_ref(*args)
    got = llama.moe_experts_blocked(*args, block)
    assert seen == [block]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("first", [None, 4, 12], ids=lambda f: "first_%s" % f)
@pytest.mark.parametrize("share", [0.13, 1.0])
def test_kernel_live_rows_held_experts_in_place(kernel_form, monkeypatch,
                                                share, first):
    """Dead rows make no pair, a pair whose expert is not held neither
    (``first``: the stacks hold experts [first, first + 8) of the 24 the
    router scores), and the stacks are the whole [L, E, ...] parameters:
    the layers around the one asked for are NaN, so reading them
    shows."""
    seen = _calls(monkeypatch)
    N, E, k, D, I = 256, 8, 4, 128, 128
    x, _, _, wg, wu, wd = _case(N, E, k, D, I, seed=6)
    w, idx = _routing(jax.random.PRNGKey(7), N, E if first is None else 24, k)
    live = jax.random.uniform(jax.random.PRNGKey(9), (N,)) < share
    held = idx if first is None else idx - first
    here = (held >= 0) & (held < E)
    ref = np.asarray(_dense_ref(x, jnp.where(here, w, 0.0),
                                jnp.where(here, held, 0), wg, wu, wd))
    stacks = [jnp.stack([a * jnp.nan, a, a * jnp.nan]) for a in (wg, wu, wd)]
    got = np.asarray(jax.jit(lambda *a: llama.moe_experts_blocked(
        *a, 32, live=live, layer=jnp.int32(1), first=first))(
            x, w, idx, *stacks))
    assert seen == [32]
    keep = np.asarray(live)
    np.testing.assert_allclose(got[keep], ref[keep], rtol=2e-4, atol=2e-4)
    assert not got[~keep].any()


def test_kernel_dispatch_follows_the_live_chunks(kernel_form):
    """A bucket of four chunks of 256 tokens of which two hold live
    rows, and more blocks than one turn of the gather takes: the rows in
    and the pairs read back go by chunks of live work
    (``_moe_rows_in`` / ``_moe_rows_out``); the dead chunks read zeros."""
    N, E, k, D, I = 1024, 8, 8, 128, 128
    x, w, idx, wg, wu, wd = _case(N, E, k, D, I, seed=15)
    rows = np.arange(N)
    live = jnp.asarray(((rows >= 256) & (rows < 300)) | (rows >= 800))
    ref = np.asarray(_dense_ref(x, w, idx, wg, wu, wd))
    got = np.asarray(jax.jit(lambda *a: llama.moe_experts_blocked(
        *a, 32, live=live))(x, w, idx, wg, wu, wd))
    keep = np.asarray(live)
    assert llama._MOE_CHUNK_ROWS // 32 < keep.sum() * k // 32  # > one turn
    np.testing.assert_allclose(got[keep], ref[keep], rtol=2e-4, atol=2e-4)
    assert not got[~keep].any()


def test_kernel_expert_without_a_pair_and_with_many_blocks(kernel_form):
    """Experts 1 and 6 get every pair (five blocks of 64 for 300 rows
    each), the other six none: they own no block and are never read."""
    N, E, k, D, I = 300, 8, 2, 128, 128
    x, w, _, wg, wu, wd = _case(N, E, k, D, I, seed=10)
    idx = jnp.tile(jnp.asarray([[1, 6]], jnp.int32), (N, 1))
    ref = _dense_ref(x, w, idx, wg, wu, wd)
    unused = jnp.asarray([0, 2, 3, 4, 5, 7])
    wg, wu, wd = (a.at[unused].set(jnp.nan) for a in (wg, wu, wd))
    got = llama.moe_experts_blocked(x, w, idx, wg, wu, wd, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_all_rows_dead(kernel_form, monkeypatch):
    """No block runs, no slot is written: zeros, not what the slots
    held."""
    seen = _calls(monkeypatch)
    args = _case(256, 8, 2, 128, 128, seed=11)
    got = np.asarray(llama.moe_experts_blocked(
        *args, 64, live=jnp.zeros((256,), bool)))
    assert seen == [64]
    assert got.shape == (256, 128) and not got.any()


def test_kernel_bfloat16_stacks(kernel_form):
    """Stored bfloat16, the operands go to the dots as bfloat16 (one pass
    of the MXU, what XLA's default precision gives the loop form on the
    chip) and accumulate in float32: the dense form within bfloat16's
    rounding of x and of the activation."""
    N, E, k, D, I = 256, 8, 2, 128, 256
    x, w, idx, wg, wu, wd = _case(N, E, k, D, I, seed=12)
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    wg, wu, wd = (a.astype(jnp.bfloat16) for a in (wg, wu, wd))
    ref = np.asarray(_dense_ref(x, w, idx, *(a.astype(jnp.float32)
                                             for a in (wg, wu, wd))))
    got = np.asarray(llama.moe_experts_blocked(x, w, idx, wg, wu, wd, 64))
    assert np.abs(got - ref).max() <= 0.01 * np.abs(ref).max()


@pytest.mark.parametrize("tile", [None, 128, 256], ids=lambda t: "tile_%s" % t)
def test_kernel_tiles_the_intermediate_width(tile):
    """The kernel alone on a plan of five blocks of which three run,
    ``I`` whole or in tiles that accumulate the down-projection: the
    same rows either way, and a block past the plan's end writes
    nothing (its slot keeps the interpreter's NaN)."""
    from dynamo_tpu.ops.moe_grouped import moe_grouped_mlp

    E, D, I, block = 4, 128, 512, 32
    wg, wu, wd = (a[None] for a in _weights(jax.random.PRNGKey(13), E, D, I))
    xs = jax.random.normal(jax.random.PRNGKey(14), (5 * block, D))
    block_e = jnp.asarray([0, 2, 2, 3, 3], jnp.int32)
    ys = np.asarray(moe_grouped_mlp(
        xs, wg, wu, wd, jnp.int32(0), jnp.int32(3), block_e, block=block,
        interpret=True, tile=tile))
    for j in range(3):
        e, rows = int(block_e[j]), slice(j * block, (j + 1) * block)
        want = (jax.nn.silu(xs[rows] @ wg[0, e]) * (xs[rows] @ wu[0, e])
                ) @ wd[0, e]
        np.testing.assert_allclose(ys[rows], np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    assert np.isnan(ys[3 * block:]).all()


@pytest.mark.parametrize("block,D,I,whole", [
    (128, 2048, 768, True),      # Qwen3-30B-A3B, Kanana-2, SDAR (cells
                                 # 2, 5, 7)
    (256, 2048, 1536, True),     # LFM2-24B-A2B (cell 6)
    (256, 4096, 768, True),      # granite-4.0-h-small (cell 8)
    (256, 2048, 1408, True),     # Moonlight-16B-A3B
    (256, 4096, 14336, False),   # Mixtral-8x7B (cells 1, 3)
])
def test_tile_comes_from_the_shapes(block, D, I, whole):
    """An expert's matrices whole where two copies of them fit VMEM
    beside the rows (consecutive blocks of one expert then share a
    fetch), else whole lanes that divide I and fit."""
    from dynamo_tpu.ops import moe_grouped

    ti = moe_grouped.i_tile(block, D, I, 2)
    assert (ti == I) is whole
    assert I % ti == 0 and (ti == I or ti % 128 == 0)
    assert moe_grouped._vmem_bytes(block, D, ti, 2) <= moe_grouped._VMEM_BYTES
    if not whole:
        assert moe_grouped._vmem_bytes(block, D, 2 * ti, 2) \
            > moe_grouped._VMEM_BYTES or I % (2 * ti)


@pytest.mark.parametrize("why", ["off_the_tpu", "pallas_disabled", "int8"])
def test_loop_form_runs_where_the_kernel_may_not(monkeypatch, why):
    """Off the TPU without the tests' hook, under the kill switch, and
    for int8 stacks (an expert is dequantized at a time) the blocks run
    as the loop of small programs: the kernel is never called."""
    from dynamo_tpu.models.quant import quantize_int8

    monkeypatch.delenv("DYN_PALLAS_INTERPRET", raising=False)
    if why != "off_the_tpu":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    if why == "pallas_disabled":
        monkeypatch.setenv("DYN_DISABLE_PALLAS", "1")
    seen = _calls(monkeypatch)
    x, w, idx, wg, wu, wd = _case(300, 8, 2, seed=8)
    if why == "int8":
        qs = [quantize_int8(a) for a in (wg, wu, wd)]
        wg, wu, wd = (q.dequant(jnp.float32) for q in qs)
    else:
        qs = [wg, wu, wd]
    assert llama._moe_kernel_interpret(qs[0]) is None
    got = llama.moe_experts_blocked(x, w, idx, *qs, 64)
    assert not seen
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense_ref(x, w, idx, wg, wu, wd)),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,hook,takes", [
    (512, True, True), (128, True, False), (512, False, False)])
def test_engine_counts_the_prefill_programs_that_take_the_kernel(
        monkeypatch, T, hook, takes):
    """``moe_kernel_takes`` is the rule ``_dispatch_prefill`` counts
    ``moe_grouped_programs_total`` by: the sorted form by the bucket's
    rows, and a form of running it that is the kernel."""
    monkeypatch.delenv("DYN_PALLAS_INTERPRET", raising=False)
    if hook:
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg, params, _, _ = _prefill_setup(1)
    assert llama.moe_kernel_takes(cfg, params, None, T) is takes
    dense = ModelConfig.tiny()
    assert not llama.moe_kernel_takes(
        dense, llama.init_params(dense, jax.random.PRNGKey(0)), None, T)


def test_serving_prefill_through_the_kernel_matches_the_loop(monkeypatch):
    """llama.forward on a padded T = 512 chunk, experts in place, in
    either form of running the blocks."""
    cfg, params, (kv_k, kv_v), table = _prefill_setup(2)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (300,), 0, 500)
    tok, pos, flat = _chunk(table, tokens, 0, 300, 512)
    monkeypatch.delenv("DYN_PALLAS_INTERPRET", raising=False)
    loop, _, _ = llama.forward(params, cfg, tok, pos, kv_k, kv_v, table,
                               flat, allow_pallas=False)
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    seen = _calls(monkeypatch)
    kernel, _, _ = llama.forward(params, cfg, tok, pos, kv_k, kv_v, table,
                                 flat, allow_pallas=False)
    assert len(seen) == 1       # one trace of the scanned layer
    np.testing.assert_allclose(np.asarray(kernel[0, :300], np.float32),
                               np.asarray(loop[0, :300], np.float32),
                               rtol=5e-2, atol=5e-2)
