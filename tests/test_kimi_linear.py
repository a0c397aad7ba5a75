"""Kimi Linear (KDA + latent attention without positions + a dense first
layer, then sigmoid-routed experts of which a share is held, beside a
shared expert; models/kimi_linear.py): the step programs, the forms
of the delta rule, the step kernel and the chunk kernel, the chip's
share of a layer's experts and the engine's state pool against the plain
reference
(benchmark/configs/kimi-linear-48b-a3b/reference.py), on the CPU at a
small size with every kind of layer: float32, two whole periods of 8
layers (attention at layers 4 and 8 of 1..8, layer 1 dense), hidden 64,
4 KDA heads of 16, conv 4, latent 32 + 8 shared columns under 4 heads of
16 + 8 / 16, 16 experts top-4 of width 32 with one shared, pages of 4,
prefill chunks of 8, scan chunks of 4, seeded random weights at the
cell's weight scales (benchmark/harness/weights.py with about.json's
``weight_scales``; the embedding's follows the vocabulary).

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in chunks of matrix products with a carried state, in
latent space and in rows; the reference token by token from zero and a
head at a time), so logits of magnitude ~3 differ by a few 1e-5; ATOL =
2e-4 leaves room and is far under what a dropped state, a state pool
rounded to bf16, a decay a head where it is a channel, a rotated shared
key or an expert of the wrong share moves (1e-3 and more: see the tests
that provoke them)."""

import asyncio
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import jamba, kimi_linear, llama, mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import get_model_module
from dynamo_tpu.ops import kda
from dynamo_tpu.ops.kda import kda_chunk, kda_step
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "kimi-linear-48b-a3b")
ATOL = 2e-4
PS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)


def tiny_hf(**over) -> dict:
    hf = dict(model_type="kimi_linear", vocab_size=512, hidden_size=64,
              intermediate_size=128, num_hidden_layers=8,
              num_attention_heads=4, num_key_value_heads=4, head_dim=16,
              linear_attn_config=dict(
                  kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11],
                  full_attn_layers=[4, 8, 12], num_heads=4, head_dim=16,
                  short_conv_kernel_size=4),
              kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
              first_k_dense_replace=1, num_experts=16,
              num_experts_per_token=4, moe_intermediate_size=32,
              num_shared_experts=1, moe_router_activation_func="sigmoid",
              moe_renormalize=True, routed_scaling_factor=2.446,
              num_expert_group=1, topk_group=1, rms_norm_eps=1e-5,
              tie_word_embeddings=False)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    cfg.kda_chunk_size = 4
    return cfg


def make_params(cfg, seed=0):
    """The cell's weights at this size: the harness's rule and the
    configuration's scales (an embedding of unit RMS at this
    vocabulary)."""
    scales = dict(ABOUT["weight_scales"], embed=math.sqrt(cfg.vocab_size))
    return weights.build_tree(kimi_linear, cfg, weights.seed_key(seed),
                              scales)


def ref_logits(params, cfg, tokens, last=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens, last))


_STEP_FNS = {}


def _step_fns(cfg):
    """The step programs of a configuration, built (and compiled) once
    for every Pools that runs the module as it stands; a test that
    patches the module gets programs of its own."""
    key = (repr(cfg), id(kimi_linear.BLOCKS), id(kimi_linear._kda_chunk),
           id(mla._latent_qkv))
    if key not in _STEP_FNS:
        _STEP_FNS[key] = kimi_linear.make_step_fns(cfg)
    return _STEP_FNS[key]


class Pools:
    """One sequence's pages and state slot in small pools, driven the way
    the engine drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2, 13, 6, 1, 8), slot=2,
                 slots=5):
        self.cfg = cfg
        self.kv_k, self.kv_v = kimi_linear.init_kv_cache(
            cfg, KVCacheSpec(16, PS))
        ssm, conv = kimi_linear.init_state(cfg, slots)
        # what a previous owner left in the slot must not matter
        self.state = (ssm.at[slot].set(7.0), conv.at[:, slot].set(3.0))
        self.pages, self.slot, self.drop = list(pages), slot, slots - 1
        self.prefill, self.decode = _step_fns(cfg)

    def table(self, rows, width=10):
        t = np.zeros((rows, width), np.int32)
        t[0, :len(self.pages)] = self.pages
        return jnp.asarray(t)

    def flat(self, at):
        return np.asarray(self.pages)[at // PS] * PS + at % PS

    def run_prefill(self, params, tokens, start, bucket):
        """One chunk of row 0 (row 1 is padding) in a [2, bucket]
        program; logits at the chunk's last token."""
        n = len(tokens)
        tok = np.zeros((2, bucket), np.int32)
        pos = np.full((2, bucket), -1, np.int32)
        slots = np.full((2, bucket), DROP_SLOT, np.int32)
        at = np.arange(start, start + n)
        tok[0, :n], pos[0, :n], slots[0, :n] = tokens, at, self.flat(at)
        logits, self.kv_k, self.kv_v, self.state = self.prefill(
            params, jnp.asarray(tok), jnp.asarray(pos), self.kv_k,
            self.kv_v, self.table(2), jnp.asarray(slots),
            jnp.asarray([n - 1, 0]), None, self.state,
            jnp.asarray([self.slot, self.drop], jnp.int32))
        return np.asarray(logits[0])

    def run_decode(self, params, token, pos):
        """decode_step on row 0 at ``pos`` (row 1 is padding)."""
        logits, self.kv_k, self.kv_v, self.state = self.decode(
            params, jnp.asarray([token, 0], jnp.int32),
            jnp.asarray([pos, -1], jnp.int32), self.kv_k, self.kv_v,
            self.table(2),
            jnp.asarray([int(self.flat(np.asarray(pos))), DROP_SLOT],
                        jnp.int32),
            self.state, jnp.asarray([self.slot, self.drop], jnp.int32))
        return np.asarray(logits[0])


# ------------------------------------------------------ the configuration


def test_from_hf_config_on_the_cell_config():
    """The cell's config.json: the layer kinds from the two 1-based
    lists cut at num_hidden_layers, the KDA and MLA sizes, the gate, the
    held share; and the module the registry sends it to, before the
    ``is_mla`` arm."""
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    assert cfg.model_type == "kimi_linear" and cfg.num_layers == 8
    assert cfg.layer_types == ("kda", "kda", "kda", "attention") * 2
    assert cfg.attn_layer_ids == (3, 7)
    assert (cfg.kda_n_heads, cfg.kda_head_dim, cfg.mamba_d_conv) == (32, 128,
                                                                     4)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 0, 128, 64, 128)
    assert cfg.mla_nope and cfg.is_mla and cfg.has_recurrent_state
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok) == (64, 256, 0, 8)
    assert (cfg.moe_router, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.n_shared_experts, cfg.first_k_dense_replace,
            cfg.moe_intermediate_size, cfg.intermediate_size) == (
        "deepseek_v3", True, 2.446, 1, 1, 1024, 9216)
    assert get_model_module(cfg) is kimi_linear
    assert jamba.segments(cfg) == [
        ("mamba", 0, 0, 1), ("mamba", 1, 1, 2), ("attn", 0, 3),
        ("mamba", 3, 4, 3), ("attn", 1, 7)]
    state = jax.eval_shape(lambda: kimi_linear.init_state(cfg, 129))
    assert state[0].shape == (129, 6, 128, 4096)        # 2 MiB a layer a row
    assert state[1].shape == (6, 129, 3 * 12288)         # layer-major
    kv = jax.eval_shape(lambda: kimi_linear.init_kv_cache(
        cfg, KVCacheSpec(8, 128)))
    assert kv[0].shape == (2, 8, 1, 128, 512)           # attending layers only


@pytest.mark.parametrize("change,names", [
    (dict(mla_use_nope=False), "mla_use_nope false"),
    (dict(num_expert_group=4), "num_expert_group 4"),
    (dict(topk_group=2), "topk_group 2"),
    (dict(q_lora_rank=16), "q_lora_rank 16"),
    (dict(first_local_expert=14, router_num_experts=16, num_experts=4),
     "first_local_expert 14"),
    (dict(linear_attn_config=dict(
        kda_layers=[1, 2, 3, 5, 6], full_attn_layers=[4, 8], num_heads=4,
        head_dim=16, short_conv_kernel_size=4)), "partition"),
    (dict(linear_attn_config=dict(
        kda_layers=[1, 2, 3, 4, 5, 6, 7], full_attn_layers=[4, 8],
        num_heads=4, head_dim=16, short_conv_kernel_size=4)), "partition"),
], ids=["rope", "groups", "topk-group", "q-lora", "share", "missing-layer",
        "layer-twice"])
def test_a_configuration_it_cannot_run_is_refused_by_name(change, names):
    with pytest.raises(NotImplementedError, match=names):
        ModelConfig.from_hf_config(tiny_hf(**change))


# ------------------------------------- the step programs and the reference


def test_prefill_in_chunks_and_decode_match_the_reference():
    """A 21-token prompt in chunks of 8, 8 and a ragged 5 (state and
    latents carried from chunk to chunk), then four decode steps: the
    logits agree with the reference's full forward at every chunk's last
    token and every step; what the slot held before does not matter."""
    cfg = tiny()
    params = make_params(cfg, 1)
    toks = np.random.default_rng(1).integers(1, 512, 25)
    want = ref_logits(params, cfg, toks)
    pools = Pools(cfg)
    for start, n in ((0, 8), (8, 8), (16, 5)):
        got = pools.run_prefill(params, toks[start:start + n], start, 8)
        assert np.abs(got - want[start + n - 1]).max() < ATOL
    for p in range(21, 25):
        got = pools.run_decode(params, int(toks[p]), p)
        assert np.abs(got - want[p]).max() < ATOL


def _controls(cfg, params, prompt, change_state=None, module=None):
    """Prefill 32 tokens, decode the 33rd; the worst gap of its
    log-probabilities to the reference's."""
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, prompt, last=1), -1))[-1]
    pools = Pools(cfg)
    for start in range(0, 32, 8):
        pools.run_prefill(params, prompt[start:start + 8], start, 8)
    if change_state is not None:
        pools.state = (change_state(pools.state[0]), pools.state[1])
    got = pools.run_decode(params, int(prompt[32]), 32)
    return np.abs(np.asarray(jax.nn.log_softmax(got)) - want).max()


def test_the_check_can_see_the_state_its_precision_and_the_three_controls(
        monkeypatch):
    """With the cell's weight scales the carried state matters to the
    logits, the comparison is tight enough to tell the state's type, and
    the three departures the chip tool provokes
    (tools/kimi_linear_long_context_check.py) fail here too: a decay a
    head (the mean over the head's channels) where it is a channel, a
    state rounded to an 8-bit float, and the rotary embedding applied to
    the shared key columns."""
    cfg = tiny()
    params = make_params(cfg, 2)
    prompt = np.random.default_rng(2).integers(1, 512, 33)
    assert _controls(cfg, params, prompt) < ATOL
    assert _controls(cfg, params, prompt, jnp.zeros_like) > 100 * ATOL
    assert _controls(cfg, params, prompt, lambda s: s.astype(
        jnp.bfloat16).astype(s.dtype)) > 5 * ATOL
    assert _controls(cfg, params, prompt, lambda s: s.astype(
        jnp.float8_e5m2).astype(s.dtype)) > 50 * ATOL
    # the decay a head: g replaced by its mean over the head's channels
    step, chunk = kimi_linear._kda_step, kimi_linear._kda_chunk

    def a_head(g):
        return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)

    with monkeypatch.context() as m:
        m.setattr(kimi_linear, "_kda_chunk",
                  lambda s, q, k, v, g, b, c: chunk(s, q, k, v, a_head(g), b,
                                                    c))
        m.setattr(kimi_linear, "BLOCKS", kimi_linear.BLOCKS._replace(
            mixer=lambda cfg, mp, u, valid, s, tail, step_=None:
            kimi_linear._kda(cfg, mp, u, valid, s, tail,
                             lambda s, q, k, v, g, b: step(
                                 s, q, k, v, a_head(g), b))))
        assert _controls(cfg, params, prompt) > 50 * ATOL
    # the rotation NoPE leaves out
    roped = ModelConfig.from_hf_config(tiny_hf())
    roped.dtype, roped.kda_chunk_size, roped.mla_nope = "float32", 4, False
    inv = llama.rope_freqs(roped, dim=roped.qk_rope_head_dim)
    qkv = mla._latent_qkv

    def rotated(cfg, lp, x, safe_pos, inv_freq, dtype):
        return qkv(roped, lp, x, safe_pos, inv, dtype)

    with monkeypatch.context() as m:
        m.setattr(mla, "_latent_qkv", rotated)
        want = np.asarray(jax.nn.log_softmax(
            ref_logits(params, cfg, prompt[:8], last=1), -1))[-1]
        got = Pools(cfg).run_prefill(params, prompt[:8], 0, 8)
        assert np.abs(np.asarray(jax.nn.log_softmax(got)) - want).max() \
            > 50 * ATOL


# --------------------------------------------------- the two scan forms


def _scan_operands(rng, B, T, H, dk, strength=1.0):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k = kimi_linear._l2norm(f(B, T, H, dk)), kimi_linear._l2norm(
        f(B, T, H, dk))
    g = -strength * jax.nn.softplus(f(B, T, H, dk))
    return q, k, f(B, T, H, dk), g, jax.nn.sigmoid(f(B, T, H))


def _token_by_token(s, q, k, v, g, beta):
    outs = []
    for t in range(q.shape[1]):
        s, o = kimi_linear._kda_step(s, q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t])
        outs.append(o)
    return s, jnp.stack(outs, 1)


@pytest.mark.parametrize("strength", [1.0, 25.0], ids=["mild", "strong"])
@pytest.mark.parametrize("T,Q", [(12, 1), (12, 4), (13, 4), (12, 12)],
                         ids=["Q1", "Q4", "ragged", "one-chunk"])
def test_the_chunked_form_is_the_token_recurrence(T, Q, strength):
    """_kda_chunk against the recurrence token by token from a carried
    state: at Q = 1 (where it IS the recurrence), in chunks of 4, at a
    length the chunk does not divide (13: the scan falls to the chunk
    that does) and in one chunk; and under a strong decay (g near -20 a
    token: exp(-G) of a factored form would overflow at the third
    token), where every exponent being a difference <= 0 keeps every
    value finite. A row's trailing tokens that do not count (g = 0, beta
    = 0) leave its state where its last valid token left it."""
    rng = np.random.default_rng(T * Q)
    B, H, dk = 2, 3, 8
    q, k, v, g, beta = _scan_operands(rng, B, T, H, dk, strength)
    if strength > 1:
        assert float(g.mean()) < -15
    valid = jnp.arange(T)[None, :] < jnp.asarray([T, T - 3])[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    s0 = jnp.asarray(rng.normal(size=(B, dk, H * dk)), jnp.float32)
    want_s, want_o = _token_by_token(s0, q, k, v, g, beta)
    got_s, got_o = kimi_linear._kda_chunk(s0, q, k, v, g, beta, Q)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    assert np.abs(np.asarray(got_o - want_o)).max() < 1e-5
    assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5
    short, _ = _token_by_token(s0[1:], q[1:, :T - 3], k[1:, :T - 3],
                               v[1:, :T - 3], g[1:, :T - 3],
                               beta[1:, :T - 3])
    assert np.abs(np.asarray(got_s[1:] - short)).max() < 1e-5


@pytest.mark.parametrize("B,T,H,carried,strength", [
    (2, 256, 1, True, 1.0),
    (1, 64, 1, False, 25.0),
    (1, 128, 4, True, 1.0),
], ids=["two-rows-carried-two-tiles", "strong-from-zeros-one-chunk",
        "four-heads"])
def test_chunk_kernel_is_the_token_recurrence(B, T, H, carried, strength):
    """ops/kda.py kda_chunk under interpretation (heads of 128 channels,
    as the kernel's blocks lie on the chip) against the recurrence token
    by token and against _kda_chunk on the same operands: from a carried
    state and from zeros; one row and two, the second's trailing 37
    tokens not counting (its state is that of its last counted token);
    T of one kernel chunk (64: padded to the kernel's tile of 128 with
    tokens that do not count), of two (one tile) and of four (two tiles:
    the state stays in VMEM between them); one head and four (a grid
    step a head: each reads and writes its own lane block of the state
    and of the tokens' arrays); under the strong decay (g near -20 a
    token: every exponent the kernel takes is a sum of log decays <= 0,
    so all is finite) at the same tolerance."""
    assert T % kda.CHUNK == 0 and (T > kda.TILE) == (T == 256)
    rng = np.random.default_rng(T + H)
    dk = 128
    q, k, v, g, beta = _scan_operands(rng, B, T, H, dk, strength)
    if strength > 1:
        assert float(g.mean()) < -15
    valid = jnp.arange(T)[None, :] < jnp.asarray([T, T - 37][:B])[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    s0 = jnp.asarray(rng.normal(size=(B, dk, H * dk)), jnp.float32) \
        * float(carried)
    got_s, got_o = kda_chunk(s0, q, k, v, g, beta, interpret=True)
    assert got_o.shape == v.shape and got_s.shape == s0.shape
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    counted = np.asarray(valid)[..., None, None]
    for want_s, want_o in (
            _token_by_token(s0, q, k, v, g, beta),
            kimi_linear._kda_chunk(s0, q, k, v, g, beta, 16)):
        assert np.abs(np.asarray(got_o - want_o) * counted).max() < 1e-5
        assert np.abs(np.asarray(got_s - want_s)).max() < 1e-5
    if B > 1:
        short, _ = _token_by_token(s0[1:], q[1:, :T - 37], k[1:, :T - 37],
                                   v[1:, :T - 37], g[1:, :T - 37],
                                   beta[1:, :T - 37])
        assert np.abs(np.asarray(got_s[1:] - short)).max() < 1e-5


def test_only_this_family_supplies_a_chunk_kernel():
    """jamba.Blocks.chunk: None for Jamba's and Granite's blocks (their
    prefill programs are what they were), ops/kda.py's kda_chunk here."""
    from dynamo_tpu.models import granite

    assert jamba.MAMBA1.chunk is None and granite.BLOCKS.chunk is None
    assert kimi_linear.BLOCKS.chunk is kda_chunk


def test_the_prefills_kernel_arm_is_its_xla_arm(monkeypatch):
    """prefill_step where the kernels run (interpreted here:
    jamba.forward asks llama.kernel_mode for T > 1 because the blocks
    carry a chunk kernel, and hands it to the mixer) against the XLA arm:
    the same logits, the same state and conv tails in the pool, over two
    chunks of one prompt (the second enters with a carried state)."""
    cfg = tiny(num_hidden_layers=4)     # a dense layer, two KDA, one attending
    params = make_params(cfg, 5)
    prompt = np.random.default_rng(5).integers(1, 512, 16)
    out = []
    for interpret in (False, True):
        if interpret:
            monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
        pools = Pools(cfg)
        if interpret:   # programs of its own: the mode is read at trace
            pools.prefill = kimi_linear.make_step_fns(cfg)[0]
        logits = [pools.run_prefill(params, prompt[at:at + 8], at, 8)
                  for at in (0, 8)]
        out.append((*logits, *(np.asarray(x[pools.slot])
                               for x in pools.state)))
    for x, y in zip(*out):
        assert np.abs(x - y).max() < ATOL
    assert np.abs(out[0][2]).max() > 1e-2


@pytest.mark.parametrize("slots,still", [
    ((3,), ()),                                         # one row
    ((5, 0, 3, 1, 6, 2, 4, 7), ()),                     # eight, permuted
    ((4, 1, 6), (1,)),                                  # a frozen row
    ((2, 12, 5, 12, 12, 0, 12, 9), (0,)),               # rows on the drop slot
], ids=["one", "eight-permuted", "three-one-frozen", "drop-slot-shared"])
def test_step_kernel_in_the_pool_matches_the_recurrence(slots, still):
    """ops/kda.py kda_step under interpretation against the XLA step on
    gathered rows: the rows' new state and o agree to float32 rounding;
    a row that does not advance (g = 0, beta = 0: frozen by a stop, or
    padding on the shared drop slot) keeps its state BIT FOR BIT; a call
    on layer m touches no other layer and no slot that no row holds; a
    row marked fresh starts from zeros whatever its slot held."""
    S, M, H, dk = 13, 3, 4, 16
    B = len(slots)
    rng = np.random.default_rng(B)
    pool = jnp.asarray(rng.normal(size=(S, M, dk, H * dk)), jnp.float32)
    at = jnp.asarray(slots, jnp.int32)
    q, k, v, g, beta = (x[:, 0] for x in _scan_operands(rng, B, 1, H, dk))
    frozen = [r for r in range(B) if r in still or slots[r] == S - 1]
    still_ = jnp.asarray([r in frozen for r in range(B)])
    g = jnp.where(still_[:, None, None], 0.0, g)
    beta = jnp.where(still_[:, None], 0.0, beta)
    live = [r for r in range(B) if r not in frozen]
    idx = np.asarray(slots)
    for m in (1, 2):                    # two layers of ONE pool, in turn
        before = np.asarray(pool)
        want_s, want_o = kimi_linear._kda_step(pool[at, m], q, k, v, g, beta)
        pool, o = kda_step(pool, at, jnp.int32(m), q, k, v, g, beta,
                           interpret=True)
        got = np.asarray(pool)
        assert np.abs(np.asarray(o - want_o))[live].max() < 1e-5
        assert np.abs(got[idx[live], m]
                      - np.asarray(want_s)[live]).max() < 1e-5
        assert np.abs(got[idx[live], m] - before[idx[live], m]).max() > 1e-3
        for r in frozen:
            assert (got[idx[r], m] == before[idx[r], m]).all()
        others = [j for j in range(M) if j != m]
        assert (got[:, others] == before[:, others]).all()
        unheld = sorted(set(range(S)) - set(slots))
        assert (got[unheld] == before[unheld]).all()
    fresh = jnp.arange(B) == 0
    want_s, want_o = kimi_linear._kda_step(
        jnp.where(fresh[:, None, None], 0.0, pool[at, 0]), q, k, v, g, beta)
    pool, o = kda_step(pool, at, jnp.int32(0), q, k, v, g, beta, fresh,
                       interpret=True)
    assert np.abs(np.asarray(o[0] - want_o[0])).max() < 1e-5
    assert np.abs(np.asarray(pool[at[0], 0] - want_s[0])).max() < 1e-5


def test_the_windows_kernel_arm_is_its_xla_arm():
    """The fused window with the kernels interpreted (the state advanced
    in its pool by ops/kda.py, the latent decode kernel over the
    attending layers' pools) against the XLA arm on gathered rows: the
    same tokens, the same pools afterwards."""
    cfg = tiny()
    params = make_params(cfg, 4)
    prompt = np.random.default_rng(4).integers(1, 512, 9)
    out = []
    for interpret in (False, True):
        pools = Pools(cfg)
        pools.run_prefill(params, prompt[:8], 0, 8)
        window = kimi_linear.make_decode_window_fn(
            cfg, True, 8, pallas_interpret=interpret)
        B = 2
        res = window(
            params, jnp.asarray([int(prompt[8]), 0], jnp.int32),
            jnp.asarray([8, -1], jnp.int32), jnp.zeros(B, bool),
            jnp.zeros(B, jnp.int32), jnp.full(B, 100, jnp.int32),
            pools.kv_k, pools.kv_v, pools.table(2),
            jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
            jnp.ones(B, jnp.float32), jnp.zeros(B, jnp.uint32),
            jnp.full((B, 8), -1, jnp.int32), None, pools.state,
            jnp.asarray([pools.slot, pools.drop], jnp.int32), k_steps=4,
            logprobs_topn=0)
        out.append((np.asarray(res[0][0]), *res[-4:-2], *res[-1]))
    assert (out[0][0] == out[1][0]).all()
    for x, y in zip(out[0][1:], out[1][1:]):
        assert np.abs(np.asarray(x) - np.asarray(y)).max() < 1e-5


# ------------------------------------------------------------- the share


def _share(params, first, held, k):
    cfg = tiny(num_experts=held, router_num_experts=16,
               first_local_expert=first, num_experts_per_token=k)
    cut = dict(params)
    for name in kimi_linear.EXPERT_KEYS:
        cut[name] = params[name][:, first:first + held]
    return cfg, cut


@pytest.mark.parametrize("tokens,k,is_sorted",
                         [(24, 4, False), (256, 2, True)],
                         ids=["dense", "sorted"])
def test_the_four_shares_add_up(tokens, k, is_sorted):
    """The guide's test of the cut (section 4): at 16 experts in four
    shares of four (first 0, 4, 8, 12) the four partial sums, with the
    shared expert counted once, equal what the UNCUT reference gives for
    the whole layer; in both execution forms (24 tokens at top-4 run
    dense-over-experts, 256 at top-2 the sorted dispatch, which a share
    of 4 takes only where fewer than 4 are chosen: the first bucket past
    the chip's ridge, 288 before PR 66 moved the rule's edge there),
    with padding rows that count for nothing; each share's program equals the reference given
    the same share, and counts the pairs that lay in its range."""
    uncut = tiny(num_experts_per_token=k)
    params = make_params(uncut, 3)
    assert params["w_gate_e"].shape[:2] == (7, 16)
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, tokens // 2,
                                                          64)), jnp.float32)
    valid = jnp.ones(h.shape[:2], bool).at[1, -5:].set(False)
    l = 5

    def norm(x, w):
        return llama.rms_norm(x, w.astype(jnp.float32), uncut.rms_norm_eps)

    def program(cfg, p):            # routed held + shared, and the counts
        out, counted = kimi_linear._ff(p, cfg, norm, h, jnp.int32(l), valid,
                                       4)
        return jnp.where(valid[..., None], out - h, 0.0), np.asarray(counted)

    def reference(cfg, p):
        with jax.default_matmul_precision("highest"):
            out = jnp.stack([REF._second_half(cfg, p, row, l, False)
                             for row in h]) - h
        return jnp.where(valid[..., None], out, 0.0)

    assert llama._moe_use_blocked(None, tokens, 4, k) is is_sorted
    whole = reference(uncut, params)
    parts, held = [], 0
    for first in (0, 4, 8, 12):
        cfg, cut = _share(params, first, 4, k)
        assert kimi_linear.held_first(cfg) == first
        got, counted = program(cfg, cut)
        assert np.abs(np.asarray(got - reference(cfg, cut))).max() < ATOL
        assert counted[0] == k * int(valid.sum())
        parts.append(got)
        held += counted[1]
    assert held == k * int(valid.sum())     # every pair lies in one share
    x = norm(h, params["ln_mlp"][l])
    li = l - 1
    shared = (jax.nn.silu(x @ params["w_gate_s"][li])
              * (x @ params["w_up_s"][li])) @ params["w_down_s"][li]
    total = sum(parts) - 3 * jnp.where(valid[..., None], shared, 0.0)
    assert np.abs(np.asarray(total - whole)).max() < ATOL
    assert np.abs(np.asarray(parts[0] - whole)).max() > 10 * ATOL
    assert np.abs(np.asarray(program(uncut, params)[0] - whole)).max() < ATOL


# ------------------------------------------------------ through JaxEngine


def _engine(cfg=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=96, max_batch=4, prefill_chunk=8,
                batch_buckets=(4,), prefill_buckets=(8,),
                page_buckets=(24,), max_prefill_batch=2, decode_steps=4,
                warmup_logprobs=False)
    base.update(over)
    cfg = cfg or tiny()
    return JaxEngine(cfg, EngineConfig(**base), params=make_params(cfg),
                     seed=0)


def _req(prompt, n, logprobs=None):
    return PreprocessedRequest(
        token_ids=[int(t) for t in prompt], sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        output=OutputOptions(logprobs=logprobs))


async def _gen(engine, prompt, n, logprobs=None):
    toks, tops = [], []
    async for out in engine.generate(_req(prompt, n, logprobs), Context()):
        toks.extend(out.token_ids)
        tops.extend(out.top_logprobs or [])
        if out.finish_reason is not None:
            break
    return toks, tops


def _prompts(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 512, n).tolist() for n in lens]


def _agrees(eng, prompt, toks, tops):
    want = np.asarray(jax.nn.log_softmax(ref_logits(
        eng.params, eng.cfg, prompt + toks[:-1], last=len(toks)), -1))
    return max(abs(want[j][i] - v)
               for j, top in enumerate(tops) for i, v in top.items())


def test_generate_matches_the_reference_through_1_3_and_9_chunks(run_async):
    """Prompts of 7, 21 and 70 tokens (1, 3 and 9 prefill chunks of 8:
    the state carried from chunk to chunk in its slot, the latents in
    their pages) and then three or four windows, through JaxEngine, the
    page manager and the state pool: the engine's top-5
    log-probabilities agree with the reference's full forward at every
    position; a short and a long row batched together give what each
    gives alone; the pool's slots, the carried chunks and the held pairs
    are counted; and nothing compiles after warmup(), which goes through
    the same helpers as serving."""
    eng = _engine()
    eng.warmup()
    assert isinstance(eng.state, tuple) and eng.state[0].shape[1:] == (
        6, 16, 64)
    assert eng.kv_k.shape[0] == 2 and not eng.pm.prefix_reuse
    p1, p3, p9 = _prompts(2, 7, 21, 70)

    async def main():
        alone = [await _gen(eng, p, n, logprobs=5)
                 for p, n in ((p1, 13), (p3, 9), (p9, 14))]
        mid = eng.stats()
        both = await asyncio.gather(_gen(eng, p1, 13), _gen(eng, p9, 14))
        stats = eng.stats()
        await eng.stop()
        return alone, both, mid, stats

    alone, both, mid, stats = run_async(main())
    for p, (toks, tops) in zip((p1, p3, p9), alone):
        assert _agrees(eng, p, toks, tops) < ATOL
    assert both[0][0] == alone[0][0] and both[1][0] == alone[2][0]
    assert stats["state_slots_active"] == 0
    assert stats["state_slots_total"] == 4
    assert 0 < stats["state_slots_held_total"] \
        <= stats["state_slots_seen_total"]
    # 1 + 3 + 9 chunks, of which 0 + 2 + 8 start from stored state
    assert mid["prefill_row_chunks_total"] == 13
    assert mid["prefill_row_chunks_carried_total"] == 10
    # every decoded row-step chose 4 experts in each of 7 expert layers,
    # all of them held (the tiny preset holds all 16)
    routed, held = (mid["moe_pairs_routed_total"],
                    mid["moe_pairs_held_total"])
    assert routed == (12 + 8 + 13) * 7 * 4 and held == routed
    # the requests with logprobs compiled their own programs (the cell
    # warms none); the plain ones that followed compiled nothing
    assert stats["post_warmup_compiles_total"] \
        == mid["post_warmup_compiles_total"]


def test_a_preempted_row_resumes_from_zeros(run_async):
    """A pool too small for four rows' pages preempts some mid-decode: a
    preempted row's slot and pages are given back, and it is prefilled
    again from zeros when it resumes (prompt and what it had generated);
    every row answers what it answers alone."""
    eng = _engine(num_pages=16, page_buckets=(12,))
    prompts = _prompts(5, 9, 10, 11, 12)
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s) for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)

    eng._grow_or_preempt = spy

    async def main():
        alone = [(await _gen(eng, p, 20))[0] for p in prompts]
        together = await asyncio.gather(*(_gen(eng, p, 20)
                                          for p in prompts))
        stats = eng.stats()
        await eng.stop()
        return alone, [t for t, _ in together], stats

    alone, together, stats = run_async(main())
    assert preempted, "the pool was meant to be too small"
    assert together == alone
    assert stats["state_slots_active"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]


# --------------------------------------------------------------- refusals


def _refused(what):
    return pytest.raises(
        NotImplementedError,
        match=f"{what}.*recurrent state.*models/kimi_linear.py")


def test_the_host_tier_refuses_this_module():
    with _refused("host KV tier"):
        _engine(host_pages=8)


def test_spec_decode_refuses_this_module():
    with _refused("spec_decode"):
        _engine(spec_decode=True)


def test_a_mesh_of_several_devices_refuses_this_module():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with _refused("mesh"):
        JaxEngine(tiny(), EngineConfig(page_size=PS, num_pages=16),
                  mesh=mesh)


@pytest.mark.parametrize("what", ["disaggregated prefill worker",
                                  "disaggregated decode engine",
                                  "KV transfer server"])
def test_disagg_and_kv_transfer_refuse_this_modules_engine(what):
    from dynamo_tpu.llm.disagg.decode import DisaggDecodeEngine
    from dynamo_tpu.llm.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.llm.disagg.transfer import KvTransferServer

    eng = _engine()
    build = {"disaggregated prefill worker": lambda: PrefillWorker(None, eng),
             "disaggregated decode engine":
                 lambda: DisaggDecodeEngine(eng, None, None, None, "d0"),
             "KV transfer server": lambda: KvTransferServer(eng)}[what]
    with _refused(what):
        build()


def test_the_other_families_keep_their_halves():
    """jamba.py's and granite.py's programs run on GQA by default: the
    latent attending half is an argument, not a flag."""
    from dynamo_tpu.models import granite

    assert jamba.MAMBA1.attending is jamba.GQA
    assert granite.BLOCKS.attending is jamba.GQA
    assert kimi_linear.BLOCKS.attending is kimi_linear.LATENT
    assert kimi_linear.BLOCKS.step is kda_step
    assert kimi_linear.BLOCKS.counts == kimi_linear.WINDOW_COUNTS
