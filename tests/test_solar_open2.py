"""Solar Open 2 (KDA with beta in (0, 2) beside gated GQA without
positions, three to one, and in every layer sigmoid-routed experts of
which a share is held beside a shared expert; models/solar_open2.py on
jamba.Blocks): the step programs, the fused window, the chip's share of
a layer's experts and the engine's two kinds of cache against the plain
reference (benchmark/configs/solar-open2-250b/reference.py), on the CPU
at a small size: float32, one whole period of 4 layers (GQA, KDA, KDA,
KDA), hidden 64, 4 KDA heads of 16, conv 4, 4 query heads over 2 KV
heads of 16, 16 experts top-4 of width 32 with one shared, pages of 4,
prefill chunks of 8, scan chunks of 4, seeded random weights at the
cell's weight scales (benchmark/harness/weights.py with about.json's
``weight_scales``; the embedding's follows the vocabulary).

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in chunks of matrix products with a carried state and
over pages; the reference token by token from zero and over the whole
prefix), so logits of magnitude ~3 differ by a few 1e-5; ATOL = 2e-4
leaves room and is far under what beta held to (0, 1), a gate left out,
a state pool rounded to bf16 or an expert of the wrong share moves (1e-3
and more: see the tests that provoke them). The KDA mixer's own forms
(chunked, kernels) are tests/test_kimi_linear.py's: one mixer for both
families."""

import dataclasses
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
from dynamo_tpu.models import jamba, kimi_linear, llama, solar_open2
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import get_model_module
from dynamo_tpu.ops.kda import kda_chunk, kda_step
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b")
ATOL = 2e-4
PS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "solar_open2_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)


def tiny_hf(**over) -> dict:
    hf = dict(model_type="solar_open2", vocab_size=512, hidden_size=64,
              intermediate_size=128, num_hidden_layers=4,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              linear_attn_config=dict(num_heads=4, head_dim=16,
                                      short_conv_kernel_size=4,
                                      num_kv_heads=None),
              gqa_interval=3, gqa_layers=[0, 4, 8], use_rope=False,
              use_gqa_gate=True, kda_use_full_proj=False,
              kda_allow_neg_eigval=True, first_k_dense_replace=0,
              n_routed_experts=16, num_experts_per_tok=4,
              moe_intermediate_size=32, n_shared_experts=1,
              norm_topk_prob=True, routed_scaling_factor=1,
              rms_norm_eps=1e-5, tie_word_embeddings=False)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    cfg.kda_chunk_size = 4
    return cfg


_PARAMS = {}


def make_params(cfg, seed=0):
    """The cell's weights at this size: the harness's rule and the
    configuration's scales (an embedding of unit RMS at this
    vocabulary); drawn once a (configuration, seed) for the whole file."""
    key = repr(cfg), seed
    if key not in _PARAMS:
        scales = dict(ABOUT["weight_scales"],
                      embed=math.sqrt(cfg.vocab_size))
        _PARAMS[key] = weights.build_tree(
            solar_open2, cfg, weights.seed_key(seed), scales)
    return _PARAMS[key]


def ref_logits(params, cfg, tokens, last=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens, last))


_STEP_FNS = {}


def _step_fns(cfg):
    """The step programs of a configuration, built (and compiled) once."""
    if repr(cfg) not in _STEP_FNS:
        _STEP_FNS[repr(cfg)] = solar_open2.make_step_fns(cfg)
    return _STEP_FNS[repr(cfg)]


class Pools:
    """One sequence's pages and state slot in small pools, driven the way
    the engine drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2, 13, 6, 1, 8), slot=2,
                 slots=5):
        self.cfg = cfg
        self.kv_k, self.kv_v = solar_open2.init_kv_cache(
            cfg, KVCacheSpec(16, PS))
        ssm, conv = solar_open2.init_state(cfg, slots)
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

    def run_prefill(self, params, tokens, start, bucket=8):
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
    """The cell's config.json: ``gqa_layers`` counts from 0 and is cut at
    num_hidden_layers, so the four layers are GQA, KDA, KDA, KDA; the KDA
    and GQA sizes, beta's scale, the gate, the held share; the module the
    registry sends it to (KDA heads and no latent rank); the pools."""
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    assert cfg.model_type == "solar_open2" and cfg.num_layers == 4
    assert cfg.layer_types == ("attention", "kda", "kda", "kda")
    assert cfg.attn_layer_ids == (0,)
    assert (cfg.kda_n_heads, cfg.kda_head_dim, cfg.mamba_d_conv) == (64, 128,
                                                                     4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (64, 8, 128)
    assert cfg.kda_beta_scale == 2.0
    assert not cfg.is_mla and cfg.has_recurrent_state
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok) == (40, 320, 0, 8)
    assert (cfg.moe_router, cfg.norm_topk_prob, cfg.routed_scaling_factor,
            cfg.n_shared_experts, cfg.first_k_dense_replace,
            cfg.moe_intermediate_size, cfg.vocab_size) == (
        "deepseek_v3", True, 1.0, 1, 0, 1280, 24576)
    assert get_model_module(cfg) is solar_open2
    assert jamba.segments(cfg) == [("attn", 0, 0), ("mamba", 0, 1, 3)]
    shapes = jax.eval_shape(
        lambda: solar_open2.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["wg"].shape == (1, 4096, 8192)
    assert shapes["w_gate_e"].shape == (4, 40, 4096, 1280)
    assert shapes["w_router"].shape == (4, 4096, 320)
    assert not set(kimi_linear.DENSE_KEYS) & set(shapes)    # no dense leaf
    state = jax.eval_shape(lambda: solar_open2.init_state(cfg, 129))
    assert state[0].shape == (129, 3, 128, 8192)        # 4 MiB a layer a row
    assert state[1].shape == (3, 129, 3 * 24576)         # layer-major
    kv = jax.eval_shape(lambda: solar_open2.init_kv_cache(
        cfg, KVCacheSpec(8, 128)))
    assert kv[0].shape == (1, 8, 8, 128, 128)           # attending layers only
    # a deeper cut of the same file: two periods
    two = ModelConfig.from_hf_config(tiny_hf(num_hidden_layers=8))
    assert two.layer_types == ("attention", "kda", "kda", "kda") * 2


@pytest.mark.parametrize("change,names", [
    (dict(use_rope=True), "use_rope true"),
    (dict(use_gqa_gate=False), "use_gqa_gate false"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj true"),
    (dict(linear_attn_config=dict(num_heads=4, head_dim=16, num_kv_heads=2,
                                  short_conv_kernel_size=4)),
     "num_kv_heads 2"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace 1"),
    (dict(n_group=4), "n_group 4"),
    (dict(first_local_expert=14, router_num_experts=16, n_routed_experts=4),
     "first_local_expert 14"),
    (dict(gqa_layers=[4, 8]), "gqa_layers"),
], ids=["rope", "no-gate", "full-proj", "kv-heads", "dense", "groups", "share",
        "one-kind"])
def test_a_configuration_it_cannot_run_is_refused_by_name(change, names):
    with pytest.raises(NotImplementedError, match=names):
        ModelConfig.from_hf_config(tiny_hf(**change))


def test_one_mixer_for_both_families():
    """Nothing of the KDA mixer, its kernels or the held-experts second
    half is this module's own; the attending half is Jamba's GQA, which
    gates because the params hold ``wg``."""
    b = solar_open2.BLOCKS
    assert b.mixer is kimi_linear._kda and b.ff is kimi_linear._ff
    assert b.step is kda_step and b.chunk is kda_chunk
    assert b.attending is jamba.GQA and b.keys is kimi_linear.KDA_KEYS
    assert b.counts == solar_open2.WINDOW_COUNTS
    assert solar_open2.init_state is kimi_linear.init_state
    assert solar_open2.init_kv_cache is jamba.init_kv_cache
    assert kimi_linear.BLOCKS.attending is kimi_linear.LATENT
    key = jax.random.PRNGKey(0)
    assert "wg" in jax.eval_shape(
        lambda: solar_open2.init_params(tiny(), key))
    assert "wg" not in jax.eval_shape(
        lambda: jamba.init_params(ModelConfig.tiny(
            mamba_d_state=4, mamba_dt_rank=4, attn_layer_period=2,
            attn_layer_offset=1), key))


# ------------------------------------- the step programs and the reference


def test_prefill_in_chunks_and_decode_match_the_reference():
    """A 29-token prompt in chunks of 8, 8, 8 and a ragged 5 (the state
    carried over three chunk boundaries in its slot, K/V in their pages),
    then four decode steps: the logits agree with the reference's full
    forward at every chunk's last token and every step; what the slot
    held before does not matter."""
    cfg = tiny()
    params = make_params(cfg)
    toks = np.random.default_rng(1).integers(1, 512, 33)
    want = ref_logits(params, cfg, toks)
    pools = Pools(cfg)
    for start, n in ((0, 8), (8, 8), (16, 8), (24, 5)):
        got = pools.run_prefill(params, toks[start:start + n], start)
        assert np.abs(got - want[start + n - 1]).max() < ATOL
    for p in range(29, 33):
        got = pools.run_decode(params, int(toks[p]), p)
        assert np.abs(got - want[p]).max() < ATOL


def _gap(cfg, params, prompt, want, change_state=None):
    """Prefill 32 tokens, decode the 33rd; the worst gap of its
    log-probabilities to ``want``."""
    pools = Pools(cfg)
    for start in range(0, 32, 8):
        pools.run_prefill(params, prompt[start:start + 8], start)
    if change_state is not None:
        pools.state = (change_state(pools.state[0]), pools.state[1])
    got = pools.run_decode(params, int(prompt[32]), 32)
    return np.abs(np.asarray(jax.nn.log_softmax(got)) - want).max()


def test_the_check_can_see_beta_the_gate_and_the_states_precision():
    """With the cell's weight scales beta does reach past 1, and the
    comparison tells the program from what a cheaper one would compute:
    beta held to (0, 1) (``kda_allow_neg_eigval`` ignored), the
    attention gate left out, the state pool dropped or rounded to
    bfloat16."""
    cfg = tiny()
    params = make_params(cfg)
    prompt = np.random.default_rng(2).integers(1, 512, 33)
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, prompt, last=1), -1))[-1]
    assert _gap(cfg, params, prompt, want) < ATOL
    assert _gap(cfg, params, prompt, want, jnp.zeros_like) > 100 * ATOL
    assert _gap(cfg, params, prompt, want, lambda s: s.astype(
        jnp.bfloat16).astype(s.dtype)) > 5 * ATOL
    # beta as the mixer makes it: past 1 for some head of some token
    seen = []

    def spy(s, q, k, v, g, beta):
        seen.append(beta)
        return kimi_linear._kda_step(s, q, k, v, g, beta)

    mp = {n: params[n][0] for n in kimi_linear.KDA_KEYS}
    u = jnp.asarray(np.random.default_rng(3).normal(size=(8, 1, 64)),
                    jnp.float32)
    kimi_linear._kda(cfg, mp, u, jnp.ones((8, 1), bool),
                     jnp.zeros((8, 16, 64)), jnp.zeros((8, 3 * 192)), spy)
    assert 1.0 < float(seen[0].max()) < 2.0 and float(seen[0].min()) > 0.0
    # the same weights through a program that ignores the key
    clamped = dataclasses.replace(cfg, kda_beta_scale=1.0)
    assert _gap(clamped, params, prompt, want) > 50 * ATOL
    # the gate left out: the program of a family without the leaf
    ungated = {n: x for n, x in params.items() if n != "wg"}
    assert _gap(cfg, ungated, prompt, want) > 50 * ATOL


# ------------------------------------------------------------- the share


def _share(params, first, held, k):
    cfg = tiny(n_routed_experts=held, router_num_experts=16,
               first_local_expert=first, num_experts_per_tok=k)
    cut = dict(params)
    for name in kimi_linear.EXPERT_KEYS:
        cut[name] = params[name][:, first:first + held]
    return cfg, cut


def test_the_eight_shares_add_up():
    """The guide's test of the cut (section 4): at 16 experts in eight
    shares of two (first 0, 2, ..., 14: the deployment's eight chips a
    layer) the eight partial sums, with the shared expert counted once,
    equal what the UNCUT reference gives for the whole layer, with
    padding rows that count for nothing; each share's program equals the
    reference given the same share, and counts the pairs that lay in its
    range."""
    k, tokens, l = 4, 24, 2
    uncut = tiny()
    params = make_params(uncut)
    assert params["w_gate_e"].shape[:2] == (4, 16)
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, tokens // 2,
                                                          64)), jnp.float32)
    valid = jnp.ones(h.shape[:2], bool).at[1, -5:].set(False)

    def norm(x, w):
        return llama.rms_norm(x, w.astype(jnp.float32), uncut.rms_norm_eps)

    def program(cfg, p):            # routed held + shared, and the counts
        out, counted = kimi_linear._ff(p, cfg, norm, h, jnp.int32(l), valid,
                                       1)
        return jnp.where(valid[..., None], out - h, 0.0), np.asarray(counted)

    def reference(cfg, p):
        with jax.default_matmul_precision("highest"):
            out = jnp.stack([REF._second_half(cfg, p, row, l)
                             for row in h]) - h
        return jnp.where(valid[..., None], out, 0.0)

    whole = reference(uncut, params)
    parts, held = [], 0
    for first in range(0, 16, 2):
        cfg, cut = _share(params, first, 2, k)
        assert kimi_linear.held_first(cfg) == first
        got, counted = program(cfg, cut)
        assert np.abs(np.asarray(got - reference(cfg, cut))).max() < ATOL
        assert counted[0] == k * int(valid.sum())
        parts.append(got)
        held += counted[1]
    assert held == k * int(valid.sum())     # every pair lies in one share
    x = norm(h, params["ln_mlp"][l])
    shared = (jax.nn.silu(x @ params["w_gate_s"][l])
              * (x @ params["w_up_s"][l])) @ params["w_down_s"][l]
    total = sum(parts) - 7 * jnp.where(valid[..., None], shared, 0.0)
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


async def _gen(engine, prompt, n, logprobs=None):
    req = PreprocessedRequest(
        token_ids=[int(t) for t in prompt], sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        output=OutputOptions(logprobs=logprobs))
    toks, tops = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        tops.extend(out.top_logprobs or [])
        if out.finish_reason is not None:
            break
    return toks, tops


def test_generate_matches_the_reference_through_the_window(run_async):
    """Prompts of 7 and 37 tokens (1 and 5 prefill chunks of 8: the state
    carried from chunk to chunk in its slot, K/V in the one attending
    layer's pages) and then three or four decode windows, through
    JaxEngine, the page manager and the state pool: the engine's top-5
    log-probabilities agree with the reference's full forward at every
    position; the pool's slots, the carried chunks and the held pairs
    are counted; the prefix cache is off."""
    eng = _engine()
    assert isinstance(eng.state, tuple) and eng.state[0].shape[1:] == (
        3, 16, 64)
    assert eng.kv_k.shape[:1] == (1,) and eng.kv_k.shape[2:] == (2, PS, 16)
    assert not eng.pm.prefix_reuse
    rng = np.random.default_rng(2)
    p1, p5 = (rng.integers(1, 512, n).tolist() for n in (7, 37))

    async def main():
        out = [await _gen(eng, p, n, logprobs=5)
               for p, n in ((p1, 13), (p5, 10))]
        stats = eng.stats()
        await eng.stop()
        return out, stats

    out, stats = run_async(main())
    for p, (toks, tops) in zip((p1, p5), out):
        want = np.asarray(jax.nn.log_softmax(ref_logits(
            eng.params, eng.cfg, p + toks[:-1], last=len(toks)), -1))
        assert max(abs(want[j][i] - v) for j, top in enumerate(tops)
                   for i, v in top.items()) < ATOL
    assert stats["state_slots_active"] == 0
    assert stats["state_slots_total"] == 4
    assert 0 < stats["state_slots_held_total"] \
        <= stats["state_slots_seen_total"]
    # 1 + 5 chunks, of which 0 + 4 start from stored state
    assert stats["prefill_row_chunks_total"] == 6
    assert stats["prefill_row_chunks_carried_total"] == 4
    # every decoded row-step chose 4 experts in each of 4 expert layers,
    # all of them held (the tiny preset holds all 16)
    routed, held = (stats["moe_pairs_routed_total"],
                    stats["moe_pairs_held_total"])
    assert routed == (12 + 9) * 4 * 4 and held == routed


@pytest.mark.parametrize("what,over", [
    ("host KV tier", dict(host_pages=8)),
    ("spec_decode", dict(spec_decode=True))])
def test_what_cannot_move_a_state_refuses_this_module(what, over):
    with pytest.raises(
            NotImplementedError,
            match=f"{what}.*recurrent state.*models/solar_open2.py"):
        _engine(**over)
