"""LongCat-Flash's language model (two latent attentions and two dense MLPs a
layer with ONE shortcut MoE, a softmax router with a selection bias over
real + identity experts, a chip's share of the real ones;
models/longcat_flash.py): the step programs, the window, the engine's
pools of 2 x num_layers entries, the prefix cache and the host tier
against the plain reference
(benchmark/configs/longcat-flash-omni/reference.py), on the CPU at a small
size: float32, 2 layers (4 sub-blocks), hidden 64, 4 heads of 16 + 16 /
16 over a latent of 32 behind a query LoRA of 48, dense MLPs of 128, 16
real + 8 identity experts top-4 of width 32, pages of 4, prefill chunks
of 8, seeded random weights at the cell's weight scales (the embedding's
and the selection bias's follow the size).

Tolerance. Both sides are float32 and compute the same sums in another
order (the program absorbed, in latent space, in chunks against pages;
the reference a head at a time over the whole sequence), so logits of
magnitude ~3 differ by a few 1e-5; ATOL = 3e-4 leaves room and is far
under what the shortcut added early, a LoRA scale left out, a bias that
does not select, renormalised weights or a dropped identity pair moves
(the tests that provoke them ask for 10 x ATOL)."""

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
from dynamo_tpu.models import llama, longcat_flash, mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import KVCacheSpec
from dynamo_tpu.models.registry import family_of
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "longcat-flash-omni")
ATOL = 3e-4
PS = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "longcat_flash_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)
with open(os.path.join(CONFIG_DIR, "config.json")) as _f:
    CELL = json.load(_f)


def tiny_hf(**over) -> dict:
    hf = dict(model_type="longcat_flash", vocab_size=512, hidden_size=64,
              ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
              num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
              qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
              mla_scale_q_lora=True, mla_scale_kv_lora=True,
              routed_scaling_factor=6, n_routed_experts=16,
              zero_expert_num=8, zero_expert_type="identity", moe_topk=4,
              rms_norm_eps=1e-5, rope_theta=10000000, attention_bias=False)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    return cfg


def make_params(cfg, seed=0):
    """The cell's weights at this size: the harness's rule and the
    configuration's scales; an embedding of unit RMS at this vocabulary
    and a selection bias of about a fifth of a mean score at this
    router's width (the cell's is sized for 768 outputs)."""
    scales = dict(ABOUT["weight_scales"], embed=math.sqrt(cfg.vocab_size),
                  router_bias=0.3 / cfg.router_width)
    return weights.build_tree(longcat_flash, cfg, weights.seed_key(seed),
                              scales)


def ref_logits(params, cfg, tokens, last=None, fault=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens, last,
                                               fault))


# ------------------------------------------------------ the configuration


def test_from_hf_config_on_the_cell_config():
    """The cell's config.json read by the family's reader: every width as
    published, the share and the router's width, the two LoRA scales."""
    cfg = ModelConfig.from_hf_config(CELL)
    fam = family_of(cfg)
    assert fam.name == "longcat_flash" and fam.module is longcat_flash
    assert fam.init_state is None and not fam.pool_by_kind
    assert fam.window_counts == ("moe_pairs_routed_total",
                                 "moe_pairs_held_total",
                                 "moe_pairs_identity_total")
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_heads) == (
        6144, 12288, 64)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64,
                                                      128)
    assert (cfg.num_layers, cfg.num_experts, cfg.vocab_size) == (
        4, 16, 16384)
    assert (cfg.router_width, cfg.identity_from, cfg.zero_experts,
            cfg.first_expert, cfg.num_experts_per_tok) == (768, 512, 256,
                                                           0, 12)
    assert cfg.mla_q_scale == 2.0
    assert abs(cfg.mla_kv_scale - math.sqrt(12)) < 1e-12
    assert cfg.routed_scaling_factor == 6 and not cfg.norm_topk_prob
    assert cfg.moe_intermediate_size == 2048 and cfg.rope_theta == 1e7
    assert llama.held_first(cfg) == 0
    # two pool entries a layer, mla.py's page geometry
    lat, rope = jax.eval_shape(
        lambda: longcat_flash.init_kv_cache(cfg, KVCacheSpec(8, 128)))
    assert lat.shape == (8, 8, 1, 128, 512)
    assert rope.shape == (8, 8, 1, 128, mla.rope_width(cfg))
    # every other latent model keeps scales of 1 and no identity outputs
    other = ModelConfig(kv_lora_rank=512)
    assert (other.mla_q_scale, other.mla_kv_scale, other.zero_experts,
            other.identity_from) == (1.0, 1.0, 0, 0)


@pytest.mark.parametrize("change,names", [
    (dict(zero_expert_type="copy"), ("zero_expert_type", "identity")),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 10}),
     ("rope_scaling",)),
    (dict(q_lora_rank=None), ("q_lora_rank",)),
    (dict(attention_bias=True), ("attention_bias",)),
    (dict(n_routed_experts=4, router_num_experts=16, first_local_expert=13),
     ("first_local_expert",)),
], ids=["zero_type", "rope_scaling", "no_q_lora", "bias", "share"])
def test_a_configuration_it_cannot_run_is_refused_by_name(change, names):
    with pytest.raises(NotImplementedError) as e:
        tiny(**change)
    assert all(n in str(e.value) for n in names)


# ------------------------------------------------- the programs by hand


_STEP_FNS = {}


def _step_fns(cfg):
    """The step programs of a configuration, built (and compiled) once."""
    if repr(cfg) not in _STEP_FNS:
        _STEP_FNS[repr(cfg)] = longcat_flash.make_step_fns(cfg)
    return _STEP_FNS[repr(cfg)]


class Pools:
    """One sequence's pages in small pools, driven the way the engine
    drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2, 13, 6, 1, 8)):
        self.cfg = cfg
        self.kv_k, self.kv_v = longcat_flash.init_kv_cache(
            cfg, KVCacheSpec(16, PS))
        self.pages = list(pages)
        self.prefill, self.decode = _step_fns(cfg)
        self.table = jnp.asarray([self.pages], jnp.int32)

    def slots(self, positions):
        return jnp.asarray([[self.pages[p // PS] * PS + p % PS
                             for p in positions]], jnp.int32)

    def chunk(self, params, tokens, start):
        pos = list(range(start, start + len(tokens)))
        logits, self.kv_k, self.kv_v = self.prefill(
            params, jnp.asarray([tokens], jnp.int32),
            jnp.asarray([pos], jnp.int32), self.kv_k, self.kv_v,
            self.table, self.slots(pos),
            jnp.asarray([len(tokens) - 1], jnp.int32))
        return np.asarray(logits[0])

    def step(self, params, token, pos):
        logits, self.kv_k, self.kv_v = self.decode(
            params, jnp.asarray([token], jnp.int32),
            jnp.asarray([pos], jnp.int32), self.kv_k, self.kv_v,
            self.table, self.slots([pos])[0])
        return np.asarray(logits[0])


def test_chunks_steps_and_the_window_match_the_reference():
    """A 24-token prompt in three chunks of 8 (two chunk boundaries: the
    pool's part merged with the chunk's own), then three
    single decode steps, then the fused window over three more: the
    logits at every compared position equal the reference's full forward,
    and the window's tokens are the greedy ones of those logits. The
    pools have 2 x num_layers entries and every one is written."""
    cfg = tiny()
    params = make_params(cfg, 1)
    rng = np.random.default_rng(1)
    toks = rng.integers(1, 512, 24).tolist()
    pools = Pools(cfg)
    assert pools.kv_k.shape[0] == 4
    for start in (0, 8):
        pools.chunk(params, toks[start:start + 8], start)
    got = [pools.chunk(params, toks[16:], 16)]
    for _ in range(3):
        toks.append(int(np.argmax(got[-1])))
        got.append(pools.step(params, toks[-1], len(toks) - 1))
    assert all(float(jnp.abs(pools.kv_k[j, pools.pages[0]]).max()) > 0
               for j in range(4))

    window = longcat_flash.make_decode_window_fn(cfg)
    B = 2                                   # a live row and a padding row
    nxt = int(np.argmax(got[-1]))
    out = window(
        params, jnp.asarray([nxt, 0], jnp.int32),
        jnp.asarray([len(toks), -1], jnp.int32), jnp.zeros(B, bool),
        jnp.zeros(B, jnp.int32), jnp.full(B, 100, jnp.int32), pools.kv_k,
        pools.kv_v, jnp.asarray([pools.pages, [0] * 10], jnp.int32),
        jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.float32), jnp.zeros(B, jnp.uint32),
        jnp.full((B, 1), -1, jnp.int32), k_steps=3, logprobs_topn=5)
    from dynamo_tpu.models.window import unpack

    res = unpack(out, 5, counts=True, state=False)
    toks.append(nxt)
    seq = toks + np.asarray(res.toks[0]).tolist()
    want = ref_logits(params, cfg, seq[:-1], last=7)
    for i, g in enumerate(got):             # the chunk's end + 3 steps
        assert np.abs(g - want[i]).max() < ATOL
    # the window's steps: greedy on the reference's logits, and its top-5
    # log-probabilities are the reference's
    logp = np.asarray(jax.nn.log_softmax(want[4:], -1))
    assert np.asarray(res.toks[0]).tolist() == np.argmax(
        want[4:], -1).tolist()
    vals, ids = np.asarray(res.aux[1][0]), np.asarray(res.aux[2][0])
    for i in range(3):
        assert np.abs(logp[i][ids[i]] - vals[i]).max() < ATOL
    # one live row x 3 steps x 2 layers x top-4; every real expert is held
    routed, held, identity = np.asarray(res.counts).tolist()
    assert routed == 3 * 2 * 4 and held + identity == routed
    assert 0 < identity < routed
    assert np.asarray(res.emitted).tolist() == [3, 0]


@pytest.fixture(scope="module")
def two_chunks():
    """(cfg, params, tokens, the program's logits at the end of a
    16-token prompt prefilled in two chunks), checked sound."""
    cfg = tiny()
    params = make_params(cfg, 2)
    toks = np.random.default_rng(2).integers(1, 512, 16).tolist()
    pools = Pools(cfg)
    pools.chunk(params, toks[:8], 0)
    got = pools.chunk(params, toks[8:], 8)
    assert np.abs(got - ref_logits(params, cfg, toks, last=1)[0]).max() < ATOL
    return cfg, params, toks, got


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_the_comparison_sees_each_departure(two_chunks, fault):
    """The controls: the reference with ONE thing wrong (the shortcut
    added after sub-block 0, both LoRA scales at 1, the bias left out of
    selection, the chosen weights renormalised, identity pairs dropped)
    is NOT what the program computes, by far more than the tolerance."""
    cfg, params, toks, got = two_chunks
    wrong = ref_logits(params, cfg, toks, last=1, fault=fault)[0]
    assert np.abs(got - wrong).max() > 10 * ATOL


# ------------------------------------------------------------- the experts


def _naive_experts(x, w, idx, params, l, first, held, real):
    """sum_k w_k E_idx_k(x), a pair at a time: a real expert held here,
    a real expert held elsewhere (nothing), an identity expert (x)."""
    out = np.zeros_like(x)
    for n in np.ndindex(*idx.shape):
        e, t = int(idx[n]), n[:-1]
        if e >= real:
            out[t] += w[n] * x[t]
        elif first <= e < first + held:
            g, u, d = (np.asarray(params[k][l, e - first], np.float64)
                       for k in longcat_flash.EXPERT_KEYS)
            a = x[t] @ g
            out[t] += w[n] * ((a / (1 + np.exp(-a)) * (x[t] @ u)) @ d)
    return out


@pytest.mark.parametrize("tokens", [24, 288], ids=["dense", "sorted"])
def test_identity_pairs_in_both_forms(tokens):
    """``llama.moe_experts`` with ``identity_from``, in the dense form and
    in the sorted dispatch: a token whose picks are ALL identity experts
    gets x times the sum of its weights and no expert's output, a token
    whose picks are all real gets no identity part, mixed tokens both;
    with a share (experts 4-7 of 16 real) a real pair held elsewhere adds
    nothing and an identity pair still adds the token; padding rows count
    for nothing."""
    cfg = tiny()
    params = make_params(cfg, 3)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, tokens // 2, 64)).astype(np.float32)
    k, real = 4, 16
    idx = np.stack([rng.permutation(24)[:k] for _ in range(tokens)]
                   ).reshape(2, -1, k).astype(np.int32)
    idx[0, 0] = [16, 19, 23, 20]            # all identity
    idx[0, 1] = [3, 4, 7, 12]               # all real
    w = rng.uniform(0.1, 1.0, idx.shape).astype(np.float32)
    live = np.ones(idx.shape[:2], bool)
    live[1, -5:] = False
    blocked = llama._moe_use_blocked(None, tokens, cfg.router_width, k)
    assert blocked == (tokens > 256)
    l = 1
    for first, held in ((0, 16), (4, 4)):
        cut = {n: params[n][:, first:first + held]
               for n in longcat_flash.EXPERT_KEYS}
        stacks = [cut[n] if blocked else cut[n][l]
                  for n in longcat_flash.EXPERT_KEYS]
        got = llama.moe_experts(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(idx), *stacks,
            blocked, live=jnp.asarray(live) if blocked else None,
            layer=jnp.int32(l) if blocked else None, first=first,
            width=24, identity_from=real)
        want = _naive_experts(x.astype(np.float64), w, idx, cut, l, first,
                              held, real)
        diff = np.abs(np.asarray(got) - want)[live]
        assert diff.max() < ATOL
        assert np.abs(np.asarray(got)[0, 0]
                      - w[0, 0].sum() * x[0, 0]).max() < 1e-5
    # without identity_from the same call drops the identity pairs
    plain = llama.moe_experts(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(idx), *stacks, blocked,
        live=jnp.asarray(live) if blocked else None,
        layer=jnp.int32(l) if blocked else None, first=first, width=24)
    assert np.abs(np.asarray(plain)[0, 0]).max() == 0.0


def _share(params, first, held):
    cfg = tiny(n_routed_experts=held, router_num_experts=16,
               first_local_expert=first)
    cut = dict(params)
    for name in longcat_flash.EXPERT_KEYS:
        cut[name] = params[name][:, first:first + held]
    return cfg, cut


@pytest.mark.parametrize("tokens", [24, 288], ids=["dense", "sorted"])
def test_the_four_shares_add_up(tokens):
    """The guide's test of the cut (section 4): at 16 real + 8 identity
    experts, top-4, in four shares of four real experts (first 0, 4, 8,
    12) the four partial sums, with the identity part counted ONCE (every
    share computes it for its own rows: it goes with the token, not with
    an expert's owner), equal what the UNCUT reference gives for the
    whole MoE; in both execution forms; each share's program equals the
    reference given the same share and counts the pairs in its range, and
    the three counters add up."""
    uncut = tiny()
    params = make_params(uncut, 4)
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, tokens // 2, 64)), jnp.float32)
    valid = jnp.ones(h.shape[:2], bool).at[1, -5:].set(False)
    l, k = 1, 4
    in_place = llama._moe_use_blocked(None, tokens, uncut.router_width, k)
    assert in_place == (tokens > 256)

    def program(cfg, p):
        out, counted = longcat_flash._moe(p, cfg, h, jnp.int32(l), in_place,
                                          valid, valid)
        return jnp.where(valid[..., None], out, 0.0), np.asarray(counted)

    def reference(cfg, p, fault=None):
        with jax.default_matmul_precision("highest"):
            out = jnp.stack([REF._moe(cfg, fault, p, row, l) for row in h])
        return jnp.where(valid[..., None], out, 0.0)

    whole = reference(uncut, params)
    identity = whole - reference(uncut, params, "identity_dropped")
    assert float(jnp.abs(identity).max()) > 10 * ATOL
    parts, held = [], 0
    n_valid = int(valid.sum())
    for first in (0, 4, 8, 12):
        cfg, cut = _share(params, first, 4)
        assert (cfg.router_width, cfg.identity_from,
                llama.held_first(cfg)) == (24, 16, first)
        got, counted = program(cfg, cut)
        assert np.abs(np.asarray(got - reference(cfg, cut))).max() < ATOL
        assert counted[0] == k * n_valid
        parts.append(got)
        held += counted[1]
        seen_identity = counted[2]
    # every pair is a real one of exactly one share or an identity one
    assert held + seen_identity == k * n_valid and seen_identity > 0
    total = sum(parts) - 3 * identity
    assert np.abs(np.asarray(total - whole)).max() < ATOL
    assert np.abs(np.asarray(parts[0] - whole)).max() > 10 * ATOL
    got, counted = program(uncut, params)
    assert np.abs(np.asarray(got - whole)).max() < ATOL
    assert counted.tolist() == [k * n_valid, held, seen_identity]


def test_the_gate_is_a_third_arm_and_leaves_the_two_others_alone():
    """softmax over ALL outputs, selection by score + bias, the chosen
    scores times the factor and not renormalised; the bias selects and
    never weighs; deepseek_v2's arm ignores a bias as before."""
    cfg = tiny()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 6, 64)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(64, 24)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(24,)) * 0.05, jnp.float32)
    w, idx = llama.deepseek_gate(x, w_r, bias, cfg)
    sc = jax.nn.softmax(x @ w_r, axis=-1)
    want_idx = jnp.argsort(-(sc + bias), axis=-1)[..., :4]
    assert (np.sort(np.asarray(idx)) == np.sort(np.asarray(want_idx))).all()
    assert np.abs(np.asarray(w) - 6 * np.asarray(
        jnp.take_along_axis(sc, idx, -1))).max() < 1e-6
    assert (np.sort(np.asarray(idx))
            != np.sort(np.asarray(jnp.argsort(-sc, -1)[..., :4]))).any()
    v2 = ModelConfig(moe_router="deepseek_v2", num_experts=24,
                     num_experts_per_tok=4, kv_lora_rank=32)
    _, idx2 = llama.deepseek_gate(x, w_r, bias, v2)
    assert (np.sort(np.asarray(idx2))
            == np.sort(np.asarray(jnp.argsort(-sc, -1)[..., :4]))).all()


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


def test_generate_matches_the_reference_and_a_prefix_hit_is_exact(run_async):
    """Prompts of 7, 21 and 70 tokens (1, 3 and 9 prefill chunks of 8)
    and then three to six windows through JaxEngine and the page
    manager: the engine's top-5 log-probabilities agree with the
    reference's full forward at every position; the prefix cache is ON
    for this family (a page holds everything a prefix is): the long
    prompt again with another tail is a hit on its whole pages and still
    agrees; a short and a long row batched together give what each gives
    alone; the three pair counters add up. (Every request asks for
    top-5 log-probabilities so that one variant of each program
    compiles; the benchmark's runs hold the compile fence.)"""
    eng = _engine()
    assert eng.state is None and eng.pm.prefix_reuse
    assert eng.kv_k.shape[0] == eng.kv_v.shape[0] == 4
    p1, p3, p9 = _prompts(6, 7, 21, 70)
    tail = p9[:64] + _prompts(7, 5)[0]

    async def main():
        # (lengths chosen so that the reference compiles for two sizes)
        alone = [await _gen(eng, p, n, logprobs=5)
                 for p, n in ((p1, 24), (p3, 10), (p9, 14))]
        mid = eng.stats()
        hit = await _gen(eng, tail, 15, logprobs=5)
        after = eng.stats()
        both = await asyncio.gather(_gen(eng, p1, 24, logprobs=5),
                                    _gen(eng, p9, 14, logprobs=5))
        stats = eng.stats()
        await eng.stop()
        return alone, hit, both, mid, after, stats

    alone, hit, both, mid, after, stats = run_async(main())
    for p, (toks, tops) in zip((p1, p3, p9), alone):
        assert _agrees(eng, p, toks, tops) < ATOL
    assert _agrees(eng, tail, *hit) < ATOL
    assert (after["prefix_hit_tokens_total"]
            - mid["prefix_hit_tokens_total"]) == 64
    assert both[0][0] == alone[0][0] and both[1][0] == alone[2][0]
    # every decoded row-step chose 4 outputs in each of the 2 layers; all
    # 16 real experts are held, so a pair is held or an identity one
    routed, held, identity = (mid[k] for k in longcat_flash.WINDOW_COUNTS)
    assert routed == (23 + 9 + 13) * 2 * 4
    assert held + identity == routed and 0 < identity < routed
    assert stats["moe_pairs_routed_total"] == routed + (14 + 23 + 13) * 8


def test_the_host_tier_holds_two_entries_a_layer(run_async):
    """host_pages > 0: the host pools take their leading axis from the
    device pools (2 x num_layers entries here, not num_layers), a prompt
    churned out of a tiny device pool is restored from the host tier as a
    prefix hit, and the continuation is what it was."""
    eng = _engine(num_pages=16, host_pages=32, host_tier_int8=False,
                  watermark_pages=2, page_buckets=(12,))
    assert eng.host_k.shape == (4, 32) + eng.kv_k.shape[2:]
    assert eng.host_v.shape == (4, 32) + eng.kv_v.shape[2:]
    prompts = _prompts(8, 23, 24, 24, 24, 24)

    async def main():
        first = await _gen(eng, prompts[0], 8, logprobs=5)
        for p in prompts[1:]:
            await _gen(eng, p, 8, logprobs=5)
        before = eng.prefix_hit_tokens_total
        again = await _gen(eng, prompts[0], 8, logprobs=5)
        hits = eng.prefix_hit_tokens_total - before
        await eng.stop()
        return first, again, hits

    first, again, hits = run_async(main())
    assert hits > 0 and eng.restore_pages_total > 0
    assert first[0] == again[0]
    assert _agrees(eng, prompts[0], *again) < ATOL


def test_a_mesh_of_four_devices_gives_the_unsharded_logits(two_chunks):
    """The leaves carry mla.py's names, so parallel/mesh.py places them
    (heads over "model", the experts over "expert"); under a mesh every
    program takes the XLA attention arm and the dense-over-experts form,
    the identity part is elementwise on the token: the second prefill
    chunk of ``two_chunks`` on expert=2 x model=2 gives what one device
    gave."""
    from dynamo_tpu.parallel.mesh import (MeshSpec, shard_kv_cache,
                                          shard_params)

    cfg, params, toks, want = two_chunks
    mesh = MeshSpec(expert=2, model=2).build()
    pools = Pools(cfg)
    pools.prefill, _ = longcat_flash.make_step_fns(cfg, mesh=mesh)
    pools.kv_k, pools.kv_v = shard_kv_cache(pools.kv_k, pools.kv_v, cfg,
                                            mesh)
    sharded = shard_params(params, cfg, mesh)
    pools.chunk(sharded, toks[:8], 0)
    assert np.abs(pools.chunk(sharded, toks[8:], 8) - want).max() < ATOL


def test_what_mla_is_served_this_family_is_served():
    """The family declares no capability: no feature of REFUSALS names
    it; spec_decode falls back (no verify forward), as for mla."""
    fam = family_of(tiny())
    from dynamo_tpu.models.registry import REFUSALS

    assert all(fam.refusal(f) is None for f in REFUSALS)
    assert fam.make_verify_fn is None
