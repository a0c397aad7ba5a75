"""LFM2-MoE (gated short convolutions + attention + routed experts,
models/lfm2.py): the step programs, the state pools by slot and by page,
and the engine's prefix hits on a model with state, against the plain
reference (benchmark/configs/lfm2-24b-a2b/reference.py), on the CPU at a
tiny size: float32, the cell's own eight layers (conv conv attn conv
conv conv attn conv: two dense layers, six routed), hidden 256 with 4 / 2
heads of 64 (so the K / V pools pack two heads into 128 lanes, as the
cell's do), 8 experts top-2, seeded random weights with a router bias
that changes the chosen set.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in chunks from a carried state, the reference as one
sequence from zero), so logits of magnitude ~3 differ by a few 1e-6
(measured 4e-6 at worst); ATOL = 1e-4 leaves room and is 100x under what
a lost state, a wrong snapshot or a missed token moves (1e-2 and more:
the tests that provoke them say so)."""

import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import lfm2, mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import family_of, get_model_module
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b")
ATOL = 1e-4
PS = 8
KINDS = ["conv", "conv", "full_attention", "conv"] * 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny(**over) -> ModelConfig:
    hf = dict(model_type="lfm2_moe", vocab_size=512, hidden_size=256,
              intermediate_size=96, num_hidden_layers=8,
              num_attention_heads=4, num_key_value_heads=2,
              layer_types=KINDS, conv_L_cache=3, conv_bias=False,
              num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
              moe_intermediate_size=32, norm_eps=1e-5, norm_topk_prob=True,
              use_expert_bias=True, routed_scaling_factor=1,
              rope_parameters={"rope_theta": 1000000,
                               "rope_type": "default"},
              tie_word_embeddings=False)
    hf.update(over)
    cfg = ModelConfig.from_hf_config(hf)
    cfg.dtype = "float32"
    return cfg


def make_params(cfg, seed=0):
    """init_params with a router bias that is not zero: at std 0.1 it
    changes the chosen pair for about a third of the tokens."""
    params = lfm2.init_params(cfg, jax.random.PRNGKey(seed))
    params["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 100), params["router_bias"].shape)
    return params


def ref_logits(params, cfg, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens))


class Pools:
    """One sequence's pages and state slot in small pools, driven the way
    the engine drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2), slot=2, slots=5):
        self.cfg = cfg
        spec = KVCacheSpec(16, PS)
        self.kv_k, self.kv_v = lfm2.init_kv_cache(cfg, spec)
        (by_slot,) = lfm2.init_state(cfg, slots)
        # what a previous owner left in the slot, and what an earlier
        # page of the same id left in its snapshot, must not matter
        self.state = (by_slot.at[slot].set(3.0),
                      lfm2.init_state_snapshots(cfg, spec) + 5.0)
        self.pages, self.slot, self.drop = list(pages), slot, slots - 1
        self.prefill, self.decode = lfm2.make_step_fns(cfg)

    def table(self, rows, width=8):
        t = np.zeros((rows, width), np.int32)
        t[0, :len(self.pages)] = self.pages
        return jnp.asarray(t)

    def run_prefill(self, params, tokens, start, bucket, src=-1):
        """One chunk of row 0 (row 1 is padding) in a [2, bucket]
        program; logits at the chunk's last token."""
        n = len(tokens)
        tok = np.zeros((2, bucket), np.int32)
        pos = np.full((2, bucket), -1, np.int32)
        slots = np.full((2, bucket), DROP_SLOT, np.int32)
        at = np.arange(start, start + n)
        tok[0, :n], pos[0, :n] = tokens, at
        slots[0, :n] = np.asarray(self.pages)[at // PS] * PS + at % PS
        logits, self.kv_k, self.kv_v, self.state = self.prefill(
            params, jnp.asarray(tok), jnp.asarray(pos), self.kv_k,
            self.kv_v, self.table(2), jnp.asarray(slots),
            jnp.asarray([n - 1, 0]), None, self.state,
            jnp.asarray([self.slot, self.drop], jnp.int32),
            jnp.asarray([src, -1], jnp.int32))
        return np.asarray(logits[0])

    def snapshot_rows(self):
        """Ids of the pages whose snapshot a program has written."""
        snap = np.asarray(self.state[1])
        return [p for p in range(len(snap)) if not np.all(snap[p] == 5.0)]


def test_from_hf_config_on_the_catalog_config():
    """The published config: 30 conv + 10 attending layers (2, 6, ...),
    heads of 64, two dense layers, 64 experts top-4 behind the shared
    sigmoid gate at 1e-6; a cut in depth keeps the list whole; what the
    module does not compute is refused."""
    with open(os.path.join(CONFIG_DIR, "about.json")) as f:
        published = json.load(f)["published"]
    cfg = ModelConfig.from_hf_config(published)
    assert cfg.num_layers == 40 and cfg.attn_layer_ids == tuple(
        range(2, 40, 4))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (32, 8, 64)
    assert (cfg.conv_l_cache, cfg.num_dense_layers) == (3, 2)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size) == (
                64, 4, 1536, 11776)
    assert (cfg.moe_router, cfg.norm_topk_prob, cfg.moe_renorm_eps,
            cfg.routed_scaling_factor, cfg.n_group) == (
                "deepseek_v3", True, 1e-6, 1, 0)
    assert cfg.rope_theta == 1000000 and cfg.rms_norm_eps == 1e-5
    assert cfg.qk_norm and cfg.tie_word_embeddings
    assert cfg.has_recurrent_state and get_model_module(cfg) is lfm2
    assert lfm2.kv_pack(cfg) == 2 and lfm2.num_conv_layers(cfg) == 30
    cut = ModelConfig.from_hf_config(dict(published, num_hidden_layers=8))
    assert cut.layer_types == tuple(KINDS)
    assert lfm2.segments(cut) == [
        ("conv", 0, 0, 2), ("attn", 0, 2), ("conv", 2, 3, 3),
        ("attn", 1, 6), ("conv", 5, 7, 1)]
    # a run that the end of the dense layers cuts in two
    assert lfm2.segments(tiny(num_dense_layers=1))[:2] == [
        ("conv", 0, 0, 1), ("conv", 1, 1, 1)]
    with pytest.raises(NotImplementedError, match="conv_bias"):
        ModelConfig.from_hf_config(dict(published, conv_bias=True))
    with pytest.raises(NotImplementedError, match="use_expert_bias"):
        ModelConfig.from_hf_config(dict(published, use_expert_bias=False))
    with pytest.raises(NotImplementedError, match="layer_types"):
        ModelConfig.from_hf_config(dict(
            published, layer_types=["conv", "sliding_attention"] * 20))


def test_the_shared_gate_equals_a_plain_one():
    """(f) LFM2's weights and indices from mla._deepseek_gate (the gate
    cell 5 runs) equal a ten-line plain version: sigmoid scores, the k
    largest of score + bias, the UNBIASED scores of the chosen over
    (their sum + 1e-6); with a bias that changes the chosen set."""
    cfg = tiny()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 64, 256)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(256, 8)) / 16, jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=8), jnp.float32)
    w, idx = mla._deepseek_gate(x, w_router, bias, cfg)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x[0]) @ np.asarray(w_router))))
    changed = 0
    for t in range(64):
        chosen = np.argsort(-(scores[t] + np.asarray(bias)),
                            kind="stable")[:2]
        changed += set(chosen) != set(np.argsort(-scores[t])[:2])
        assert list(np.asarray(idx[0, t])) == list(chosen)
        want = scores[t][chosen] / (scores[t][chosen].sum() + 1e-6)
        assert np.abs(np.asarray(w[0, t]) - want).max() < 1e-6
    assert 5 < changed < 60, "the bias was meant to change some sets"
    # DeepSeek-V3's own epsilon is a value of the configuration too
    assert ModelConfig.from_hf_config(dict(
        model_type="deepseek_v3", vocab_size=8, hidden_size=8,
        intermediate_size=8, num_hidden_layers=1, num_attention_heads=1,
        n_routed_experts=2)).moe_renorm_eps == 1e-20


@pytest.mark.parametrize("tied,n_prompt", [(False, 21), (True, 21),
                                           (False, PS - 2)])
def test_prefill_and_window_match_reference(tied, n_prompt):
    """(a) prefill_step then two decode_windows through the pools and the
    state against the reference's full forward, on logits (the window's
    top-8 log-probabilities at each of its steps). From a prompt of
    PS - 2 the first window's rows lie on both sides of a page boundary:
    it fills page 0 and leaves that page's snapshot."""
    cfg = tiny(tie_word_embeddings=tied)
    params = make_params(cfg)
    assert ("lm_head" in params) == (not tied)
    pools = Pools(cfg)
    prompt = np.random.default_rng(0).integers(1, 512, n_prompt)
    logits = pools.run_prefill(params, prompt, 0, 32)
    want = ref_logits(params, cfg, prompt)
    assert np.abs(logits - want[-1]).max() < ATOL
    # (g) padding rows read and wrote the drop slot, and left it as it
    # was; no page but the row's own full ones got a snapshot
    assert float(jnp.abs(pools.state[0][pools.drop]).max()) == 0.0
    assert pools.snapshot_rows() == pools.pages[:n_prompt // PS]

    window = lfm2.make_decode_window_fn(cfg, True, 64)
    B, K = 2, 4
    first = int(np.argmax(logits))
    carry = (jnp.asarray([first, 0], jnp.int32),
             jnp.asarray([len(prompt), -1], jnp.int32), jnp.zeros(B, bool),
             jnp.zeros(B, jnp.int32), jnp.asarray([100, 1], jnp.int32))
    kv_k, kv_v, state = pools.kv_k, pools.kv_v, pools.state
    toks, vals, ids = [], [], []
    for _ in range(2):
        t, emitted, aux, carry, kv_k, kv_v, state = window(
            params, *carry, kv_k, kv_v, pools.table(B), jnp.zeros(B),
            jnp.zeros(B, jnp.int32), jnp.ones(B), jnp.zeros(B, jnp.uint32),
            jnp.full((B, 8), -1, jnp.int32), None, state,
            jnp.asarray([pools.slot, pools.drop], jnp.int32),
            k_steps=K, logprobs_topn=8)
        assert list(np.asarray(emitted)) == [K, 0]
        toks += [int(x) for x in t[0]]
        vals += list(np.asarray(aux[1][0]))
        ids += list(np.asarray(aux[2][0]))
    seq = list(prompt) + [first] + toks
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, seq[:-1]), -1))
    for j in range(2 * K):
        at = len(prompt) + j
        assert np.abs(vals[j] - want[at][ids[j]]).max() < ATOL
        assert toks[j] == int(np.argmax(want[at]))
    pools.state = state
    assert pools.snapshot_rows() == sorted(
        pools.pages[:(n_prompt + 2 * K) // PS])
    assert float(jnp.abs(state[0][pools.drop]).max()) == 0.0


@pytest.mark.parametrize("cuts", [(13,), (8, 29), (7, 14, 30), (9, 10)])
def test_a_prompt_in_chunks_gives_the_same_logits_and_state(cuts):
    """(a) a 37-token prompt prefilled whole and in chunks whose ends fall
    on every residue of the position mod 3 (the conv's taps) and on and
    off the page boundary, one chunk a single token: the same last
    logits, the same stored state, the same page snapshots."""
    cfg = tiny()
    params = make_params(cfg, 1)
    prompt = np.random.default_rng(1).integers(1, 512, 37)
    whole = Pools(cfg)
    want = whole.run_prefill(params, prompt, 0, 64)
    assert np.abs(want - ref_logits(params, cfg, prompt)[-1]).max() < ATOL

    parts = Pools(cfg)
    edges = (0, *cuts, len(prompt))
    for a, b in zip(edges, edges[1:]):
        got = parts.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(got - want).max() < ATOL
    assert np.abs(np.asarray(parts.state[0][parts.slot])
                  - np.asarray(whole.state[0][whole.slot])).max() < ATOL
    assert parts.snapshot_rows() == whole.snapshot_rows() == [3, 5, 7, 9]
    assert np.abs(np.asarray(parts.state[1])
                  - np.asarray(whole.state[1])).max() < ATOL
    # the fault this guards against is visible at this tolerance: a
    # second chunk that starts from zeros instead of the carried state
    lost = Pools(cfg)
    lost.run_prefill(params, prompt[:cuts[0]], 0, 32)
    lost.state = (jnp.zeros_like(lost.state[0]), lost.state[1])
    for a, b in zip(edges[1:], edges[2:]):
        bad = lost.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(bad - want).max() > 100 * ATOL


def test_a_chunk_after_a_hit_starts_from_the_pages_snapshot():
    """(b) at the level of the programs: sequence A prefills 24 tokens
    (three pages); sequence B, another slot and its own fourth page,
    shares A's three pages and prefills only its last 13 tokens with
    ``state_src`` = A's third page: the logits of the reference's full
    forward of B. Without the source (the slot's junk) and with the
    snapshot of the wrong page they are far off."""
    cfg = tiny()
    params = make_params(cfg, 2)
    rng = np.random.default_rng(2)
    shared, tail = rng.integers(1, 512, 24), rng.integers(1, 512, 13)
    pools = Pools(cfg)
    pools.run_prefill(params, shared, 0, 32)
    b = np.concatenate([shared, tail])
    want = ref_logits(params, cfg, b)[-1]
    pools.pages = [3, 5, 7, 12, 13]
    pools.slot = 1
    keep = pools.kv_k, pools.kv_v, pools.state

    def second(src):
        pools.kv_k, pools.kv_v, pools.state = jax.tree.map(jnp.copy, keep)
        return pools.run_prefill(params, tail, 24, 16, src=src)

    assert np.abs(second(7) - want).max() < ATOL
    assert np.abs(second(-1) - want).max() > 100 * ATOL
    assert np.abs(second(5) - want).max() > 100 * ATOL


# ------------------------------------------------------ through JaxEngine


def _engine(cfg=None, params=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=64, max_batch=4, prefill_chunk=16,
                batch_buckets=(4,), prefill_buckets=(16,),
                page_buckets=(16,), max_prefill_batch=2, decode_steps=4,
                warmup_logprobs=False)
    base.update(over)
    cfg = cfg or tiny()
    return JaxEngine(cfg, EngineConfig(**base),
                     params=params or make_params(cfg), seed=0)


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
    """The engine's top-5 log-probabilities at every position it sampled
    from against the reference's full forward."""
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(eng.params, eng.cfg, prompt + toks[:-1]), -1))
    assert len(tops) == len(toks)
    for j, top in enumerate(tops):
        row = want[len(prompt) - 1 + j]
        assert max(abs(row[i] - v) for i, v in top.items()) < ATOL


def test_the_engine_snapshots_and_keeps_the_prefix_cache_on(run_async):
    """A model whose module declares snapshots keeps prefix reuse; the
    pool by page is the last member of the state and counts in
    state_pool_bytes. A 37-token prompt crosses three prefill chunks of
    16 and then four windows: log-probabilities agree with the reference
    at every position; two sequences interleaved give what each gives
    alone."""
    eng = _engine()
    assert eng.pm.prefix_reuse and eng._state_snapshots
    assert [x.shape for x in eng.state] == [(5, 6 * 2 * 256),
                                            (64, 6 * 2 * 256)]
    p1, p2 = _prompts(2, 37, 11)

    async def main():
        a, tops = await _gen(eng, p1, 13, logprobs=5)
        b, _ = await _gen(eng, p2, 9)
        both = await asyncio.gather(_gen(eng, p1, 13), _gen(eng, p2, 9))
        stats = eng.stats()
        await eng.stop()
        return a, tops, b, both, stats

    a, tops, b, both, stats = run_async(main())
    _agrees(eng, p1, a, tops)
    assert both[0][0] == a and both[1][0] == b
    assert stats["state_slots_active"] == 0
    assert stats["state_pool_bytes"] == sum(x.nbytes for x in eng.state)
    # the second time around p1 and p2 hit their own published pages
    assert stats["state_restores_total"] == 2
    assert stats["prefix_hit_tokens_total"] == 32 + 8


def test_a_prefix_hit_hands_over_pages_and_state(run_async):
    """(b) A is served and its pages published; B shares A's first 40
    tokens (five pages) and is admitted with a hit: its log-probabilities
    at every position after the hit agree with the reference and its
    tokens with B served cold on a fresh engine. The same again after A
    has been released and an unrelated sequence C has taken A's SLOT and
    run through it (the snapshot, not the slot, is what is read), and for
    a B' that shares only three of the pages."""
    params = make_params(tiny(), 3)
    shared, ta, tb, tc, td = _prompts(3, 40, 9, 13, 30, 6)
    pa, pb, pd = shared + ta, shared + tb, shared[:24] + td

    async def main():
        cold = _engine(params=params)
        b_cold, _ = await _gen(cold, pb, 11)
        d_cold, _ = await _gen(cold, pd, 11)
        await cold.stop()
        eng = _engine(params=params)
        await _gen(eng, pa, 7)
        s0 = eng.stats()
        b1, tops1 = await _gen(eng, pb, 11, logprobs=5)
        s1 = eng.stats()
        slots = []
        admit = eng._admit

        def spy():
            admit()
            slots.extend(s.state_slot for s in eng.prefilling)

        eng._admit = spy
        await _gen(eng, tc, 9)          # C: unrelated, reuses the slot
        b2, tops2 = await _gen(eng, pb, 11, logprobs=5)
        d, tops_d = await _gen(eng, pd, 11, logprobs=5)
        s2 = eng.stats()
        await eng.stop()
        return eng, b_cold, d_cold, (b1, tops1), (b2, tops2), (d, tops_d), \
            s0, s1, s2, slots

    (eng, b_cold, d_cold, (b1, tops1), (b2, tops2), (d, tops_d),
     s0, s1, s2, slots) = run_async(main())
    assert s0["state_restores_total"] == 0
    assert s1["state_restores_total"] == 1
    assert s1["prefix_hit_tokens_total"] - s0["prefix_hit_tokens_total"] == 40
    assert s1["prefill_tokens_total"] - s0["prefill_tokens_total"] == 13
    assert b1 == b_cold and b2 == b_cold and d == d_cold
    _agrees(eng, pb, b1, tops1)
    _agrees(eng, pb, b2, tops2)
    _agrees(eng, pd, d, tops_d)
    # every request ran in the same slot, one after the other: C's state
    # was in it when B came back
    assert len(set(slots)) == 1
    # C took no hit; the second B hit its own 48 tokens, B' 24
    assert s2["state_restores_total"] == 3
    assert s2["prefix_hit_tokens_total"] \
        - s1["prefix_hit_tokens_total"] == 48 + 24


def test_a_hit_read_as_zeros_is_seen(run_async):
    """(b), the control: the same two requests on an engine whose restore
    is replaced by 'no source' (the chunk then starts from what the slot
    held): B's log-probabilities leave the reference by far more than
    the tolerance. What test_a_prefix_hit_hands_over_pages_and_state
    passes on is the snapshot."""
    params = make_params(tiny(), 3)
    shared, ta, tb = _prompts(3, 40, 9, 13)
    eng = _engine(params=params)
    eng._state_args = lambda slots, src=None: (
        (eng.state, slots) if src is None
        else (eng.state, slots, eng._no_src(len(slots))))

    async def main():
        await _gen(eng, shared + ta, 7)
        out = await _gen(eng, shared + tb, 11, logprobs=5)
        await eng.stop()
        return out

    toks, tops = run_async(main())
    assert eng.stats()["state_restores_total"] == 1
    want = np.asarray(jax.nn.log_softmax(ref_logits(
        eng.params, eng.cfg, shared + tb + toks[:-1]), -1))
    row = want[len(shared + tb) - 1]
    assert max(abs(row[i] - v) for i, v in tops[0].items()) > 100 * ATOL


def test_an_evicted_page_takes_its_snapshot_with_it(run_async):
    """(c) a pool of 12 pages: A's six pages are published, then two
    unrelated sequences need the pool and evict them (their snapshots
    are overwritten by the new owners of the same page ids). A again
    misses, prefills from position 0 and answers as before."""
    params = make_params(tiny(), 4)
    pa, pb, pc = _prompts(4, 44, 41, 43)
    eng = _engine(params=params, num_pages=12, watermark_pages=1)

    async def main():
        a1, tops = await _gen(eng, pa, 5, logprobs=5)
        s0 = eng.stats()
        await _gen(eng, pb, 5)
        await _gen(eng, pc, 5)
        s1 = eng.stats()
        a2, tops2 = await _gen(eng, pa, 5, logprobs=5)
        s2 = eng.stats()
        await eng.stop()
        return a1, tops, a2, tops2, s0, s1, s2

    a1, tops, a2, tops2, s0, s1, s2 = run_async(main())
    assert a1 == a2
    _agrees(eng, pa, a1, tops)
    _agrees(eng, pa, a2, tops2)
    assert s2["state_restores_total"] == 0
    assert s2["prefix_hit_tokens_total"] == 0
    assert s2["prefill_tokens_total"] - s1["prefill_tokens_total"] == 44


def test_a_row_that_stops_mid_window_keeps_the_state_of_its_last_token(
        run_async):
    """(g) max_tokens 3 = one token from prefill and two of a 4-step
    window: the row freezes after step 2. Its slot then holds the state
    after the last token it CONSUMED, and the page it was filling has no
    snapshot (its last token was never written). Shown on logits: one
    more decode step from the slot and the pages against the reference's
    last row."""
    eng = _engine()
    (p,) = _prompts(5, 21)              # 21 + 2 consumed = 23: page 2 open
    held = []
    release = eng._release

    def spy(seq):
        held.append((list(seq.pages), seq.state_slot))
        release(seq)

    eng._release = spy
    eng.state = (eng.state[0], eng.state[1] + 5.0)

    async def main():
        toks, _ = await _gen(eng, p, 3)
        await eng.stop()
        return toks

    toks = run_async(main())
    assert len(toks) == 3
    (pages, slot), = [h for h in held if h[1] is not None]
    snap = np.asarray(eng.state[1])
    assert sorted(q for q in range(len(snap))
                  if not np.all(snap[q] == 5.0)) == sorted(pages[:2])
    pos = len(p) + 2                    # position of the unconsumed token
    table = np.zeros((4, 16), np.int32)
    table[0, :len(pages)] = pages
    flat = np.full(4, DROP_SLOT, np.int32)
    flat[0] = pages[pos // PS] * PS + pos % PS
    positions = np.full(4, -1, np.int32)
    positions[0] = pos
    slots = np.full(4, eng.ecfg.max_batch, np.int32)
    slots[0] = slot
    logits, _, _, state = eng.decode_fn(
        eng.params, jnp.asarray([toks[-1], 0, 0, 0], jnp.int32),
        jnp.asarray(positions), eng.kv_k, eng.kv_v, jnp.asarray(table),
        jnp.asarray(flat), eng.state, jnp.asarray(slots))
    want = ref_logits(eng.params, eng.cfg, p + toks)[-1]
    assert np.abs(np.asarray(logits[0]) - want).max() < ATOL
    # that step wrote position 23, the last of page 2: its snapshot
    assert not np.all(np.asarray(state[1])[pages[2]] == 5.0)


def test_preempt_and_resume_equals_an_uninterrupted_run(run_async):
    """(d) a pool too small for four rows preempts some; a preempted row
    gives up its slot and its pages, comes back (here on a miss: the rows
    that stay take its pages before it returns), prefills again from
    position 0 into whichever slot it is given, and still answers as it
    does alone."""
    params = make_params(tiny(), 6)
    prompts = _prompts(6, 16, 16, 16, 16)
    eng = _engine(params=params, num_pages=16, watermark_pages=1,
                  prefill_buckets=(16, 32), prefill_chunk=32)
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s): s for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)
        assert all(s.state_slot is None for s in eng.waiting)

    eng._grow_or_preempt = spy

    async def main():
        alone_eng = _engine(params=params)
        alone = [(await _gen(alone_eng, p, 16))[0] for p in prompts]
        await alone_eng.stop()
        together = await asyncio.wait_for(asyncio.gather(*(
            _gen(eng, p, 16) for p in prompts)), 300)
        stats = eng.stats()
        await eng.stop()
        return alone, [t for t, _ in together], stats

    alone, together, stats = run_async(main())
    assert preempted, "the pool was meant to run out"
    assert together == alone
    assert stats["state_slots_active"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]


def test_a_preempted_row_resumes_from_its_own_pages_snapshot(run_async):
    """(d) the pool refuses one row a page after it has decoded 8 tokens
    (PageManager.grow made to fail twice: the engine's own preemption
    runs): the row gives up slot and pages, is admitted again with a hit
    on the pages it published itself, the third of them filled by a
    decode WINDOW, and starts from that page's snapshot. Its tokens and
    log-probabilities are those of the uninterrupted run and of the
    reference."""
    params = make_params(tiny(), 7)
    (p,) = _prompts(7, 21)
    eng = _engine(params=params)
    grow, fails, seen = eng.pm.grow, [0], []

    def spy(pages, needed):
        seq = eng.running[0] if eng.running else None
        if seq is not None and seq.generated >= 8 and not seen:
            seen.append(len(seq.tokens))
            fails[0] = 2
        if fails[0]:
            fails[0] -= 1
            return False
        return grow(pages, needed)

    eng.pm.grow = spy

    async def main():
        alone_eng = _engine(params=params)
        alone, _ = await _gen(alone_eng, p, 20)
        await alone_eng.stop()
        toks, tops = await _gen(eng, p, 20, logprobs=5)
        stats = eng.stats()
        await eng.stop()
        return alone, toks, tops, stats

    alone, toks, tops, stats = run_async(main())
    assert seen and toks == alone
    _agrees(eng, p, toks, tops)
    # (a resumed row's hit is not counted among prefix_hit_tokens_total)
    assert seen[0] >= 29 and stats["state_restores_total"] == 1
    # the resumed row prefilled only what lies past its last full page
    assert 21 < stats["prefill_tokens_total"] <= 21 + PS


def test_warmup_covers_the_serving_forms(run_async):
    """The state operands (with the source pages of a prefill) are part
    of every program's call form: warmup() goes through the same helpers
    as serving, so nothing compiles after it, on a miss or on a hit."""
    eng = _engine()
    eng.warmup()
    (p,) = _prompts(7, 37)

    async def main():
        toks, _ = await _gen(eng, p, 9)
        again, _ = await _gen(eng, p, 9)
        stats = eng.stats()
        await eng.stop()
        return toks, again, stats

    toks, again, stats = run_async(main())
    assert len(toks) == 9 and again == toks
    assert stats["state_restores_total"] == 1
    assert stats["post_warmup_compiles_total"] == 0


# ---------------------------------------------- what stays as it was (e)


def test_jamba_still_takes_no_hit_and_declares_no_snapshots(run_async):
    """(e) models/jamba.py declares no snapshot pool: the engine turns
    the prefix cache off for it, the same prompt twice prefills twice,
    its programs take no source operand and its stats count no
    restore."""
    from dynamo_tpu.models import jamba
    from tests.test_jamba import _engine as jamba_engine

    assert not hasattr(jamba, "init_state_snapshots")
    eng = jamba_engine()
    assert not eng.pm.prefix_reuse and not eng._state_snapshots
    assert len(eng._state_args(np.zeros(2, np.int32),
                               np.zeros(2, np.int32))) == 2
    (p,) = _prompts(8, 40)

    async def main():
        a, _ = await _gen(eng, p, 4)
        b, _ = await _gen(eng, p, 4)
        stats = eng.stats()
        await eng.stop()
        return a, b, stats

    a, b, stats = run_async(main())
    assert a == b
    assert stats["prefill_tokens_total"] == 80
    assert stats["prefix_hit_tokens_total"] == 0
    assert stats["state_restores_total"] == 0 and not eng.pm.by_hash


def _refused(what):
    return pytest.raises(
        NotImplementedError,
        match=f"{what}.*recurrent state.*jamba.py, .*models/lfm2.py.*"
              "under its page's id, or not at all")


class _Stateful:
    """Stands for an engine that serves a model with recurrent state."""
    state = object()
    family = family_of(tiny())


@pytest.mark.parametrize("what,build", [
    ("host KV tier", lambda: _engine(host_pages=8)),
    ("spec_decode", lambda: _engine(spec_decode=True)),
    ("mesh", lambda: JaxEngine(
        tiny(), EngineConfig(page_size=PS, num_pages=16),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                               ("data", "model")))),
    ("disaggregated prefill worker",
     lambda: __import__("dynamo_tpu.llm.disagg.prefill_worker",
                        fromlist=["PrefillWorker"]).PrefillWorker(
                            None, _Stateful())),
    ("KV transfer server",
     lambda: __import__("dynamo_tpu.llm.disagg.transfer",
                        fromlist=["KvTransferServer"]).KvTransferServer(
                            _Stateful())),
])
def test_what_still_refuses_a_model_with_state(what, build):
    """(e) snapshots by the page change nothing for the paths that move
    pages between places or roll a state back: each still refuses, and
    says what is true today."""
    with _refused(what):
        build()
