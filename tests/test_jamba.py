"""Jamba (Mamba-1 + attention, models/jamba.py): the step programs and
the engine's recurrent-state pool against the plain reference
(benchmark/configs/jamba2-3b/reference.py), on the CPU at a tiny size:
float32, ONE whole period of 14 layers (attention at layer 7), d_state
16, d_conv 4, seeded random weights.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in row blocks with a carried state, the reference as
one sequence from zero), so logits of magnitude ~3 differ by a few 1e-6
(measured 7e-6 at worst); ATOL = 1e-4 leaves room and is still 100x
under what a dropped state, a wrong conv tail or a missed token moves
(1e-2 and more, see the tests that provoke them)."""

import asyncio
import importlib.util
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import jamba
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import family_of, get_model_module
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 1e-4
PS = 8


def _reference():
    spec = importlib.util.spec_from_file_location(
        "jamba_reference", os.path.join(
            ROOT, "benchmark", "configs", "jamba2-3b", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny(**over) -> ModelConfig:
    hf = dict(model_type="jamba", vocab_size=512, hidden_size=64,
              intermediate_size=128, num_hidden_layers=14,
              num_attention_heads=4, num_key_value_heads=1,
              mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
              mamba_dt_rank=8, attn_layer_period=14, attn_layer_offset=7,
              num_experts=1, rms_norm_eps=1e-6, tie_word_embeddings=False)
    hf.update(over)
    cfg = ModelConfig.from_hf_config(hf)
    cfg.dtype = "float32"
    return cfg


def ref_logits(params, cfg, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens))


class Pools:
    """One sequence's pages and state slot in small pools, driven the way
    the engine drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2), slot=2, slots=5):
        self.cfg = cfg
        self.kv_k, self.kv_v = jamba.init_kv_cache(cfg, KVCacheSpec(16, PS))
        ssm, conv = jamba.init_state(cfg, slots)
        # what a previous owner left in the slot must not matter
        self.state = (ssm.at[slot].set(7.0), conv.at[:, slot].set(3.0))
        self.pages, self.slot, self.drop = list(pages), slot, slots - 1
        self.prefill, self.decode = jamba.make_step_fns(cfg)

    def table(self, rows, width=8):
        t = np.zeros((rows, width), np.int32)
        t[0, :len(self.pages)] = self.pages
        return jnp.asarray(t)

    def run_prefill(self, params, tokens, start, bucket):
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
            jnp.asarray([self.slot, self.drop], jnp.int32))
        return np.asarray(logits[0])


def test_from_hf_config_on_the_catalog_config():
    """(e) the published config: attention at layers 7 and 21 only, the
    Mamba sizes as published, and what the module does not compute is
    refused."""
    with open(os.path.join(ROOT, "benchmark", "configs", "jamba2-3b",
                           "about.json")) as f:
        published = json.load(f)["published"]
    cfg = ModelConfig.from_hf_config(published)
    assert cfg.attn_layer_ids == (7, 21) and cfg.num_layers == 28
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank,
            cfg.mamba_d_inner) == (16, 4, 160, 5120)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (20, 1, 128)
    assert cfg.tie_word_embeddings and cfg.has_recurrent_state
    assert get_model_module(cfg) is jamba
    assert jamba.segments(cfg) == [("mamba", 0, 0, 7), ("attn", 0, 7),
                                   ("mamba", 7, 8, 13), ("attn", 1, 21),
                                   ("mamba", 20, 22, 6)]
    with pytest.raises(NotImplementedError, match="num_experts"):
        ModelConfig.from_hf_config(dict(published, num_experts=2))
    with pytest.raises(NotImplementedError, match="sliding_window"):
        ModelConfig.from_hf_config(dict(published, sliding_window=4096))


@pytest.mark.parametrize("tied,n_prompt,interpret", [
    (False, 21, False), (True, 21, False), (False, PS - 2, False),
    (False, 21, True)], ids=["untied", "tied", "page-edge",
                             "pallas_interpret"])
def test_prefill_and_window_match_reference(tied, n_prompt, interpret):
    """(a) and (f): prefill_step then two decode_windows through the
    pools against the reference's full forward, on logits (the window's
    top-8 log-probabilities at each of its steps), with the head tied
    and not. The second window reads from the pool what the first one
    committed: from a prompt of PS - 2 the first window's four rows lie
    on both sides of a page boundary (commit_window's second page slot).
    ``pallas_interpret``: the window's kernels under interpretation, the
    scan state advanced in the pool (ops/selective_scan.py); the second
    window then starts from what the first one's kernel left there."""
    cfg = tiny(tie_word_embeddings=tied)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    assert ("lm_head" in params) == (not tied)
    pools = Pools(cfg)
    prompt = np.random.default_rng(0).integers(1, 512, n_prompt)
    logits = pools.run_prefill(params, prompt, 0, 32)
    want = ref_logits(params, cfg, prompt)
    assert np.abs(logits - want[-1]).max() < ATOL
    # padding rows read and wrote the drop slot, and left it as it was
    assert float(jnp.abs(pools.state[0][pools.drop]).max()) == 0.0

    window = jamba.make_decode_window_fn(cfg, True, 64,
                                         pallas_interpret=interpret)
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
    # the padding row went through the drop slot and left it as it was
    assert float(jnp.abs(state[0][pools.drop]).max()) == 0.0
    seq = list(prompt) + [first] + toks
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, seq[:-1]), -1))
    for j in range(2 * K):
        at = len(prompt) + j
        assert np.abs(vals[j] - want[at][ids[j]]).max() < ATOL
        assert toks[j] == int(np.argmax(want[at]))


@pytest.mark.parametrize("cuts", [(13,), (8, 29)])
def test_a_prompt_in_chunks_gives_the_same_logits_and_state(cuts):
    """(b) a 37-token prompt prefilled whole, in 2 and in 3 chunks (one
    cut off a page boundary is not possible in the engine; these are on
    it and off it for the scan and the conv tail, which do not care):
    the same last logits and the same stored state."""
    cfg = tiny()
    params = jamba.init_params(cfg, jax.random.PRNGKey(1))
    prompt = np.random.default_rng(1).integers(1, 512, 37)
    whole = Pools(cfg)
    want = whole.run_prefill(params, prompt, 0, 64)
    assert np.abs(want - ref_logits(params, cfg, prompt)[-1]).max() < ATOL

    parts = Pools(cfg)
    edges = (0, *cuts, len(prompt))
    for a, b in zip(edges, edges[1:]):
        got = parts.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(got - want).max() < ATOL
    for x, y in zip(parts.state, whole.state):
        assert np.abs(np.asarray(x[parts.slot], np.float32)
                      - np.asarray(y[whole.slot], np.float32)
                      ).max() < ATOL
    # the fault this guards against is visible at this tolerance: a
    # second chunk that starts from zeros instead of the carried state
    lost = Pools(cfg)
    lost.run_prefill(params, prompt[:cuts[0]], 0, 32)
    lost.state = jax.tree.map(jnp.zeros_like, lost.state)
    for a, b in zip(edges[1:], edges[2:]):
        bad = lost.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(bad - want).max() > 100 * ATOL


# ------------------------------------------------------ through JaxEngine


def _engine(cfg=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=64, max_batch=4, prefill_chunk=16,
                batch_buckets=(4,), prefill_buckets=(16,),
                page_buckets=(16,), max_prefill_batch=2, decode_steps=4,
                warmup_logprobs=False)
    base.update(over)
    return JaxEngine(cfg or tiny(), EngineConfig(**base), seed=0)


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


def test_generate_matches_reference_and_interleaving_changes_nothing(
        run_async):
    """(c) a 37-token prompt crosses three prefill chunks of 16 and then
    four windows: the engine's top-5 log-probabilities agree with the
    reference at every position; two sequences interleaved give what
    each gives alone."""
    eng = _engine()
    p1, p2 = _prompts(2, 37, 11)

    async def main():
        a, tops = await _gen(eng, p1, 13, logprobs=5)
        b, _ = await _gen(eng, p2, 9)
        both = await asyncio.gather(_gen(eng, p1, 13), _gen(eng, p2, 9))
        stats = eng.stats()
        await eng.stop()
        return a, tops, b, both, stats

    a, tops, b, both, stats = run_async(main())
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(eng.params, eng.cfg, p1 + a[:-1]), -1))
    for j, top in enumerate(tops):
        row = want[len(p1) - 1 + j]
        assert max(abs(row[i] - v) for i, v in top.items()) < ATOL
    assert both[0][0] == a and both[1][0] == b
    assert stats["state_slots_active"] == 0
    assert stats["state_slots_total"] == 4
    assert stats["state_pool_bytes"] == sum(x.nbytes for x in eng.state)
    assert 0 < stats["state_slots_held_total"] \
        <= stats["state_slots_seen_total"]


def test_the_same_prompt_twice_prefills_twice(run_async):
    """(d) no prefix hit for a model with recurrent state: the second
    request computes every prompt token again and answers alike; no page
    is ever published."""
    eng = _engine()
    (p,) = _prompts(3, 40)

    async def main():
        a, _ = await _gen(eng, p, 6)
        mid = eng.stats()
        b, _ = await _gen(eng, p, 6)
        end = eng.stats()
        await eng.stop()
        return a, b, mid, end

    a, b, mid, end = run_async(main())
    assert a == b
    assert mid["prefill_tokens_total"] == 40
    assert end["prefill_tokens_total"] == 80
    assert end["prefix_hit_tokens_total"] == 0
    assert end["kv_cached_blocks"] == 0 and not eng.pm.by_hash


def test_a_slot_is_reused_while_the_previous_window_is_in_flight(run_async):
    """(c) max_batch 2 = two state slots, five requests of different
    lengths: each finish hands its slot to a waiting request while the
    pipelined window that still lists the finished row is in flight.
    Every answer equals the one the request gets alone."""
    eng = _engine(max_batch=2, batch_buckets=(2,))
    prompts = _prompts(4, 9, 21, 14, 30, 5)
    lens = [5, 11, 7, 3, 9]
    claimed = []
    admit = eng._admit

    def spy():
        before = {id(s) for s in eng.prefilling}
        admit()
        claimed.extend((s.state_slot, bool(eng._inflight))
                       for s in eng.prefilling if id(s) not in before)

    eng._admit = spy

    async def main():
        alone = [(await _gen(eng, p, n))[0] for p, n in zip(prompts, lens)]
        del claimed[:]
        together = await asyncio.gather(*(
            _gen(eng, p, n) for p, n in zip(prompts, lens)))
        stats = eng.stats()
        await eng.stop()
        return alone, [t for t, _ in together], stats

    alone, together, stats = run_async(main())
    assert together == alone
    assert len(claimed) == 5 and {s for s, _ in claimed} == {0, 1}
    assert any(inflight for _, inflight in claimed[2:]), \
        "no slot changed hands with a window in flight"
    assert stats["state_slots_active"] == 0


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla", "pallas_interpret"])
def test_a_row_that_stops_mid_window_keeps_the_state_of_its_last_token(
        run_async, monkeypatch, interpret):
    """(c) max_tokens 3 = one token from prefill and two of a 4-step
    window: the row freezes after step 2. Its slot then holds the state
    after the last token it CONSUMED (prompt + 2 tokens; the third was
    sampled and never fed back). Shown on logits: one more decode step
    from the slot and the pages, on the third token, against the
    reference's last row. ``pallas_interpret``: the engine's window and
    the decode step here run the scan kernel on the pool (under
    interpretation), where a frozen row's dt = 0 writes back the bits it
    read."""
    if interpret:
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    eng = _engine()
    (p,) = _prompts(5, 19)
    held = []
    release = eng._release

    def spy(seq):
        held.append((list(seq.pages), seq.state_slot))
        release(seq)

    eng._release = spy

    async def main():
        toks, _ = await _gen(eng, p, 3)
        await eng.stop()
        return toks

    toks = run_async(main())
    assert len(toks) == 3
    (pages, slot), = [h for h in held if h[1] is not None]
    pos = len(p) + 2                    # position of the unconsumed token
    table = np.zeros((4, 16), np.int32)
    table[0, :len(pages)] = pages
    flat = np.full(4, DROP_SLOT, np.int32)
    flat[0] = pages[pos // PS] * PS + pos % PS
    positions = np.full(4, -1, np.int32)
    positions[0] = pos
    slots = np.full(4, eng.ecfg.max_batch, np.int32)
    slots[0] = slot
    logits, *_ = eng.decode_fn(
        eng.params, jnp.asarray([toks[-1], 0, 0, 0], jnp.int32),
        jnp.asarray(positions), eng.kv_k, eng.kv_v, jnp.asarray(table),
        jnp.asarray(flat), eng.state, jnp.asarray(slots))
    want = ref_logits(eng.params, eng.cfg, p + toks)[-1]
    assert np.abs(np.asarray(logits[0]) - want).max() < ATOL


def test_preempt_and_resume_equals_an_uninterrupted_run(run_async):
    """(c) a pool too small for four rows preempts some; a preempted row
    gives up its slot, prefills again from position 0 into whichever
    slot it is given, and still answers as it does alone."""
    eng = _engine(num_pages=16, watermark_pages=1, prefill_buckets=(16, 32),
                  prefill_chunk=32)
    prompts = _prompts(6, 16, 16, 16, 16)
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s): s for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)
        assert all(s.state_slot is None for s in eng.waiting)

    eng._grow_or_preempt = spy

    async def main():
        alone = [(await _gen(eng, p, 16))[0] for p in prompts]
        del preempted[:]
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


def test_warmup_covers_the_serving_forms(run_async):
    """The state operand is part of every program's call form: warmup()
    goes through the same helpers as serving, so nothing compiles after
    it, with the fence set to raise."""
    eng = _engine()
    eng.warmup()
    (p,) = _prompts(7, 37)

    async def main():
        toks, _ = await _gen(eng, p, 9)
        stats = eng.stats()
        await eng.stop()
        return toks, stats

    toks, stats = run_async(main())
    assert len(toks) == 9 and stats["post_warmup_compiles_total"] == 0


def test_models_without_state_take_no_state_operand():
    """A llama engine holds no pool, reports no state keys and keeps its
    call forms: its programs are the parent's."""
    eng = JaxEngine(ModelConfig.tiny(), EngineConfig(
        page_size=PS, num_pages=16, max_batch=2, batch_buckets=(2,),
        prefill_buckets=(16,), page_buckets=(8,), prefill_chunk=16), seed=0)
    assert eng.state is None and eng._state_args(None) == ()
    assert eng.pm.prefix_reuse
    assert not [k for k in eng.stats() if k.startswith("state_")]


# ------------------------------------------- the scan kernel on the pool


def _scan_case(rng, S, M, N, di, slots, still=()):
    """A pool of random states and one token's operands for the rows at
    ``slots``; rows in ``still`` (and every row on the last slot, the
    engine's drop slot) have dt = 0."""
    B = len(slots)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = rng.uniform(1e-3, 1e-1, (B, di))
    dt[[b for b in range(B) if b in still or slots[b] == S - 1]] = 0.0
    return (f(S, M, N, di), jnp.asarray(slots, jnp.int32),
            (jnp.asarray(dt, jnp.float32), f(B, di), f(B, N), f(B, N),
             -jnp.exp(f(N, di))))


@pytest.mark.parametrize("slots,rows_per_step,still", [
    ((3,), None, ()),                                   # one row
    ((5, 0, 3, 1, 6, 2, 4, 7), None, ()),               # eight, permuted
    ((4, 1, 6), None, (1,)),                            # not a power of two
    ((7, 2, 9, 0, 5, 11, 3, 10, 1, 8, 6, 4), 8, (3,)),  # a short last group
    ((2, 12, 5, 12, 12, 0, 12, 9, 12, 12, 12, 12, 12, 12, 12, 12,
      12, 12, 12, 12, 7, 12, 12, 12), 8, (0,)),         # rows on the drop slot
], ids=["one", "eight-permuted", "three", "twelve-in-groups",
        "drop-slot-shared"])
def test_scan_kernel_in_the_pool_matches_the_step_on_gathered_rows(
        slots, rows_per_step, still):
    """ops/selective_scan.py under interpretation against _ssm_step on
    the gathered rows: y and the rows' new state agree to float32
    rounding (the same products, the sum over N in another order); a row
    with dt = 0 keeps its state BIT FOR BIT, and so does the drop slot
    that several padding rows share, across grid steps; a call on layer m
    touches no other layer of any slot and no slot that is not listed;
    a second layer of the same pool then does the same on its own m; a
    row marked fresh starts from zeros whatever its slot held."""
    from dynamo_tpu.ops.selective_scan import selective_scan_step

    S, M, N, di = 13, 3, 16, 256
    rng = np.random.default_rng(len(slots))
    pool, at, row = _scan_case(rng, S, M, N, di, slots, still)
    pool0 = np.asarray(pool)
    idx = np.asarray(slots)
    for m in (1, 2):                    # two layers of ONE pool, in turn
        before = np.asarray(pool)
        want_s, want_y = jamba._ssm_step(pool[at, m], *row)
        pool, y = selective_scan_step(pool, at, jnp.int32(m), *row,
                                      interpret=True,
                                      rows_per_step=rows_per_step)
        got = np.asarray(pool)
        live = [b for b in range(len(slots))
                if float(jnp.abs(row[0][b]).max()) > 0]
        assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
        assert np.abs(got[idx[live], m]
                      - np.asarray(want_s)[live]).max() < 1e-5
        assert np.abs(got[idx[live], m] - before[idx[live], m]).max() > 1e-3
        # rows that do not advance, the drop slot among them: the same bits
        for b in set(range(len(slots))) - set(live):
            assert (got[idx[b], m] == before[idx[b], m]).all()
        # nothing else moved: the other layers, the slots no row holds
        others = [x for x in range(M) if x != m]
        assert (got[:, others] == before[:, others]).all()
        unheld = sorted(set(range(S)) - set(slots))
        assert (got[unheld] == before[unheld]).all()
    assert (np.asarray(pool)[:, 0] == pool0[:, 0]).all()
    # a fresh row reads zeros, whatever its slot held
    fresh = jnp.arange(len(slots)) == 0
    want_s, want_y = jamba._ssm_step(
        jnp.where(fresh[:, None, None], 0.0, pool[at, 0]), *row)
    pool, y = selective_scan_step(pool, at, jnp.int32(0), *row, fresh,
                                  interpret=True,
                                  rows_per_step=rows_per_step)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
    assert np.abs(np.asarray(pool[at[0], 0])
                  - np.asarray(want_s[0])).max() < 1e-5


@pytest.mark.parametrize("program", ["window", "decode_step"])
def test_the_kernel_arm_never_gathers_or_scatters_the_scan_pool(program,
                                                                monkeypatch):
    """The traced program, not its timing: with the kernel arm on, no
    ``gather`` / ``scatter`` / ``dynamic_slice`` / ``dynamic_update_slice``
    of decode_window (or decode_step) has an operand of the scan pool's
    shape, no value anywhere has the gathered rows' shape, and every
    kernel call takes the pool as an operand that IS one of its results
    (``input_output_aliases``). On the XLA arm the same walk finds the
    gather and the scatter: the check can see what it guards against."""
    from tests.test_sampling_topk import _eqns  # every equation, nested too

    cfg = tiny()
    S, B, K = 5, 2, 4
    pool_shape = (S, jamba.num_mamba_layers(cfg), cfg.mamba_d_state,
                  cfg.mamba_d_inner)
    rows_shape = (B,) + pool_shape[1:]
    params = jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.PRNGKey(0)))
    kv_k, kv_v = jax.eval_shape(
        lambda: jamba.init_kv_cache(cfg, KVCacheSpec(16, PS)))
    state = jax.eval_shape(lambda: jamba.init_state(cfg, S))
    s = jax.ShapeDtypeStruct
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)

    def trace(interpret):
        if program == "window":
            fn = jamba.make_decode_window_fn(cfg, True, 64,
                                             pallas_interpret=interpret)
            return jax.make_jaxpr(partial(fn, k_steps=K, logprobs_topn=0))(
                params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
                s((B, 8), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
                s((B, 8), jnp.int32), None, state, i32)
        _, fn = jamba.make_step_fns(cfg)
        return jax.make_jaxpr(fn)(params, i32, i32, kv_k, kv_v,
                                  s((B, 8), jnp.int32), i32, state, i32)

    def walk(jaxpr):
        moves, kernels, rows = [], [], 0
        for eqn in _eqns(jaxpr.jaxpr):
            shapes = [getattr(v.aval, "shape", None) for v in eqn.invars]
            rows += sum(getattr(v.aval, "shape", None) == rows_shape
                        for v in eqn.outvars)
            if eqn.primitive.name in ("gather", "scatter", "dynamic_slice",
                                      "dynamic_update_slice") \
                    and shapes[0] == pool_shape:
                moves.append(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call" and pool_shape in shapes:
                kernels.append((shapes.index(pool_shape), eqn))
        return moves, kernels, rows

    moves, kernels, rows = walk(trace(False))
    assert "gather" in moves and "scatter" in moves and rows and not kernels

    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")     # decode_step's hook
    moves, kernels, rows = walk(trace(True))
    assert moves == [] and rows == 0
    # one call a run of Mamba layers a step (the jitted wrapper's trace
    # is shared; the call sites are not)
    runs = sum(seg[0] == "mamba" for seg in jamba.segments(cfg))
    assert len(kernels) == runs * (K if program == "window" else 1)
    for operand, eqn in kernels:
        aliases = dict(eqn.params["input_output_aliases"])
        assert operand in aliases
        assert eqn.outvars[aliases[operand]].aval.shape == pool_shape


# ------------------------------------------- the conv tails of a decode step


# family -> (layers M, rows B, channels C of a tail, a b_conv leaf): the
# tail shapes of the four families at small widths
_TAILS = {
    "mamba1": (3, 8, 128, True),                # d_inner
    "mamba2": (2, 5, 128 + 2 * 16, True),       # d_inner + 2N, five rows
    "kda-32-heads": (2, 16, 3 * 32 * 8, False),     # q, k, v of 32 heads
    "kda-64-heads": (2, 32, 3 * 64 * 8, False),     # of 64, two grid steps
}


@pytest.mark.parametrize("family", list(_TAILS))
def test_the_tail_step_is_the_chunk_form_at_one_token_bit_for_bit(
        family, monkeypatch):
    """ops/conv_step.py (under interpretation, through _causal_conv's
    ``tail_step``) against _causal_conv's chunk form on a chunk of ONE
    token, bf16 tails and weights: the same result and the same next
    tail BIT FOR BIT, for rows that advance, rows that are frozen
    (``valid`` false keeps the tail) and a fresh row (a tail of zeros);
    on two layers of one carried array in turn, a call on layer m
    touching no other layer. 64 heads: rows in two grid steps."""
    from dynamo_tpu.ops import conv_step

    M, B, C, has_bias = _TAILS[family]
    dc = 4
    if family == "kda-64-heads":
        monkeypatch.setattr(conv_step, "_BLOCK_BYTES", 16 * 3 * C * 2)
        assert conv_step._rows_per_step(B, 3 * C * 2) == 16
    rng = np.random.default_rng(C)
    bf = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    tails = bf(M, B, (dc - 1) * C).at[:, 0].set(0)      # row 0 is fresh
    valid = jnp.asarray(np.arange(B) % 3 != 1)[:, None]     # 1, 4, ..: frozen
    chunk = jax.jit(lambda mp, x, tail: jamba._causal_conv(
        mp, x, valid, tail, dc))
    step = jax.jit(lambda mp, x, tails, m: jamba._causal_conv(
        mp, x, valid, tails, dc,
        tail_step=lambda t, *row: conv_step.conv_tail_step(
            t, m, *row, interpret=True)))
    bits = lambda a: np.asarray(a.astype(jnp.float32))
    for m in (1, 0):
        mp = {"conv_w": bf(dc, C)}
        if has_bias:
            mp["b_conv"] = bf(C)
        x = jnp.asarray(rng.normal(size=(B, 1, C)), jnp.float32)
        before = bits(tails)
        want_xc, want_tail = chunk(mp, x, tails[m])
        got_xc, tails = step(mp, x, tails, jnp.int32(m))
        assert got_xc.dtype == jnp.float32 and tails.dtype == jnp.bfloat16
        assert (np.asarray(got_xc) == np.asarray(want_xc)).all()
        got = bits(tails)
        assert (got[m] == bits(want_tail)).all()
        frozen = ~np.asarray(valid)[:, 0]
        assert (got[m][frozen] == before[m][frozen]).all()
        # a row that advances dropped its oldest input and took x in
        assert (got[m][~frozen][:, :2 * C] == before[m][~frozen][:, C:]).all()
        assert (got[m][~frozen][:, 2 * C:]
                == bits(x[:, 0].astype(jnp.bfloat16))[~frozen]).all()
        others = [k for k in range(M) if k != m]
        assert (got[others] == before[others]).all()


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla", "pallas_interpret"])
def test_a_windows_tails_are_those_of_single_steps_and_of_one_chunk(
        interpret, monkeypatch):
    """A window of K steps leaves in the pool the conv tails (and the
    scan state) that K single ``forward(T = 1)`` calls on its tokens
    leave, and that ONE ``forward(T = K)`` chunk of them leaves: the
    tails' layout and the step form change where the bytes lie and how
    they move, not what a slot holds. ``pallas_interpret``: the window
    and the single steps advance tails and state through the kernels
    (ops/conv_step.py on the carried tails, ops/selective_scan.py on the
    pool); the chunk is the chunk form either way."""
    if interpret:
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")     # decode_step
    cfg = tiny()
    params = jamba.init_params(cfg, jax.random.PRNGKey(4))
    pools = Pools(cfg)
    prompt = np.random.default_rng(4).integers(1, 512, 19)
    first = int(np.argmax(pools.run_prefill(params, prompt, 0, 32)))
    start = jax.tree.map(jnp.copy, (pools.kv_k, pools.kv_v, pools.state))
    slots = jnp.asarray([pools.slot, pools.drop], jnp.int32)
    B, K, n = 2, 4, len(prompt)

    window = jamba.make_decode_window_fn(cfg, True, 64,
                                         pallas_interpret=interpret)
    toks, emitted, *_, in_window = window(
        params, jnp.asarray([first, 0], jnp.int32),
        jnp.asarray([n, -1], jnp.int32), jnp.zeros(B, bool),
        jnp.zeros(B, jnp.int32), jnp.asarray([100, 1], jnp.int32),
        *jax.tree.map(jnp.copy, start[:2]), pools.table(B), jnp.zeros(B),
        jnp.zeros(B, jnp.int32), jnp.ones(B), jnp.zeros(B, jnp.uint32),
        jnp.full((B, 8), -1, jnp.int32), None, jax.tree.map(jnp.copy,
                                                            start[2]),
        slots, k_steps=K, logprobs_topn=0)
    assert list(np.asarray(emitted)) == [K, 0]
    fed = [first, *(int(t) for t in toks[0][:K - 1])]   # the last is not fed

    kv_k, kv_v, state = jax.tree.map(jnp.copy, start)
    for j, tok in enumerate(fed):
        at = n + j
        flat = pools.pages[at // PS] * PS + at % PS
        _, kv_k, kv_v, state = pools.decode(
            params, jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([at, -1], jnp.int32), kv_k, kv_v, pools.table(B),
            jnp.asarray([flat, DROP_SLOT], jnp.int32), state, slots)
    pools.kv_k, pools.kv_v, pools.state = jax.tree.map(jnp.copy, start)
    pools.run_prefill(params, fed, n, 8)

    def of_slot(state):
        ssm, conv = state
        assert conv.shape[:2] == (jamba.num_mamba_layers(cfg), 5)
        return np.asarray(ssm[pools.slot]), np.asarray(conv[:, pools.slot])

    for other in (state, pools.state):
        for got, want in zip(of_slot(in_window), of_slot(other)):
            assert np.abs(got - want).max() < ATOL
    # the tails moved (K tokens in, every layer's), and the padding row
    # left the drop slot's as they were
    assert np.abs(of_slot(in_window)[1] - of_slot(start[2])[1]).max() > 1e-2
    assert float(jnp.abs(in_window[1][:, pools.drop]).max()) == 0.0


# ------------------------------------------------------------- refusals


class _Stateful:
    """Stands for an engine that serves a model with recurrent state."""
    state = object()
    family = family_of(tiny())


def _refused(what):
    return pytest.raises(NotImplementedError,
                         match=f"{what}.*recurrent state")


def test_the_host_tier_refuses_recurrent_state():
    with _refused("host KV tier"):
        _engine(host_pages=8)


def test_spec_decode_refuses_recurrent_state():
    with _refused("spec_decode"):
        _engine(spec_decode=True)


def test_a_mesh_of_several_devices_refuses_recurrent_state():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with _refused("mesh"):
        JaxEngine(tiny(), EngineConfig(page_size=PS, num_pages=16),
                  mesh=mesh)


def test_disagg_prefill_refuses_recurrent_state():
    from dynamo_tpu.llm.disagg.prefill_worker import PrefillWorker

    with _refused("disaggregated prefill worker"):
        PrefillWorker(None, _Stateful())


def test_disagg_decode_refuses_recurrent_state():
    from dynamo_tpu.llm.disagg.decode import DisaggDecodeEngine

    with _refused("disaggregated decode engine"):
        DisaggDecodeEngine(_Stateful(), None, None, None, "d0")


def test_kv_transfer_refuses_recurrent_state():
    from dynamo_tpu.llm.disagg.transfer import KvTransferServer

    with _refused("KV transfer server"):
        KvTransferServer(_Stateful())
