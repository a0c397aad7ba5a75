"""Generation by diffusion over blocks (``model_type: sdar_moe``:
models/llama.py under a block mask, ``_make_block_window_fn``, the
engine's bookkeeping of a step that yields a block a row) against the
plain reference of ``benchmark/configs/sdar-30b-a3b-chat/reference.py``,
on seeded random float32 weights at tiny widths.

Tolerance: everything here runs in float32 on the CPU, the engine and the
reference differ in the order of their sums (paged gather against one
dense softmax, experts by einsum against one at a time), and measured
differences of log-probabilities are 1e-6 to 3e-6; ATOL = 1e-4 leaves two
orders of room and is three orders under what a wrong mask, a lost page
or a stale K/V row reads (0.05 and more, ``test_the_causal_mask_is_seen``).
"""

import asyncio
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.sampling import unmask
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import family_of, get_model_module
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b-chat")
ATOL = 1e-4
PS = 8
MASK = 511
STRATEGIES = ("sequential", "low_confidence_static",
              "low_confidence_dynamic")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny(**over) -> ModelConfig:
    hf = dict(model_type="sdar_moe", vocab_size=512, hidden_size=64,
              intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
              num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
              rms_norm_eps=1e-6, tie_word_embeddings=False,
              block_length=4, denoising_steps=4, mask_token_id=MASK,
              remasking_strategy="sequential")
    hf.update(over)
    cfg = ModelConfig.from_hf_config(hf)
    cfg.dtype = "float32"
    return cfg


def make_params(cfg, seed=0, sharp=1.0):
    """``sharp`` scales the head: at 40 a greedy token's probability
    passes 0.9 at most positions, which is what lets the dynamic
    strategy finish a block early."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    params["lm_head"] = params["lm_head"] * sharp
    return params


def ref_logits(params, cfg, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens))


def _engine(cfg=None, params=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=64, max_batch=4, prefill_chunk=16,
                batch_buckets=(4,), prefill_buckets=(16,),
                page_buckets=(16,), max_prefill_batch=2, decode_steps=8,
                warmup_logprobs=False)
    base.update(over)
    cfg = cfg or tiny()
    return JaxEngine(cfg, EngineConfig(**base),
                     params=params or make_params(cfg), seed=0)


def _req(prompt, n, logprobs=None, **stop):
    stop.setdefault("ignore_eos", True)
    return PreprocessedRequest(
        token_ids=[int(t) for t in prompt], sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, **stop),
        output=OutputOptions(logprobs=logprobs))


async def _collect(engine, req):
    toks, tops, finish = [], [], None
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        tops.extend(out.top_logprobs or [])
        if out.finish_reason is not None:
            finish = out.finish_reason
            break
    return toks, tops, finish


async def _gen(engine, prompt, n, logprobs=None, **stop):
    return await _collect(engine, _req(prompt, n, logprobs, **stop))


def _prompts(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 500, n).tolist() for n in lens]


def _max_diff(rows, tops):
    """Largest gap between the engine's top log-probabilities and the
    reference's rows (logits [n, V]) at the same positions."""
    want = np.asarray(jax.nn.log_softmax(jnp.asarray(rows), -1))
    assert len(tops) == len(rows)
    return max(abs(want[j][i] - v) for j, top in enumerate(tops)
               for i, v in top.items())


# --------------------------------------------------------- configuration


def test_from_hf_config_on_the_catalog_config():
    """The benchmark's config.json (the catalog row's config, cut to 6
    layers, plus the generation keys) loads as the Qwen3-MoE shape plus a
    block mask, and dispatches to models/llama.py."""
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    assert (cfg.model_type, cfg.qk_norm, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.intermediate_size) == (
        "sdar_moe", True, 128, 8, 768)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id,
            cfg.remasking_strategy, cfg.confidence_threshold) == (
        4, 4, 151669, "sequential", 0.9)
    assert get_model_module(cfg) is llama
    assert not cfg.has_recurrent_state


def test_generation_keys_default_to_the_familys():
    cfg = tiny()
    hf = dict(model_type="sdar_moe", vocab_size=512, hidden_size=64,
              intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=2, num_attention_heads=4,
              norm_topk_prob=True)
    got = ModelConfig.from_hf_config(hf)
    assert (got.block_length, got.denoising_steps, got.mask_token_id,
            got.remasking_strategy, got.confidence_threshold) == (
        4, 4, 151669, "low_confidence_dynamic", 0.9)
    assert cfg.remasking_strategy == "sequential"
    with pytest.raises(NotImplementedError, match="remasking_strategy"):
        ModelConfig.from_hf_config(dict(hf, remasking_strategy="random"))
    with pytest.raises(NotImplementedError, match="sdar_moe with "
                       "norm_topk_prob=false"):
        ModelConfig.from_hf_config(dict(hf, norm_topk_prob=False))


@pytest.mark.parametrize("strategy,n,want", [
    ("sequential", 1, [1, 0, 0, 0]), ("sequential", 2, [1, 1, 0, 0]),
    ("low_confidence_static", 1, [0, 0, 0, 1]),
    ("low_confidence_static", 2, [0, 1, 0, 1]),
    ("low_confidence_dynamic", 1, [0, 1, 0, 1]),    # two pass 0.9 >= n
    ("low_confidence_dynamic", 3, [1, 1, 0, 1]),    # too few: as static
])
def test_unmask_by_hand(strategy, n, want):
    """One row, positions 0, 1, 3 masked with confidences .1, .95, .97
    (position 2 is final and its .99 must not count)."""
    masked = jnp.asarray([[True, True, False, True]])
    conf = jnp.asarray([[0.1, 0.95, 0.99, 0.97]])
    got = unmask(strategy, masked, conf, jnp.asarray([n]), 0.9, False)
    assert np.asarray(got)[0].astype(int).tolist() == want
    last = unmask(strategy, masked, conf, jnp.asarray([n]), 0.9, True)
    assert np.asarray(last)[0].tolist() == [True, True, False, True]


def test_equal_confidences_go_by_position():
    masked = jnp.ones((1, 4), bool)
    conf = jnp.full((1, 4), 0.5)
    got = unmask("low_confidence_static", masked, conf, jnp.asarray([2]),
                 0.9, False)
    assert np.asarray(got)[0].tolist() == [True, True, False, False]


# ------------------------------------ (a) programs against the reference


class Pools:
    """One sequence's pages in a small pool, driven as the engine drives
    the two programs."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2)):
        self.cfg = cfg
        self.kv_k, self.kv_v = llama.init_kv_cache(cfg, KVCacheSpec(16, PS))
        self.pages = list(pages)
        self.prefill, _ = llama.make_step_fns(cfg)
        self.window = llama.make_decode_window_fn(cfg, True, 64)

    def table(self, rows, width=8):
        t = np.zeros((rows, width), np.int32)
        t[0, :len(self.pages)] = self.pages
        return jnp.asarray(t)

    def run_prefill(self, params, tokens, bucket=32):
        n = len(tokens)
        tok = np.zeros((2, bucket), np.int32)
        pos = np.full((2, bucket), -1, np.int32)
        slots = np.full((2, bucket), DROP_SLOT, np.int32)
        at = np.arange(n)
        tok[0, :n], pos[0, :n] = tokens, at
        slots[0, :n] = np.asarray(self.pages)[at // PS] * PS + at % PS
        _, self.kv_k, self.kv_v = self.prefill(
            params, jnp.asarray(tok), jnp.asarray(pos), self.kv_k,
            self.kv_v, self.table(2), jnp.asarray(slots),
            jnp.asarray([max(n - 1, 0), 0]), None)

    def run_window(self, params, tail, start, budget, k_steps=8, topn=0,
                   stop_ids=(), pending=()):
        """``pending``: the final tokens of the block before ``start``,
        where the pool does not hold its K/V yet."""
        L = self.cfg.block_length
        tok = np.full((2, 2 * L), -1, np.int32)
        tok[0, :len(pending)] = pending
        tok[0, L:L + len(tail)] = tail
        eos = np.full((2, 8), -1, np.int32)
        eos[0, :len(stop_ids)] = stop_ids
        out = self.window(
            params, jnp.asarray(tok), jnp.asarray([start, -1], jnp.int32),
            jnp.zeros(2, bool), jnp.zeros(2, jnp.int32),
            jnp.asarray([budget, 1], jnp.int32), self.kv_k, self.kv_v,
            self.table(2), jnp.zeros(2), jnp.zeros(2, jnp.int32),
            jnp.ones(2), jnp.zeros(2, jnp.uint32), jnp.asarray(eos), None,
            k_steps=k_steps, logprobs_topn=topn)
        *out, self.kv_k, self.kv_v, info = out
        return out, np.asarray(info)


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("n_prompt", [16, 17, 18, 19, 2])
def test_prefill_and_every_denoising_forward_match_reference(
        n_prompt, path, monkeypatch):
    """(a) block-causal prefill of the prompt's whole blocks, then two
    blocks through the window (the first opened by the prompt's tail):
    the FULL log-softmax row of the forward that made each position final
    against the reference's row for that position, teacher-forced on the
    window's own tokens; on the XLA arm and with both Pallas kernels in
    interpret mode (the prefill kernel with the block edge, the decode
    kernel at group G x L). Logits, not tokens."""
    if path == "kernels":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = tiny()
    params = make_params(cfg, 1)
    (prompt,) = _prompts(n_prompt, n_prompt)
    pools = Pools(cfg)
    L, V = cfg.block_length, cfg.vocab_size
    start = n_prompt // L * L
    if start:
        pools.run_prefill(params, prompt[:start])
    (toks, emitted, aux, carry), info = pools.run_window(
        params, prompt[start:], start, budget=100, topn=V)
    off = n_prompt - start
    n = int(emitted[0])
    assert n == 2 * L - off and int(carry[1][0]) == start + 2 * L
    new = [int(t) for t in np.asarray(toks)[0, off:off + n]]
    want = ref_logits(params, cfg, prompt + new[:-1])[n_prompt - 1:]
    want = np.asarray(jax.nn.log_softmax(jnp.asarray(want), -1))
    _, tv, ti = (np.asarray(a) for a in aux)
    for j in range(n):
        got = np.empty(V, np.float32)
        got[ti[0, off + j]] = tv[0, off + j]
        assert np.abs(got - want[j]).max() < ATOL, j
        assert new[j] == int(np.argmax(want[j]))
    # a live row's counts: 2 blocks, a denoising forward a new position
    # and no forward that only commits (the first block's K/V were made
    # final by the second's first forward, the second's wait in the
    # carry), nothing early, nothing dropped
    assert info[0].tolist() == [2, n, 1, 0, 0]
    assert info[1].tolist() == [0, 0, 0, 0, 0]
    assert np.asarray(carry[0])[0].tolist() == new[-L:] + [-1] * L


def test_the_window_commits_whole_blocks_only():
    """A budget that ends inside the second block: the row emits up to
    it, freezes, and commits the first block alone; the pool's rows of
    the second block keep the bytes they had."""
    cfg = tiny()
    params = make_params(cfg, 2)
    (prompt,) = _prompts(3, 16)
    pools = Pools(cfg)
    pools.run_prefill(params, prompt)
    before = np.asarray(pools.kv_k)
    (toks, emitted, carry), info = pools.run_window(params, [], 16, budget=6)
    assert int(emitted[0]) == 6 and bool(carry[2][0])
    assert int(carry[1][0]) == 20                 # one whole block
    assert info[0].tolist() == [2, 8, 1, 0, 2]   # two tokens dropped
    after = np.asarray(pools.kv_k)
    page = pools.pages[2]                         # positions 16 .. 23
    changed = np.abs(after[:, page] - before[:, page]).sum(axis=(0, 1, 3))
    assert (changed[:4] > 0).all() and (changed[4:] == 0).all()


def _one_block_forward(params, cfg, tokens, at, kv_k, kv_v, table):
    """One forward of ONE block of one row through ``llama.forward`` (the
    prefill's: block-causal over the pool, K/V written by rows): the
    hidden states [L, D] behind ``ln_final`` and the pools after it."""
    L = cfg.block_length
    pos = at + np.arange(L)
    slots = np.asarray(table)[0, pos // PS] * PS + pos % PS
    h, kv_k, kv_v = llama.forward(
        params, cfg, jnp.asarray([tokens], jnp.int32), jnp.asarray([pos]),
        kv_k, kv_v, table[:1], jnp.asarray([slots], jnp.int32),
        allow_pallas=False)
    return h[0], kv_k, kv_v


def _kv_rows(kv, pages, lo, hi):
    """A pool's rows [layers, hi - lo, KV, hd] of positions lo .. hi - 1
    of the sequence that holds ``pages``."""
    pos = np.arange(lo, hi)
    return np.asarray(kv)[:, np.asarray(pages)[pos // PS], :, pos % PS]


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("tail", [0, 2])
def test_a_two_block_forward_is_the_commit_and_the_first_denoising_forward(
        tail, path, monkeypatch):
    """The [B, 2L] forward that opens a block, against two forwards of
    one block each (``llama.forward`` on L tokens: the block before on
    its final tokens, then the open block on its tail and the mask id):
    the first half's K/V are the commit forward's, the second half's
    logits the first denoising forward's. ``denoising_steps`` 1 makes
    every masked position final in that first forward, so the window's
    top-V log-probabilities are its whole rows. Three rows at once: one
    with a pending block (prompt 0 .. 11 prefilled, 12 .. 15 pending),
    one with the same tokens and NO pending block (0 .. 15 prefilled:
    its pool rows must keep their bits and its logits be the same), and
    a padding row."""
    if path == "kernels":
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    cfg = tiny(denoising_steps=1)
    params = make_params(cfg, 21)
    L, V = cfg.block_length, cfg.vocab_size
    (prompt,) = _prompts(22, 16 + tail)
    rows = [Pools(cfg, pages=(3, 5, 7)), Pools(cfg, pages=(9, 11, 2))]
    rows[0].run_prefill(params, prompt[:12])
    rows[1].kv_k, rows[1].kv_v = rows[0].kv_k, rows[0].kv_v
    rows[1].run_prefill(params, prompt[:16])
    kv_k, kv_v = rows[1].kv_k, rows[1].kv_v
    table = np.zeros((3, 8), np.int32)
    table[0, :3], table[1, :3] = rows[0].pages, rows[1].pages
    tok = np.full((3, 2 * L), -1, np.int32)
    tok[0, :L] = prompt[12:16]
    tok[:2, L:L + tail] = prompt[16:]
    before = np.asarray(kv_k), np.asarray(kv_v)
    # the two forwards of one block, on row 0's pages in a copy
    _, ck, cv = _one_block_forward(params, cfg, prompt[12:16], 12,
                                   jnp.array(kv_k), jnp.array(kv_v),
                                   jnp.asarray(table))
    h, _, _ = _one_block_forward(
        params, cfg, prompt[16:] + [MASK] * (L - tail), 16, ck, cv,
        jnp.asarray(table))
    want = np.asarray(jax.nn.log_softmax(
        llama.project_logits(params, cfg, h), -1))
    ck, cv = np.asarray(ck), np.asarray(cv)
    toks, emitted, aux, carry, kv_k, kv_v, info = rows[0].window(
        params, jnp.asarray(tok), jnp.asarray([16, 16, -1], jnp.int32),
        jnp.zeros(3, bool), jnp.zeros(3, jnp.int32),
        jnp.asarray([100, 100, 1], jnp.int32), kv_k, kv_v,
        jnp.asarray(table), jnp.zeros(3), jnp.zeros(3, jnp.int32),
        jnp.ones(3), jnp.zeros(3, jnp.uint32),
        jnp.full((3, 8), -1, jnp.int32), None, k_steps=L, logprobs_topn=V)
    _, tv, ti = (np.asarray(a) for a in aux)
    for i in range(2):
        for j in range(tail, L):
            got = np.empty(V, np.float32)
            got[ti[i, j]] = tv[i, j]
            assert np.abs(got - want[j]).max() < ATOL, (i, j)
    assert np.asarray(info).tolist() == [[1, 1, 1, 0, 0], [1, 1, 0, 0, 0],
                                         [0] * 5]
    assert np.asarray(emitted).tolist() == [L - tail, L - tail, 0]
    after = np.asarray(kv_k), np.asarray(kv_v)
    for was, now, commit in zip(before, after, (ck, cv)):
        # row 0: its pending block's rows are the commit forward's
        assert np.abs(_kv_rows(now, rows[0].pages, 12, 16)
                      - _kv_rows(commit, rows[0].pages, 12, 16)).max() < 1e-5
        assert np.abs(_kv_rows(now, rows[0].pages, 12, 16)
                      - _kv_rows(now, rows[1].pages, 12, 16)).max() < 1e-5
        # and nothing else of the pool moved: not row 1's pages (no
        # pending block: its blocks are never run or written again), not
        # the open block's (its K/V wait in the carry for the next window)
        now = now.copy()
        page = rows[0].pages[1]
        now[:, page, :, 4:] = was[:, page, :, 4:]
        assert (now == was).all()
    assert np.asarray(carry[0])[:, :L].tolist()[2] == [-1] * L


# --------------------------------- (b) the engine against reference_generate


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_generate_equals_reference_generate(strategy, run_async):
    """(b) ``engine.generate`` end to end (prefill of whole blocks, the
    tail opening the first block, pipelined windows of two blocks, the
    cut at max_tokens inside a block) against ``reference_generate``'s
    plain loop, for prompts with tails 0 to 3 and one shorter than a
    block: the same tokens, the same log-probabilities from the forward
    that made each position final, and the reference's counts of
    forwards."""
    cfg = tiny(remasking_strategy=strategy)
    params = make_params(cfg, 3)
    eng = _engine(cfg, params)
    prompts = _prompts(4, 12, 13, 14, 15, 3)

    async def main():
        outs = [await _gen(eng, p, 9, logprobs=5) for p in prompts]
        stats = eng.stats()
        await eng.stop()
        return outs, stats

    outs, stats = run_async(main())
    total = dict.fromkeys(("blocks", "denoise_forwards", "commit_forwards",
                           "early_exits", "dropped_tokens"), 0)
    for p, (toks, tops, finish) in zip(prompts, outs):
        want, rows, counts = REF.reference_generate(params, cfg, p, 9)
        assert toks == want and finish == "length"
        assert _max_diff(rows, tops) < ATOL
        for k in total:
            total[k] += counts[k]
    assert stats["diffusion_blocks_total"] == total["blocks"]
    # the reference commits every block by a forward of its own; here
    # a block's K/V are made final by the next block's first forward,
    # and a row's last block (cut, or pending when it finishes) by none
    assert stats["diffusion_forwards_total"] == total["denoise_forwards"]
    assert stats["diffusion_commit_forwards_total"] == 0
    assert stats["diffusion_folded_commits_total"] == (
        total["commit_forwards"] - len(prompts))
    assert stats["diffusion_dropped_tokens_total"] == total[
        "dropped_tokens"]
    assert stats["diffusion_early_exits_total"] == total["early_exits"]
    assert stats["diffusion_tokens_total"] == 9 * len(prompts)
    assert eng.decode_tokens_total == 9 * len(prompts)


def test_a_window_of_64_rows_runs_both_kinds_of_forward_sorted(
        run_async, monkeypatch):
    """A B 64 window: the two-block forward has 512 token rows and the
    one-block forward 64 * L = 256, which reach the chip's ridge too
    (``llama._MOE_RIDGE_ROWS``), so both run their experts through the
    sorted dispatch, here as the grouped kernel under the Pallas
    interpreter, with four live rows of 64. Tokens and the
    log-probabilities of the forward that made each position final
    (sequential: position 0 of a block by the two-block forward, 1 .. 3
    by one-block forwards) against ``reference_generate``, and against
    the same engine with the dense form forced on every forward. The
    engine counts every row's forward as one through the kernel at B 64
    and none where the form is forced (B 4, 32 / 16 rows:
    ``test_warmup_covers_the_serving_forms``)."""
    monkeypatch.setattr(llama, "_moe_kernel_interpret", lambda w: True)
    cfg = tiny()
    params = make_params(cfg, 5)
    prompts = _prompts(6, 12, 13, 15, 3)
    assert llama._moe_use_blocked(None, 64 * cfg.block_length, 8, 2)

    def run():
        eng = _engine(cfg, params, max_batch=64, batch_buckets=(64,))

        async def main():
            outs = await asyncio.gather(
                *(_gen(eng, p, 9, logprobs=5) for p in prompts))
            stats = eng.stats()
            await eng.stop()
            return outs, stats

        return run_async(main())

    outs, stats = run()
    for p, (toks, tops, finish) in zip(prompts, outs):
        want, rows, _ = REF.reference_generate(params, cfg, p, 9)
        assert toks == want and finish == "length"
        assert _max_diff(rows, tops) < ATOL
    assert stats["diffusion_forwards_total"] > stats[
        "diffusion_blocks_total"] > 0
    assert stats["moe_grouped_window_forwards_total"] == stats[
        "diffusion_forwards_total"]
    monkeypatch.setattr(llama, "_moe_use_blocked", lambda *a: False)
    dense, forced = run()
    assert forced["moe_grouped_window_forwards_total"] == 0
    for (toks, tops, _), (dtoks, dtops, _) in zip(outs, dense):
        assert toks == dtoks
        assert max(abs(top[i] - dtop[i]) for top, dtop in zip(tops, dtops)
                   for i in top) < ATOL


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_a_prompt_arriving_mid_decode_equals_reference_generate(strategy):
    """The scheduler's window behind a prefill (JaxEngine._step_window,
    rule 2) for a model that generates by blocks: a second prompt arrives
    while the first row is between two windows, the window shipped behind
    its prefill gives the first row a fresh block from the in-flight
    window's carry (with the block before it pending: the merge takes
    both from the carry, and the new row's from the host with no pending
    block), and both rows answer as the plain loop does. The engine is
    stepped by hand, so the arrival falls where it is meant."""
    from dynamo_tpu.engine.jax_engine import Sequence
    cfg = tiny(remasking_strategy=strategy)
    params = make_params(cfg, 3)
    eng = _engine(cfg, params)
    first_p, second_p = _prompts(9, 14, 13)

    def submit(prompt, n):
        req = _req(prompt, n)
        seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                       tokens=list(req.token_ids),
                       num_prompt=len(req.token_ids), block=eng.block)
        eng.waiting.append(seq)
        return seq

    def step_until(cond):
        for _ in range(64):
            if cond():
                return
            eng._step()
            eng._reap()
        raise AssertionError("condition not reached")

    first = submit(first_p, 40)
    step_until(lambda: first.generated >= 8)
    assert eng.prefill_window_topups_total == 0
    second = submit(second_p, 9)
    step_until(lambda: eng.prefill_window_topups_total == 1)
    assert eng._pending.batch == [first], "the window behind the prefill"
    step_until(lambda: first.finished and second.finished)
    for seq, prompt, n in ((first, first_p, 40), (second, second_p, 9)):
        want, _, _ = REF.reference_generate(params, cfg, prompt, n)
        assert seq.tokens[seq.num_prompt:] == want
    assert eng.prefill_window_topups_total == 1


def test_the_agreement_check_form_is_exact_under_sequential(run_async):
    """What benchmark/harness/serve.py agree does: the reference is handed
    ``prompt + toks[:-1]`` and row ``len(prompt) - 1 + j`` is compared
    with the engine's j-th token, the ninth the first of a third block
    whose other positions the engine generated and never emitted."""
    eng = _engine()
    (p,) = _prompts(9, 24)

    async def main():
        out = await _gen(eng, p, 9, logprobs=5)
        await eng.stop()
        return out

    toks, tops, _ = run_async(main())
    rows = ref_logits(eng.params, eng.cfg, p + toks[:-1])[len(p) - 1:]
    assert len(rows) == 9 and _max_diff(rows, tops) < ATOL


def test_the_causal_mask_is_seen(run_async):
    """The control of (a) and (b): the same engine with the CAUSAL mask
    in its prefill (the program built from the configuration with
    block_length 1) disagrees with the reference by far more than ATOL,
    and generates other tokens."""
    cfg = tiny()
    params = make_params(cfg, 3)
    eng = _engine(cfg, params)
    causal = dataclasses.replace(cfg, block_length=1)
    eng.prefill_fn, _ = llama.make_step_fns(causal)
    (p,) = _prompts(4, 12)

    async def main():
        out = await _gen(eng, p, 9, logprobs=5)
        await eng.stop()
        return out

    toks, tops, _ = run_async(main())
    want, rows, _ = REF.reference_generate(params, cfg, p, 9)
    rows = ref_logits(params, cfg, p + toks[:-1])[len(p) - 1:]
    assert _max_diff(rows, tops) > 500 * ATOL


# ------------------------------- (c) stops inside a block, what is committed


def test_max_tokens_eos_and_a_stop_id_inside_a_block(run_async):
    """(c) a 16-token prompt and an uncut run of 12 tokens t0..t11. Cut
    by max_tokens 6, by an EOS id equal to t5, by a stop id equal to t5:
    the client gets t0..t5 each time (the stop id included, as a row of
    one token a step gets it), with ``length`` / ``eos``; tokens past it
    are dropped and counted. Then the same prompt + t0..t5 + more is sent:
    it may hit only pages whose every block was final, here the two pages
    of the prompt (16 tokens), never the page the cut block lies in."""
    cfg = tiny()
    params = make_params(cfg, 5)
    (p,) = _prompts(6, 16)
    eng = _engine(cfg, params)

    async def run():
        full, _, _ = await _gen(eng, p, 12)
        by_len = await _gen(eng, p, 6)
        req = _req(p, 12, ignore_eos=False)
        req.eos_token_ids = [full[5]]
        by_eos = await _collect(eng, req)
        by_stop = await _gen(eng, p, 12, stop_token_ids=[full[5]])
        dropped = eng.stats()["diffusion_dropped_tokens_total"]
        hits0 = eng.prefix_hit_tokens_total
        after, _, _ = await _gen(eng, p + full[:6] + [7, 8, 9], 4)
        hit = eng.prefix_hit_tokens_total - hits0
        await eng.stop()
        return full, by_len, by_eos, by_stop, dropped, hit, after

    full, by_len, by_eos, by_stop, dropped, hit, after = run_async(run())
    assert full[5] not in full[:5], "the draw must not stop earlier"
    assert by_len[0] == full[:6] and by_len[2] == "length"
    assert by_eos[0] == full[:6] and by_eos[2] == "eos"
    assert by_stop[0] == full[:6] and by_stop[2] == "eos"
    # each cut run generated its second block whole and dropped t6, t7
    assert dropped == 3 * 2
    assert hit == 16
    want, _, _ = REF.reference_generate(params, cfg,
                                        p + full[:6] + [7, 8, 9], 4)
    assert after == want


def test_a_stop_list_wider_than_the_device_table(run_async):
    """The host's token-by-token path (more stop ids than max_eos_ids):
    the device cannot see the stop and runs on, the host cuts at it."""
    cfg = tiny()
    params = make_params(cfg, 5)
    (p,) = _prompts(6, 16)
    eng = _engine(cfg, params, max_eos_ids=2)

    async def run():
        full, _, _ = await _gen(eng, p, 12)
        cut = await _gen(eng, p, 12,
                         stop_token_ids=[600, 601, 602, full[5]])
        await eng.stop()
        return full, cut

    full, cut = run_async(run())
    assert cut[0] == full[:6] and cut[2] == "eos"


# ----------------------------------------------------- (d) the prefix cache


def test_a_prefix_hit_gives_the_logits_of_a_cold_run(run_async):
    """(d) a second request that shares three pages (24 tokens = six
    whole blocks) with the first takes them as a hit and answers with the
    log-probabilities of the reference, as a cold engine does: a page's
    K/V is a function of the tokens up to the page's end because the
    block length divides the page."""
    cfg = tiny()
    params = make_params(cfg, 7)
    shared, = _prompts(8, 26)
    a, b = shared + [11, 12, 13], shared[:25] + [21, 22, 23, 24, 25]
    eng = _engine(cfg, params)

    async def run():
        await _gen(eng, a, 8)
        hits0 = eng.prefix_hit_tokens_total
        warm = await _gen(eng, b, 9, logprobs=5)
        hit = eng.prefix_hit_tokens_total - hits0
        await eng.stop()
        cold_eng = _engine(cfg, params)
        cold = await _gen(cold_eng, b, 9, logprobs=5)
        await cold_eng.stop()
        return warm, cold, hit

    warm, cold, hit = run_async(run())
    assert hit == 24
    assert warm[0] == cold[0]
    want, rows, _ = REF.reference_generate(params, cfg, b, 9)
    assert warm[0] == want
    assert _max_diff(rows, warm[1]) < ATOL


@pytest.mark.parametrize("n,pages", [(9, 2), (8, 2), (4, 1)])
def test_pages_a_window_fills_are_published_by_whole_blocks(n, pages,
                                                            run_async):
    """A row that generates across a page's end publishes that page once
    the K/V of the block that ends it are final IN THE POOL, and a later
    request hits it: prompt 12 + 9 generated = 21 tokens, two full pages,
    of which the second was filled by a window. That is one block later
    than the tokens are read back: the K/V of block 12 .. 15 are made
    final by the first forward of block 16 .. 19, not by a forward of
    their own. With 8 tokens that forward ran (the page is published,
    the row's last block 16 .. 19 is not committed and nobody reads it);
    with 4 the row finishes on the page's last token, no later block
    ever opens, the block stays pending and the page it completes is
    never handed out: 8 tokens hit where the parent of PR 62 hit 16."""
    cfg = tiny()
    params = make_params(cfg, 7)
    (p,) = _prompts(12, 12)
    eng = _engine(cfg, params)

    async def run():
        toks, _, _ = await _gen(eng, p, n)
        hits0 = eng.prefix_hit_tokens_total
        again = await _gen(eng, p + toks + [5, 6], 5, logprobs=5)
        hit = eng.prefix_hit_tokens_total - hits0
        await eng.stop()
        return toks, again, hit

    toks, again, hit = run_async(run())
    assert hit == pages * PS
    want, rows, _ = REF.reference_generate(params, cfg, p + toks + [5, 6], 5)
    assert again[0] == want and _max_diff(rows, again[1]) < ATOL


def test_a_published_page_holds_final_blocks_and_a_hit_page_keeps_its_bits(
        run_async):
    """Every page ``_publish`` hands to the prefix cache is read from
    the pool as it is handed over (windows later in the queue included)
    and set against the K of a block-causal prefill of the same tokens,
    which is what final K/V are: a block whose K came from a denoising
    forward (some positions still masked) differs by far more than the
    float32 noise. Then a second request hits the published pages and
    runs its windows on them: a window writes whole pages back, and never
    one it shares, so the hit pages keep their bits."""
    cfg = tiny()
    params = make_params(cfg, 7)
    a, = _prompts(30, 13)
    eng = _engine(cfg, params)
    handed = []
    commit_chain = eng.pm.commit_chain

    def spy(pages, tokens, extent, **kw):
        if extent > 16:     # past what the prefill published
            handed.append((list(pages), list(tokens[:extent]),
                           np.asarray(eng.kv_k)))
        return commit_chain(pages, tokens, extent, **kw)

    eng.pm.commit_chain = spy

    async def run():
        toks, _, _ = await _gen(eng, a, 28)
        b = a + toks[:20] + [1, 2]
        hits0 = eng.prefix_hit_tokens_total
        eng.pm.commit_chain = commit_chain
        shared = None

        async def second():
            nonlocal shared
            async for out in eng.generate(_req(b, 12), Context()):
                if shared is None:
                    seq = next(s for s in eng.running + eng.prefilling
                               if s.num_prompt == len(b))
                    shared = (seq.pages[:seq.prefix_hit // PS],
                              np.asarray(eng.kv_k), np.asarray(eng.kv_v))
                if out.finish_reason is not None:
                    break

        await second()
        hit = eng.prefix_hit_tokens_total - hits0
        pools = np.asarray(eng.kv_k), np.asarray(eng.kv_v)
        await eng.stop()
        return hit, shared, pools

    hit, shared, pools = run_async(run())
    assert handed and max(len(t) for _, t, _ in handed) >= 32
    for pages, tokens, kv_k in handed:
        fresh = Pools(cfg, pages=pages)
        fresh.run_prefill(params, tokens, bucket=64)
        got = _kv_rows(kv_k, pages, 0, len(tokens))
        want = _kv_rows(fresh.kv_k, pages, 0, len(tokens))
        assert np.abs(got - want).max() < 1e-5, len(tokens)
    assert hit == 32 and len(shared[0]) == 4
    for was, now in zip(shared[1:], pools):
        assert (was[:, shared[0]] == now[:, shared[0]]).all()


def test_a_block_that_straddles_a_page_is_refused():
    with pytest.raises(ValueError, match=r"block_length \(3\) must divide "
                       r"page_size \(8\).*prefix hit"):
        _engine(tiny(block_length=3, denoising_steps=3))
    with pytest.raises(ValueError, match=r"decode_steps \(6\) must be a "
                       r"multiple of block_length \(4\)"):
        _engine(decode_steps=6)
    with pytest.raises(ValueError, match="decode_steps"):
        _engine(decode_steps=1)


# ------------------------------------------------ (e) preemption and resume


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_preempt_and_resume_equals_an_uninterrupted_run(strategy, run_async):
    """(e) a pool too small for four rows preempts some; a preempted row
    gives up its pages, comes back, prefills the whole blocks of prompt +
    what it had generated (its last block among them: none is pending
    any more), opens its next block with the tail, and answers as it
    does alone. The rows that stay are seeded from the host after the
    flush, each with its pending block."""
    cfg = tiny(remasking_strategy=strategy)
    params = make_params(cfg, 6)
    prompts = _prompts(6, 14, 15, 16, 13)
    eng = _engine(cfg, params, num_pages=14, watermark_pages=1,
                  prefill_buckets=(16, 32), prefill_chunk=32)
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s): s for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)

    eng._grow_or_preempt = spy

    async def main():
        alone_eng = _engine(cfg, params)
        alone = [(await _gen(alone_eng, p, 18))[0] for p in prompts]
        await alone_eng.stop()
        together = await asyncio.wait_for(asyncio.gather(*(
            _gen(eng, p, 18) for p in prompts)), 300)
        await eng.stop()
        return alone, [t for t, _, _ in together]

    alone, together = run_async(main())
    assert preempted, "the pool was meant to run out"
    assert together == alone


# ----------------------------------------- (f) the dynamic strategy's exits


def test_a_sharp_head_lets_the_dynamic_strategy_finish_early(run_async):
    """(f) with the head scaled 40x most greedy tokens pass the 0.9
    threshold, so blocks finish in two or three forwards instead of
    five; tokens, log-probabilities and every count agree with the
    reference's loop, and ``early_exits`` is no longer zero."""
    cfg = tiny(remasking_strategy="low_confidence_dynamic")
    params = make_params(cfg, 11, sharp=40.0)
    eng = _engine(cfg, params)
    prompts = _prompts(12, 16, 17)

    async def main():
        outs = [await _gen(eng, p, 16, logprobs=5) for p in prompts]
        stats = eng.stats()
        await eng.stop()
        return outs, stats

    outs, stats = run_async(main())
    fwd = commit = early = blocks = 0
    for p, (toks, tops, _) in zip(prompts, outs):
        want, rows, counts = REF.reference_generate(params, cfg, p, 16)
        assert toks == want
        # a sharp head makes log-probabilities large: relative room
        assert _max_diff(rows, tops) < 40 * ATOL
        fwd += counts["denoise_forwards"]
        commit += counts["commit_forwards"]
        early += counts["early_exits"]
        blocks += counts["blocks"]
    assert early > 0 and fwd < 4 * blocks
    assert stats["diffusion_early_exits_total"] == early
    assert stats["diffusion_forwards_total"] == fwd
    assert stats["diffusion_folded_commits_total"] == commit - len(prompts)
    assert stats["diffusion_blocks_total"] == blocks


# --------------------------------- (g) a prompt that contains the mask id


def test_a_prompt_may_contain_the_mask_token(run_async):
    """(g) masked-ness is a flag a position: a prompt whose whole blocks
    AND whose tail hold ``mask_token_id`` is served as any other."""
    cfg = tiny()
    params = make_params(cfg, 13)
    (p,) = _prompts(14, 14)
    p[3] = p[9] = p[12] = p[13] = MASK
    eng = _engine(cfg, params)

    async def main():
        out = await _gen(eng, p, 9, logprobs=5)
        await eng.stop()
        return out

    toks, tops, _ = run_async(main())
    want, rows, _ = REF.reference_generate(params, cfg, p, 9)
    assert toks == want and _max_diff(rows, tops) < ATOL


# ------------------------------------------------------------ (i) refusals


def _refused(what):
    return pytest.raises(
        NotImplementedError,
        match=f"{what}.*generates by diffusion over blocks.*"
              "_make_block_window_fn.*yields a block a row")


class _Blocks:
    """Stands for an engine that serves a model generating by blocks."""
    state = None
    block = 4
    family = family_of(tiny())


@pytest.mark.parametrize("what,build", [
    ("host KV tier", lambda: _engine(host_pages=8)),
    ("spec_decode", lambda: _engine(spec_decode=True)),
    ("long_prefill_threshold", lambda: _engine(long_prefill_threshold=64)),
    ("mesh", lambda: JaxEngine(
        tiny(), EngineConfig(page_size=PS, num_pages=16, decode_steps=8),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                               ("data", "model")))),
    ("disaggregated prefill worker",
     lambda: __import__("dynamo_tpu.llm.disagg.prefill_worker",
                        fromlist=["PrefillWorker"]).PrefillWorker(
                            None, _Blocks())),
    ("disaggregated decode engine",
     lambda: __import__("dynamo_tpu.llm.disagg.decode",
                        fromlist=["DisaggDecodeEngine"]).DisaggDecodeEngine(
                            _Blocks(), None, None, None, 0)),
    ("KV transfer server",
     lambda: __import__("dynamo_tpu.llm.disagg.transfer",
                        fromlist=["KvTransferServer"]).KvTransferServer(
                            _Blocks())),
])
def test_what_refuses_a_model_that_generates_by_blocks(what, build):
    """(i) each path that assumes a step of one token a row refuses at
    construction, and says why."""
    if what == "mesh" and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    with _refused(what):
        build()


def test_the_sharded_prefill_kernel_refuses_the_block_mask(monkeypatch):
    """(i) the engine refuses a mesh at construction; ``forward(...,
    mesh=)`` called past it must not fall back to the causal mask in
    silence: the sharded kernel arm has no block edge and says so."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                             ("data", "model"))
    q = jnp.zeros((1, 8, 4, 16))
    pool = jnp.zeros((4, 2, PS, 16))
    with pytest.raises(NotImplementedError,
                       match="no block mask.*diffusion over blocks"):
        llama._attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                         jnp.arange(8)[None], 0.25, mesh=mesh, block=4)


def test_prefill_of_a_block_model_has_no_head():
    """Its prefill yields no token (the logits at a prompt's last
    position are of that position's own token), so the program returns
    no logits and its lowered text holds no vocabulary-wide product."""
    cfg = tiny()
    params = make_params(cfg)
    pools = Pools(cfg)
    i32 = jnp.zeros((2, 32), jnp.int32)
    args = (params, i32, i32 - 1, pools.kv_k, pools.kv_v, pools.table(2),
            i32, jnp.zeros(2, jnp.int32), None)
    assert f"x{cfg.vocab_size}x" not in pools.prefill.lower(*args).as_text()
    logits, _, _ = pools.prefill(*args)
    assert logits is None
    causal, _ = llama.make_step_fns(dataclasses.replace(cfg, block_length=1))
    assert f"x{cfg.vocab_size}x" in causal.lower(*args).as_text()


@pytest.mark.parametrize("sampling", [
    dict(repetition_penalty=1.2), dict(frequency_penalty=0.5),
    dict(presence_penalty=0.5), dict(logit_bias={3: 2.0})])
def test_a_penalty_is_refused_by_the_request(sampling, run_async):
    """(i) a request whose sampling needs a per-token history inside a
    block ends at once with an error that says why; the engine goes on
    serving."""
    eng = _engine()
    (p,) = _prompts(15, 12)

    async def main():
        req = _req(p, 4)
        req.sampling = SamplingOptions(**sampling)
        outs = [o async for o in eng.generate(req, Context())]
        ok = await _gen(eng, p, 4)
        await eng.stop()
        return outs, ok

    outs, ok = run_async(main())
    assert len(outs) == 1 and outs[0].finish_reason == "error"
    assert "sampling penalty or logit_bias" in outs[0].text
    assert "generates by diffusion over blocks" in outs[0].text
    assert len(ok[0]) == 4


# ------------------------------------------------------ warm-up and sampling


@pytest.mark.parametrize("buckets,n", [((4,), 3), ((4, 64), 6)],
                         ids=["dense", "sorted_at_64"])
def test_warmup_covers_the_serving_forms(run_async, monkeypatch, buckets,
                                         n):
    """No compile after warm-up: the block window from a host-seeded
    carry and from the previous window's, the merge of the two, the
    prefill without a sampling program. The window is compiled ONCE a
    batch bucket, with the form of the experts it serves: at B 64 both
    kinds of forward take the sorted dispatch (the grouped kernel under
    the interpreter here), at B 4 neither."""
    monkeypatch.setenv("DYN_JIT_FENCE", "raise")
    monkeypatch.setattr(llama, "_moe_kernel_interpret", lambda w: True)
    eng = _engine(max_batch=buckets[-1], batch_buckets=buckets)
    prompts = _prompts(16, 12, 14, 19, 13, 12, 15)[:n]

    async def main():
        eng.warmup()
        outs = await asyncio.gather(*(_gen(eng, p, 20) for p in prompts))
        stats = eng.stats()
        await eng.stop()
        return outs, stats

    outs, stats = run_async(main())
    assert all(len(t) == 20 for t, _, _ in outs)
    assert stats["post_warmup_compiles_total"] == 0
    assert stats["first_tokens_total"] == n
    assert eng.decode_multi_fn.__wrapped__._cache_size() == len(buckets)
    assert (stats["moe_grouped_window_forwards_total"] > 0) == (n > 4)


def test_sampled_rows_draw_by_position(run_async):
    """A seeded temperature row draws the same tokens whether it runs
    alone or beside others (the RNG step of a draw is its absolute
    position), and differs from greedy."""
    eng = _engine()
    (p, q) = _prompts(17, 13, 16)

    async def one(prompt, n, seed=None):
        req = _req(prompt, n)
        if seed is not None:
            req.sampling = SamplingOptions(temperature=1.5, seed=seed)
        return (await _collect(eng, req))[0]

    async def main():
        alone = await one(p, 12, 7)
        both = await asyncio.gather(one(p, 12, 7), one(q, 12))
        greedy = await one(p, 12)
        await eng.stop()
        return alone, both[0], greedy

    alone, beside, greedy = run_async(main())
    assert alone == beside and alone != greedy
