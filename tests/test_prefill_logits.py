"""A prefill program computes its head where a row's logits are wanted
(``last_idx`` >= 0) and not otherwise (``llama.prefill_logits``; for
models/phi4flash.py the stack's cross half with it), since PR 64.

(i) Every module that builds a ``prefill_step``, on the operands a tiny
engine really dispatches (three chunks of one prompt: a fresh row, a
carried one, the one that ends the prompt): with a wanted row the
logits, the pools and the state are bit for bit those of the form before
(``logits_at`` on the same hidden states); with every entry -1 the pools
and the state are those again and the logits are zeros.

(ii) The engine: which rows it asks for, what it counts, and that the
tokens are those of the prompt in one chunk.
"""

import asyncio
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_mla import tiny_mla

from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import jamba, lfm2, llama, mla, phi4flash
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context

CHUNK = 16
LAST_IDX = 6        # its place among prefill_step's operands, past params

# the modules that build a prefill_step, each as the tiny engine of its
# own tests (tests/test_<name>.py ``_engine``): llama.py flat and by
# kind (cohere2_moe.py builds on the latter), mla.py, jamba.py with its
# own blocks and under a module that hands it its own (granite.py, as
# nemotron_h.py, kimi_linear.py and solar_open2.py do), lfm2.py,
# phi4flash.py
MODULES = {"llama": "llama", "smallthinker": "llama", "mla": "mla",
           "jamba": "jamba", "granite": "granite", "lfm2": "lfm2",
           "phi4flash": "phi4flash"}


def _engine(cfg=None, **over) -> JaxEngine:
    ecfg = dict(page_size=8, num_pages=64, max_batch=4,
                prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
                batch_buckets=(4,), page_buckets=(16,), decode_steps=4,
                max_prefill_batch=2, warmup_logprobs=False)
    ecfg.update(over)
    return JaxEngine(cfg or ModelConfig.tiny(), EngineConfig(**ecfg), seed=0)


def _engine_of(name: str) -> JaxEngine:
    if name == "llama":
        return _engine()
    if name == "mla":
        return _engine(tiny_mla())
    return importlib.import_module("test_" + name)._engine()


def _req(prompt, n):
    return PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True))


async def _gen(engine, prompt, n):
    toks = []
    async for out in engine.generate(_req(prompt, n), Context()):
        toks.extend(out.token_ids)
        if out.finish_reason:
            break
    return toks


def _copy(tree):
    return jax.tree.map(lambda a: jnp.array(a, copy=True), tree)


class _Recorded:
    """``engine.prefill_fn`` with every call's operands kept (copies:
    the pools are donated)."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, params, *rest):
        self.calls.append(_copy(rest))
        return self.fn(params, *rest)


def _form_before(eng: JaxEngine, patch):
    """The module's prefill_step as it was: ``logits_at`` on the hidden
    states of every chunk (a name is looked up when the program is
    traced, at its first call: ``patch`` has to last until then)."""
    cfg, module = eng.cfg, eng.family.module
    if module is not phi4flash:
        for builder in (llama, mla, jamba, lfm2):
            patch.setattr(builder, "prefill_logits", llama.logits_at)
        return module.make_step_fns(cfg)[0]

    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None, state=None,
                     state_slots=None):
        h, kv_k, kv_v, state = phi4flash.forward(
            params, cfg, tokens, positions, kv_k, kv_v, page_table,
            flat_slots, last_idx, state, state_slots, page_slots=page_slots)
        return (llama.logits_at(params, cfg, h, jnp.zeros_like(last_idx)),
                kv_k, kv_v, state)

    return jax.jit(prefill_step)


def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("name", MODULES)
def test_a_prefill_step_computes_its_head_where_it_is_wanted(
        name, run_async, monkeypatch):
    eng = _engine_of(name)
    assert eng.family.module.__name__.endswith("." + MODULES[name])
    rec = eng.prefill_fn = _Recorded(eng.prefill_fn)
    chunk = eng.ecfg.prefill_chunk
    prompt = [int(t) for t in
              np.random.default_rng(64).integers(1, 500, 3 * chunk - 3)]

    async def main():
        toks = await _gen(eng, prompt, 1)
        await eng.stop()
        return toks

    assert len(run_async(main())) == 1
    asked = [np.asarray(c[LAST_IDX]).tolist() for c in rec.calls]
    assert [a[0] for a in asked] == [-1, -1, chunk - 4]
    assert all(i == -1 for a in asked for i in a[1:])
    ends = [int((np.asarray(c[1][0]) >= 0).sum()) - 1 for c in rec.calls]

    def run(fn, call, idx):
        args = list(_copy(call))
        args[LAST_IDX] = args[LAST_IDX].at[0].set(idx)
        logits, *kept = fn(eng.params, *args)
        return np.asarray(logits), kept

    with monkeypatch.context() as patch:
        fn = _form_before(eng, patch)
        before = [run(fn, call, end) for call, end in zip(rec.calls, ends)]
        # the form before computed a head whatever it was asked
        assert run(fn, rec.calls[0], -1)[0].any()
    now = eng.family.module.make_step_fns(eng.cfg)[0]
    for call, end, (logits0, kept0) in zip(rec.calls, ends, before):
        logits1, kept1 = run(now, call, end)
        logits2, kept2 = run(now, call, -1)
        assert np.abs(logits0[0]).max() > 0
        # the wanted row bit for bit; the other row's are read by nobody
        np.testing.assert_array_equal(logits1[0], logits0[0])
        assert logits2.dtype == logits0.dtype and logits2.shape \
            == logits0.shape and not logits2.any()
        _same(kept1, kept0)
        _same(kept2, kept0)


# ------------------------------------------------------------ (ii) engine


def _asked(rec: _Recorded):
    """Per recorded dispatch, the ``last_idx`` of its live rows."""
    out = []
    for call in rec.calls:
        live = (np.asarray(call[1]) >= 0).any(axis=1)
        out.append(np.asarray(call[LAST_IDX])[live].tolist())
    return out


def _run(eng: JaxEngine, run_async, *prompts, n=6):
    async def main():
        out = await asyncio.wait_for(asyncio.gather(
            *(_gen(eng, p, n) for p in prompts)), 120)
        stats = eng.stats()
        await eng.stop()
        return out, stats

    return run_async(main())


def test_a_prompt_in_three_chunks_asks_once_and_yields_the_same_tokens(
        run_async):
    prompt = [int(t) for t in
              np.random.default_rng(1).integers(1, 500, 3 * CHUNK - 3)]
    eng = _engine()
    rec = eng.prefill_fn = _Recorded(eng.prefill_fn)
    (toks,), stats = _run(eng, run_async, prompt)
    assert _asked(rec) == [[-1], [-1], [CHUNK - 4]]
    assert stats["prefill_dispatches_total"] == 3
    assert stats["prefill_logits_skipped_total"] == 2
    (whole,), stats = _run(_engine(prefill_chunk=4 * CHUNK,
                                   prefill_buckets=(4 * CHUNK,)),
                           run_async, prompt)
    assert stats["prefill_dispatches_total"] == 1
    assert stats["prefill_logits_skipped_total"] == 0
    assert len(toks) == 6 and toks == whole


def test_a_program_with_an_ending_row_and_a_row_mid_prompt_samples_the_one(
        run_async):
    rng = np.random.default_rng(2)
    short = [int(t) for t in rng.integers(1, 500, CHUNK - 6)]
    long = [int(t) for t in rng.integers(1, 500, 3 * CHUNK - 3)]
    eng = _engine()
    rec = eng.prefill_fn = _Recorded(eng.prefill_fn)
    (a, b), stats = _run(eng, run_async, short, long)
    # one program carries both: the short prompt ends in it
    assert _asked(rec) == [[CHUNK - 7, -1], [-1], [CHUNK - 4]]
    assert stats["prefill_logits_skipped_total"] == 1
    (alone_a,), _ = _run(_engine(), run_async, short)
    (alone_b,), _ = _run(_engine(), run_async, long)
    assert a == alone_a and b == alone_b


def test_a_model_that_generates_by_blocks_asks_for_no_logits(run_async):
    eng = importlib.import_module("test_sdar")._engine()
    assert eng.block > 1
    rec = eng.prefill_fn = _Recorded(eng.prefill_fn)
    prompt = [int(t) for t in
              np.random.default_rng(3).integers(1, 500,
                                                3 * eng.ecfg.prefill_chunk)]
    (toks,), stats = _run(eng, run_async, prompt, n=8)
    assert len(toks) == 8 and len(rec.calls) >= 3
    assert all(i == -1 for row in _asked(rec) for i in row)
    assert stats["prefill_logits_skipped_total"] \
        == stats["prefill_dispatches_total"] == len(rec.calls)


def test_a_resumed_prefill_asks_for_no_logits(run_async):
    """Four requests in a pool that holds three: the preempted rows
    prefill again, what they generated included, and their next token is
    sampled already."""
    eng = _engine(num_pages=16, watermark_pages=1)
    rec = eng.prefill_fn = _Recorded(eng.prefill_fn)
    preempted, grow = [], eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s) for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)

    eng._grow_or_preempt = spy
    prompts = [list(range(i * CHUNK + 1, (i + 1) * CHUNK + 1))
               for i in range(4)]
    out, stats = _run(eng, run_async, *prompts, n=16)
    assert preempted, "the pool was meant to run out"
    assert all(len(toks) == 16 for toks in out)
    rows = [i for row in _asked(rec) for i in row]
    # one draw a request, from its first pass; the resumed rows ask none
    assert sum(i >= 0 for i in rows) == 4 == stats["first_tokens_total"]
    assert len(rows) >= 4 + len(preempted)
    assert stats["prefill_logits_skipped_total"] == sum(
        all(i < 0 for i in row) for row in _asked(rec)) >= 1
