"""The batch bucket of a prefill dispatch, chosen by what the warmed
programs cost a row they ship (engine/jax_engine.py
choose_prefill_bucket): the choice as a pure function over a table, an
engine with an injected table beside one without, and warmup()'s own
table under the compile fence."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine import (EngineConfig, JaxEngine, Sequence,
                                          choose_prefill_bucket,
                                          prefill_cost_ms)
from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                             SamplingOptions,
                                             StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime import Context

# a dense model past the MXU's ridge: a batch shares nothing, and the
# padded rows of a wide bucket are computed like live ones
DENSE = {(1, 256): (18.5, 18.5), (1, 512): (32.0, 32.0),
         (8, 256): (148.0, 150.0), (8, 512): (281.0, 285.0)}
# a model bound by the read of its experts: one row pays the read
EXPERT = {(1, 512): (19.0, 19.0), (4, 512): (22.0, 45.0)}
# three rows in the wide bucket cost 40.7 a row, one alone 40
TIE = {(1, 512): (40.0, 40.0), (4, 512): (66.0, 150.0)}


@pytest.mark.parametrize("costs,T,n,unmeasured,want", [
    *[(DENSE, 512, n, 8 if n > 1 else 1, (1, 1)) for n in range(1, 9)],
    *[(DENSE, 256, n, 8, (1, 1)) for n in (2, 5, 7)],
    (DENSE, 256, 8, 8, (8, 8)),         # full: 18.75 a row for 18.5
    (EXPERT, 512, 1, 1, (1, 1)),
    *[(EXPERT, 512, n, 4, (4, n)) for n in (2, 3, 4)],
    (EXPERT, 512, 6, 4, (4, 4)),        # two rows wait
    (TIE, 512, 3, 4, (4, 3)),           # within 5%: more rows
    (TIE, 512, 2, 4, (1, 1)),           # 45 a row against 40
    ({(8, 256): (30.0, 31.0)}, 256, 1, 8, (8, 1)),   # one warmed bucket
    ({(8, 256): (30.0, 31.0)}, 256, 3, 8, (8, 3)),
    ({}, 512, 1, 1, (1, 1)),            # never warmed: the old rule
    ({}, 512, 3, 8, (8, 3)),
    (DENSE, 128, 3, 8, (8, 3)),         # no reading at this T
])
def test_choice_over_a_table(costs, T, n, unmeasured, want):
    assert choose_prefill_bucket(costs, T, n, unmeasured) == want


def test_cost_is_a_line_between_the_two_readings():
    assert prefill_cost_ms(EXPERT, 4, 512, 1) == 22.0
    assert prefill_cost_ms(EXPERT, 4, 512, 4) == 45.0
    assert prefill_cost_ms(EXPERT, 4, 512, 2) == pytest.approx(22 + 23 / 3)
    assert prefill_cost_ms(EXPERT, 1, 512, 1) == 19.0


# --------------------------------------------- an engine, stepped by hand


def _ecfg(**kw):
    base = dict(page_size=4, num_pages=128, max_batch=8, prefill_chunk=32,
                prefill_buckets=(32,), batch_buckets=(1, 8),
                page_buckets=(16,), max_prefill_batch=8, decode_steps=2)
    base.update(kw)
    return EngineConfig(**base)


def _req(tokens, mt=6):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=mt, ignore_eos=True),
        eos_token_ids=[])


def _submit(eng, req):
    seq = Sequence(req=req, context=Context(), out=asyncio.Queue(),
                   tokens=list(req.token_ids),
                   num_prompt=len(req.token_ids))
    eng.waiting.append(seq)
    return seq


def _spy_prefill(eng):
    """The token operand of every prefill program the engine runs from
    here on, in order."""
    shipped = []
    fn = eng.prefill_fn

    def spy(params, tokens, *a, **kw):
        shipped.append(np.asarray(tokens))
        return fn(params, tokens, *a, **kw)

    eng.prefill_fn = spy
    return shipped


def _serve_three(eng):
    """Three prompts waiting at once, stepped to the end: the token
    operand of every prefill program, and each request's tokens."""
    shipped = _spy_prefill(eng)
    prompts = [list(range(10 + 20 * i, 21 + 23 * i)) for i in range(3)]
    seqs = [_submit(eng, _req(p)) for p in prompts]
    for _ in range(64):
        if all(s.finished for s in seqs):
            break
        eng._step()
        eng._reap()
    assert all(s.finished for s in seqs)
    return prompts, shipped, [s.tokens[s.num_prompt:] for s in seqs]


def test_three_prompts_ship_one_a_program_in_order_with_the_same_tokens():
    """Under a dense-like table three waiting prompts ship as three PB 1
    programs, first come first, and every request reads what the one
    PB 8 program of an engine without a table gave it."""
    plain = JaxEngine(ModelConfig.tiny(), _ecfg(), seed=0)
    prompts, wide, want = _serve_three(plain)
    assert [t.shape for t in wide] == [(8, 32)]
    s = plain.stats()
    assert s["prefill_rows_held_back_total"] == 0
    assert s["prefill_bucket_narrowed_total"] == 0
    assert s["prefill_program_cost_ms"] == {}

    eng = JaxEngine(ModelConfig.tiny(), _ecfg(), seed=0)
    eng._prefill_costs = {(1, 32): (1.0, 1.0), (8, 32): (8.8, 9.0)}
    prompts, narrow, got = _serve_three(eng)
    assert [t.shape for t in narrow] == [(1, 32)] * 3
    for prompt, tokens in zip(prompts, narrow):
        assert list(tokens[0, :len(prompt)]) == prompt
    assert got == want and all(len(t) == 6 for t in got)
    s = eng.stats()
    # 2 rows wait behind the first program and 1 behind the second; the
    # third dispatch is the one row the old rule sends to PB 1 too
    assert s["prefill_rows_held_back_total"] == 3
    assert s["prefill_bucket_narrowed_total"] == 2
    assert s["prefill_dispatches_total"] == 3
    assert s["prefill_slots_total"] == 3 * 32
    assert s["prefill_program_cost_ms"] == {"1x32": [1.0, 1.0],
                                            "8x32": [8.8, 9.0]}


def test_an_expert_like_table_keeps_the_wide_bucket():
    eng = JaxEngine(ModelConfig.tiny(), _ecfg(), seed=0)
    eng._prefill_costs = {(1, 32): (2.0, 2.0), (8, 32): (2.2, 4.0)}
    _, shipped, got = _serve_three(eng)
    assert [t.shape for t in shipped] == [(8, 32)]
    s = eng.stats()
    assert s["prefill_rows_held_back_total"] == 0
    assert s["prefill_bucket_narrowed_total"] == 0


def test_the_token_budget_trims_before_the_choice():
    """Budgeted mixing forms its batch first (prompts of 11, 14 and 17
    tokens: two inside a budget of 26, then one, then one); the choice
    ships one row of the two, and the row it leaves is counted once."""
    eng = JaxEngine(ModelConfig.tiny(), _ecfg(prefill_token_budget=26),
                    seed=0)
    eng._prefill_costs = {(1, 32): (1.0, 1.0), (8, 32): (8.8, 9.0)}
    _, shipped, got = _serve_three(eng)
    assert [t.shape for t in shipped] == [(1, 32)] * 3
    assert all(len(t) == 6 for t in got)
    assert eng.stats()["prefill_rows_held_back_total"] == 1


# ------------------------------------------------- warmup()'s own table


def _fence_engine(**kw):
    """tests/test_jit_fence.py's engine."""
    base = dict(page_size=8, num_pages=64, max_batch=4, prefill_chunk=32,
                batch_buckets=(1, 2, 4), prefill_buckets=(16, 32),
                page_buckets=(8,), max_prefill_batch=2, decode_steps=2,
                spec_decode=True, spec_tokens=2)
    base.update(kw)
    return JaxEngine(ModelConfig.tiny(), EngineConfig(**base), seed=0)


def test_warmup_reads_every_warmed_prefill_program_and_compiles_nothing():
    eng = _fence_engine()
    eng.warmup()
    grid = eng.ecfg.warmed_grid()
    assert grid["prefill_batches"] == [1, 2]
    table = eng._prefill_costs
    assert sorted(table) == sorted(
        (PB, T) for PB in grid["prefill_batches"]
        for T in grid["prefill_lens"])
    for (PB, _), (one, full) in table.items():
        assert one > 0 and full > 0
        assert PB > 1 or one == full
    assert eng.fence.armed and eng.fence.post_warmup_compiles == 0
    assert set(eng.stats()["prefill_program_cost_ms"]) == {
        "1x16", "1x32", "2x16", "2x32"}

    async def one(r):
        toks = []
        async for out in eng.generate(r, Context()):
            toks.extend(out.token_ids)
        return toks

    async def main():
        out = await asyncio.gather(*(
            one(_req(range(1 + i, 12 + 5 * i))) for i in range(4)))
        compiles = eng.fence.post_warmup_compiles
        await eng.stop()
        return out, compiles

    out, compiles = asyncio.run(main())
    assert all(len(t) == 6 for t in out)
    assert compiles == 0, "the choice reached a bucket warmup() never ran"


@pytest.mark.parametrize("kw", [
    dict(batch_buckets=(4,)),                       # every prefill PB 4
    dict(batch_buckets=(2, 4), max_prefill_batch=2),
])
def test_one_warmed_bucket_times_nothing(kw):
    """One entry in warmed_grid()["prefill_batches"]: warmup() runs each
    prefill program once, to compile it, and never again."""
    eng = _fence_engine(spec_decode=False, **kw)
    grid = eng.ecfg.warmed_grid()
    assert len(grid["prefill_batches"]) == 1
    calls = _spy_prefill(eng)
    eng.warmup(decode=False)
    assert len(calls) == len(grid["prefill_lens"]) * len(grid["page_buckets"])
    assert eng._prefill_costs == {}
    assert eng.stats()["prefill_program_cost_ms"] == {}
    eng.fence.disarm()


def test_two_warmed_buckets_are_timed_inside_the_budget():
    """Each form runs once to compile, then once or twice for the table:
    every row live, and one row live where PB > 1."""
    eng = _fence_engine(spec_decode=False)
    calls = _spy_prefill(eng)
    eng.warmup(decode=False)
    compiled, forms = 4, 6      # (1, 2) x (16, 32); PB 2 in two fills
    assert compiled + forms <= len(calls) <= compiled + 2 * forms
    eng.fence.disarm()
