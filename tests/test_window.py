"""models/window.py: the decode window's step protocol, held once. A toy
family whose step reads its logits from a script (no model), so that what
every family's window inherits from ``make_window`` is checked in seconds:
stop ids, budgets, padding rows, the ``emitted`` count, the auxiliary
log-probabilities, penalty state threaded from step to step, and the
order of the results as ``unpack`` reads it."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.window import Family, make_window, unpack

V, K, S = 16, 6, 32     # vocabulary, steps a window, slots of a row's "pool"
STOP = 9


def toy_family(counts: bool = False, state: bool = False) -> Family:
    """Row b at position p yields ``params["script"][b, p]`` (a one-hot
    logit row). Its "K/V" is the step's INPUT token, kept in a window
    buffer [B, K]; the commit writes entry i of a row at ``start + i`` of
    ``kv_k`` [B, S] iff ``start + i < pos``, as commit_window does by
    pages. With ``state`` a counter a slot moves once a live row-step;
    with ``counts`` a step counts (live rows, 1)."""

    def begin(w):
        B = w.start.shape[0]
        moved = w.state[w.state_slots] if state else None
        return jnp.zeros((B, w.k_steps), jnp.int32), moved

    def step(w, bufs, tok, pos, active, i):
        buf, moved = bufs
        buf = buf.at[:, i].set(tok)
        rows = jnp.arange(tok.shape[0])
        logits = 10.0 * jnp.eye(V)[w.params["script"][rows,
                                                      jnp.maximum(pos, 0)]]
        if state:
            moved = moved + active.astype(jnp.int32)
        counted = (jnp.stack([jnp.sum(active.astype(jnp.int32)),
                              jnp.int32(1)]) if counts else None)
        return logits, (buf, moved), counted

    def commit(w, bufs, pos):
        buf, moved = bufs
        at = w.start[:, None] + jnp.arange(w.k_steps)[None, :]
        at = jnp.where((w.start[:, None] >= 0) & (at < pos[:, None]), at, S)
        rows = jnp.arange(buf.shape[0])[:, None]
        kv_k = w.kv_k.at[rows, at].set(buf, mode="drop")
        return kv_k, w.kv_v, (w.state.at[w.state_slots].set(moved)
                              if state else None)

    return Family(begin, step, commit)


def run(script, positions, remaining, *, family=None, topn=0, penalties=None,
        state=None, tokens=None, eos=(STOP,)):
    """One window of K steps over the rows of ``script`` [B, S]."""
    B = len(script)
    fn = make_window(family or toy_family(), max_top_k=8)
    eos_table = np.full((B, 4), -1, np.int32)
    eos_table[:, :len(eos)] = eos
    slots = (jnp.arange(B, dtype=jnp.int32),) if state is not None else ()
    return fn({"script": jnp.asarray(script, jnp.int32)},
              jnp.asarray(tokens if tokens is not None else np.ones(B),
                          jnp.int32),
              jnp.asarray(positions, jnp.int32), jnp.zeros(B, bool),
              jnp.zeros(B, jnp.int32), jnp.asarray(remaining, jnp.int32),
              jnp.full((B, S), -1, jnp.int32), jnp.zeros((B, 1), jnp.int32),
              jnp.zeros((B, 1), jnp.int32), jnp.zeros(B),
              jnp.zeros(B, jnp.int32), jnp.ones(B),
              jnp.zeros(B, jnp.uint32), jnp.asarray(eos_table), penalties,
              *((state,) + slots if state is not None else ()),
              k_steps=K, logprobs_topn=topn)


def four_rows():
    """Row 0 samples the stop id at its third step; row 1 has a budget of
    two; row 2 is padding; row 3 runs the whole window."""
    script = np.tile(np.arange(S) % 7 + 1, (4, 1))      # ids 1..7, no stop
    script[0, 5 + 2] = STOP
    return script, [5, 0, -1, 3], [100, 2, 1, 100]


def test_a_stop_id_a_budget_and_a_padding_row_freeze_their_rows():
    script, positions, remaining = four_rows()
    toks, emitted, carry, kv_k, kv_v = run(script, positions, remaining)
    tok, pos, done, steps, rem = (np.asarray(x) for x in carry)
    toks, emitted, kv_k = np.asarray(toks), np.asarray(emitted), \
        np.asarray(kv_k)
    assert toks.shape == (4, K)
    # emitted is the steps a row was live
    assert emitted.tolist() == [3, 2, 0, K]
    assert done.tolist() == [True, True, False, False]
    assert pos.tolist() == [8, 2, -1, 3 + K]
    assert steps.tolist() == [3, 2, 0, K]
    assert rem.tolist() == [97, 0, 1, 100 - K]
    # the stop id is emitted, and the row's token freezes at it
    assert toks[0, :3].tolist() == script[0, 5:8].tolist()
    assert toks[0, 2] == STOP and (toks[0, 2:] == STOP).all()
    assert toks[1, :2].tolist() == script[1, 0:2].tolist()
    assert (toks[1, 2:] == toks[1, 1]).all()
    assert (toks[2] == 1).all()             # padding: its input, untouched
    assert toks[3].tolist() == script[3, 3:3 + K].tolist()
    assert tok.tolist() == toks[:, -1].tolist()
    # a row commits the inputs of the steps it was live in, nothing after
    want = np.full((4, S), -1)
    want[0, 5:8] = [1, *script[0, 5:7]]
    want[1, 0:2] = [1, script[1, 0]]
    want[3, 3:3 + K] = [1, *script[3, 3:3 + K - 1]]
    np.testing.assert_array_equal(kv_k, want)


def test_a_budget_of_n_ends_after_n_whatever_the_window():
    script = np.tile(np.arange(S) % 7 + 1, (K + 1, 1))
    _, emitted, carry, _, _ = run(script, [0] * (K + 1), range(1, K + 2))
    assert np.asarray(emitted).tolist() == [*range(1, K + 1), K]
    assert np.asarray(carry[2]).tolist() == [True] * K + [False]


@pytest.mark.parametrize("topn", [0, 3])
def test_aux_is_there_when_asked_for_and_shaped_by_step(topn):
    script, positions, remaining = four_rows()
    out = run(script, positions, remaining, topn=topn)
    res = unpack(out, topn, counts=False, state=False)
    if not topn:
        assert len(out) == 5 and res.aux is None
        return
    assert len(out) == 6
    lp, tv, ti = res.aux
    assert lp.shape == (4, K)
    assert tv.shape == ti.shape == (4, K, topn)
    # a one-hot logit row: the chosen token is the first of the top ids
    np.testing.assert_array_equal(np.asarray(ti)[3, :, 0],
                                  script[3, 3:3 + K])
    np.testing.assert_allclose(np.asarray(lp)[3], np.asarray(tv)[3, :, 0])


def test_penalties_thread_from_step_to_step():
    """The same logits every step ([5, 4, 2.5] on ids 0..2) under a
    frequency penalty of 2: each step must see the counts of the steps
    before it (0, 1, 0, 2, ...); penalties left as they came give 0
    every step."""
    fixed = jnp.zeros(V).at[:3].set(jnp.asarray([5.0, 4.0, 2.5]))
    family = toy_family()._replace(
        step=lambda w, bufs, tok, pos, active, i: (
            jnp.tile(fixed, (1, 1)), bufs, None))
    pen = (jnp.zeros((1, V), jnp.int32), jnp.zeros((1, V), jnp.int8),
           jnp.ones(1), jnp.full(1, 2.0), jnp.zeros(1))
    toks = run(np.zeros((1, S)), [0], [100], family=family, penalties=pen)[0]
    assert np.asarray(toks)[0, :4].tolist() == [0, 1, 0, 2]
    toks = run(np.zeros((1, S)), [0], [100], family=family)[0]
    assert np.asarray(toks)[0].tolist() == [0] * K


@pytest.mark.parametrize("state", [False, True], ids=["stateless", "state"])
@pytest.mark.parametrize("counts", [False, True], ids=["plain", "counts"])
@pytest.mark.parametrize("topn", [0, 2])
def test_the_results_order_is_unpacks(topn, counts, state):
    """(toks, emitted, [aux,] carry, kv_k, kv_v, [counts,] [state]): what
    the program returns, read back by name."""
    script, positions, remaining = four_rows()
    pool = jnp.arange(4, dtype=jnp.int32) * 10 if state else None
    out = run(script, positions, remaining, topn=topn, state=pool,
              family=toy_family(counts, state))
    assert len(out) == 5 + bool(topn) + counts + state
    res = unpack(out, topn, counts=counts, state=state)
    assert res.pack() == tuple(out)
    assert res.toks.shape == (4, K) and res.emitted.shape == (4,)
    assert len(res.carry) == 5 and res.kv_k.shape == (4, S)
    assert (res.aux is None) == (not topn)
    if counts:      # summed over the steps: live row-steps, steps
        assert np.asarray(res.counts).tolist() == [3 + 2 + 0 + K, K]
    else:
        assert res.counts is None
    if state:       # a slot moved once a live row-step
        assert np.asarray(res.state).tolist() == [3, 12, 20, 30 + K]
    else:
        assert res.state is None


def _qwen3_moe():
    from dynamo_tpu.models.config import ModelConfig

    return ModelConfig.from_hf_config(dict(
        model_type="qwen3_moe", vocab_size=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True))


def _tiny(test_module: str, name: str = "tiny"):
    import importlib

    return getattr(importlib.import_module(f"tests.{test_module}"), name)()


@pytest.mark.parametrize("cfg", [
    pytest.param(_qwen3_moe, id="llama"),
    pytest.param(lambda: _tiny("test_sdar"), id="llama-blocks"),
    pytest.param(lambda: _tiny("test_mla", "tiny_v3"), id="mla"),
    pytest.param(lambda: _tiny("test_jamba"), id="jamba"),
    pytest.param(lambda: _tiny("test_granite"), id="granite"),
    pytest.param(lambda: _tiny("test_lfm2"), id="lfm2"),
])
def test_every_modules_window_is_named_decode_window(cfg):
    """The benchmark's ``window_ms_mean`` and ``decode_rows_mean`` find
    the program by its name (``trace.WINDOW_MODULE``), the block window
    (``block_length`` > 1) under the same one as the window of tokens."""
    from dynamo_tpu.models.registry import get_model_module

    cfg = cfg()
    fn = get_model_module(cfg).make_decode_window_fn(cfg, True, 64)
    assert fn.__name__ == "decode_window"


# ------------------------------------------------- the draw's two arms
# sample_tokens branches at run time on whether a row is sampled. One
# window of make_window's loop and one of the block window, through the
# engine: an all-greedy batch and a batch with one sampled row emit what
# they emit with the sampler as it stood before the branch, and the
# engine counts the windows that had a sampled row.


def _llama_engine():
    import jax
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import llama

    cfg = _qwen3_moe()
    cfg.dtype = "float32"
    return JaxEngine(
        cfg, EngineConfig(page_size=4, num_pages=64, max_batch=4,
                          prefill_chunk=16, batch_buckets=(4,),
                          prefill_buckets=(16,), page_buckets=(16,),
                          max_prefill_batch=2, decode_steps=8,
                          warmup_logprobs=False),
        params=llama.init_params(cfg, jax.random.PRNGKey(0)), seed=0)


def _parents_sample_tokens(logits, temperature, top_k, top_p, seeds, step,
                           max_top_k=64, penalties=None):
    from tests.test_sampling_topk import _ref_sample_tokens

    assert penalties is None
    return _ref_sample_tokens(logits, temperature, top_k, top_p, seeds,
                              step, max_top_k)


@pytest.mark.parametrize("make,tokens", [
    pytest.param(_llama_engine, 9, id="llama"),
    pytest.param(lambda: _tiny("test_sdar", "_engine"), 8,
                 id="llama-blocks"),
])
def test_a_window_draws_the_parents_tokens_on_either_arm(
        make, tokens, run_async, monkeypatch):
    import asyncio

    import jax
    from dynamo_tpu.llm.protocols.common import SamplingOptions
    from tests.test_sdar import _collect, _prompts, _req

    p, q = _prompts(47, 12, 12)

    async def batch(eng, sampled):
        reqs = [_req(p, tokens), _req(q, tokens)]
        if sampled:
            reqs[1].sampling = SamplingOptions(temperature=1.3, top_k=20,
                                               top_p=0.9, seed=11)
        outs = await asyncio.gather(*(_collect(eng, r) for r in reqs))
        s = eng.stats()
        return ([t for t, _, _ in outs], s["decode_windows_total"],
                s["decode_windows_sampled_total"])

    async def main(eng):
        got = [await batch(eng, False), await batch(eng, True)]
        await eng.stop()
        return got

    greedy, mixed = run_async(main(make()))
    assert all(len(t) == tokens for t in greedy[0] + mixed[0])
    assert greedy[0][0] == mixed[0][0] and greedy[0][1] != mixed[0][1]
    # a row's whole generation is one window, and the engine dispatches
    # the next before it has read that: every window of the second batch
    # held the sampled row, none of the first
    assert greedy[1] >= 1 and greedy[2] == 0
    assert mixed[2] == mixed[1] - greedy[1] >= 1

    # the same two batches with the sampler of before the branch
    from dynamo_tpu.engine import jax_engine, sampling
    from dynamo_tpu.models import window
    jax.clear_caches()      # the programs hold the sampler they traced
    for mod in (sampling, window, jax_engine):
        monkeypatch.setattr(mod, "sample_tokens", _parents_sample_tokens)
    want_greedy, want_mixed = run_async(main(make()))
    assert greedy[0] == want_greedy[0] and mixed[0] == want_mixed[0]
    jax.clear_caches()
