"""``exact_top_k``: the sampler's candidate selection without a sort of
the vocabulary. It has to be ``jax.lax.top_k`` to the letter (values
descending, equal values by lower vocabulary index), so everything here
compares against the plain call, and the three users of the helper
against copies of their bodies from before it existed. ``greedy_tokens``,
the draw of a batch with no sampled row (``sample_tokens`` branches on
it at run time), has to be that selection's first element to the letter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.sampling import (exact_top_k, greedy_tokens,
                                        logprob_aux, sample_tokens,
                                        verify_greedy_draft)

C = 128
# (V, k): Qwen3, Mixtral at the sampler's bound; a vocabulary that needs
# a padded tail at the logprobs' top-20; one small enough to fall back
SHAPES = [(151936, 64), (32000, 64), (128815, 20), (1000, 64)]
KINDS = ["random", "all_equal", "max_across_chunk_boundary",
         "kth_rank_tied_over_many_chunks", "mostly_neg_inf",
         "top_k_in_one_chunk", "all_neg_inf", "tail_holds_the_top",
         "signed_zeros"]

def _rows(kind, B, V, k, seed):
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.standard_normal((B, V)).astype(np.float32)
    if kind == "all_equal":
        return np.full((B, V), 0.25, np.float32)
    if kind == "max_across_chunk_boundary":
        # the maximum on the last lane of one chunk and the first of the
        # next, in several places, more often than k in all
        x = rng.standard_normal((B, V)).astype(np.float32)
        edges = rng.choice(np.arange(1, V // C), replace=False,
                           size=min(k, V // C - 1)) * C
        x[:, edges] = 9.0
        x[:, edges - 1] = 9.0
        return x
    if kind == "kth_rank_tied_over_many_chunks":
        # k // 2 clear winners, then one value tied across 3k chunks:
        # which of them make the cut is the index rule alone
        x = rng.standard_normal((B, V)).astype(np.float32)
        chunks = rng.choice(V // C, size=min(3 * k, V // C), replace=False)
        x[:, chunks * C + rng.integers(0, C, len(chunks))] = 7.0
        x[:, rng.choice(V, size=k // 2, replace=False)] = 8.0 + np.arange(
            k // 2, dtype=np.float32)
        return x
    if kind == "mostly_neg_inf":     # logit_bias / a grammar's mask
        x = np.full((B, V), -np.inf, np.float32)
        x[:, rng.choice(V, size=k // 3, replace=False)] = (
            rng.standard_normal(k // 3).astype(np.float32))
        return x
    if kind == "top_k_in_one_chunk":
        x = rng.standard_normal((B, V)).astype(np.float32)
        at = (V // C // 2) * C
        x[:, at:at + C] += 50.0
        return x
    if kind == "all_neg_inf":
        return np.full((B, V), -np.inf, np.float32)
    if kind == "signed_zeros":       # lax.top_k ranks 0.0 above -0.0
        x = np.minimum(rng.standard_normal((B, V)), 0.0).astype(np.float32)
        x[:, rng.choice(V, size=V // 4, replace=False)] = -0.0
        x[:, rng.choice(V, size=k // 2, replace=False)] = 0.0
        return x
    assert kind == "tail_holds_the_top"
    x = rng.standard_normal((B, V)).astype(np.float32)
    x[:, V - k // 2:] = 30.0        # ties that end at the last real id
    return x


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("B", [1, 2, 3, 4])
@pytest.mark.parametrize("V,k", SHAPES)
def test_exact_top_k_is_lax_top_k(V, k, B, kind):
    x = jnp.asarray(_rows(kind, B, V, k, seed=B * 1000 + k))
    want_v, want_i = jax.lax.top_k(x, k)
    got_v, got_i = exact_top_k(x, k)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    assert got_i.dtype == want_i.dtype and got_v.dtype == want_v.dtype
    assert int(jnp.max(got_i)) < V


# the vocabularies of the benchmark's configurations, one that needs a
# padded tail, and one small enough that exact_top_k is the plain call
GREEDY_V = [32000, 65536, 100352, 128256, 151936, 128815, 1000]
GREEDY_ROWS = ["distinct", "bf16_grained", "all_equal", "signed_zeros_on_top",
               "inf_twice", "neg_inf_but_one", "nan"]


def _greedy_rows(kind, B, V, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((B, V)) * 3.0).astype(np.float32)
    at = lambda n: np.stack([rng.choice(V, size=n, replace=False)
                             for _ in range(B)])
    put = lambda ids, v: np.put_along_axis(x, ids, np.float32(v), axis=1)
    if kind == "bf16_grained":      # the maximum ties all the time
        x = np.round(x * 2.0) / 2.0
    elif kind == "all_equal":
        x[:] = 0.25
    elif kind == "signed_zeros_on_top":
        # 0.0 and -0.0 the two largest, -0.0 at the lower id in half the
        # rows: the total order puts 0.0 first whichever comes first
        x = -np.abs(x) - 1.0
        ids = np.sort(at(2), axis=1)
        ids[::2] = ids[::2, ::-1]
        put(ids[:, :1], 0.0)
        put(ids[:, 1:], -0.0)
    elif kind == "inf_twice":
        put(at(2), np.inf)
    elif kind == "neg_inf_but_one":
        one = at(1)
        keep = np.take_along_axis(x, one, axis=1)
        x[:] = -np.inf
        np.put_along_axis(x, one, keep, axis=1)
    elif kind == "nan":
        put(at(1), np.nan)
    else:
        assert kind == "distinct"
    return x


@pytest.mark.parametrize("kind", GREEDY_ROWS)
@pytest.mark.parametrize("V", GREEDY_V)
def test_greedy_arm_is_the_first_of_exact_top_k(V, kind):
    """One argmax over the rank key names the token the two stages put
    first, element for element: what lets an all-greedy batch skip them.
    Through ``sample_tokens`` too, where a greedy row is divided by 1.0
    before the branch: the quotient keeps every bit that decides. (The
    NaN planted is numpy's, sign bit clear: see ``greedy_tokens``.)"""
    x = _greedy_rows(kind, 6, V, seed=V + len(kind))
    want = np.asarray(exact_top_k(jnp.asarray(x), 64)[1][:, 0])
    got = jax.jit(greedy_tokens)(jnp.asarray(x))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    zeros = jnp.zeros((6,), jnp.int32)
    drawn = sample_tokens(jnp.asarray(x), jnp.zeros((6,), jnp.float32), zeros,
                          jnp.ones((6,), jnp.float32),
                          jnp.arange(6, dtype=jnp.uint32), zeros)
    np.testing.assert_array_equal(np.asarray(drawn), want)
    if kind == "signed_zeros_on_top":
        assert np.all(x[np.arange(6), want] == 0.0)
        assert not np.any(np.signbit(x[np.arange(6), want]))
    if kind == "nan":
        assert np.all(np.isnan(x[np.arange(6), want]))


# ---------------------------------------------------------------------
# token identity: the three users against their bodies on plain
# lax.top_k, as they stood before exact_top_k (the plain reference)


def _ref_sample_tokens(logits, temperature, top_k, top_p, seeds, step,
                       max_top_k=64):
    step = jnp.broadcast_to(step, temperature.shape)
    B, V = logits.shape
    temp = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    scaled = logits / temp
    k_vals, k_idx = jax.lax.top_k(scaled, max_top_k)
    greedy = k_idx[:, 0]
    ranks = jnp.arange(max_top_k)[None, :]
    eff_k = jnp.where(top_k[:, None] > 0,
                      jnp.minimum(top_k[:, None], max_top_k), max_top_k)
    k_vals = jnp.where(ranks < eff_k, k_vals, -jnp.inf)
    probs = jax.nn.softmax(k_vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]
    k_vals = jnp.where(keep, k_vals, -jnp.inf)

    def row_sample(i):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seeds[i]), step[i])
        return k_idx[i, jax.random.categorical(key, k_vals[i])]

    sampled = jax.vmap(row_sample)(jnp.arange(B))
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _ref_verify_greedy_draft(logits, draft, draft_len, max_top_k=64):
    B, K1, V = logits.shape
    K = K1 - 1
    _, k_idx = jax.lax.top_k(logits.reshape(B * K1, V), max_top_k)
    greedy = k_idx[:, 0].reshape(B, K1).astype(jnp.int32)
    match = jnp.logical_and(draft == greedy[:, :K],
                            jnp.arange(K)[None, :] < draft_len[:, None])
    accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    bonus = jnp.take_along_axis(greedy, accepted[:, None], axis=1)
    steps = jnp.arange(K1)[None, :]
    draft_ext = jnp.concatenate(
        [draft.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1)
    out = jnp.where(steps < accepted[:, None], draft_ext,
                    jnp.where(steps == accepted[:, None], bonus, -1))
    return out.astype(jnp.int32), accepted


def _ref_logprob_aux(logits, chosen, topn):
    logp = jax.nn.log_softmax(logits, axis=-1)
    tv, ti = jax.lax.top_k(logp, topn)
    return logp[jnp.arange(logp.shape[0]), chosen], tv, ti


B_TOK = 8
ROW_MIXES = {
    # temperature, top_k, top_p per row
    "greedy": ([0.0] * 8, [0] * 8, [1.0] * 8),
    "top_k": ([0.7, 1.0, 1.3, 0.7, 1.0, 1.3, 0.9, 2.0],
              [1, 5, 40, 64, 200, 0, 17, 3], [1.0] * 8),
    "top_p": ([0.7, 1.0, 1.3, 0.7, 1.0, 1.3, 0.9, 2.0], [0] * 8,
              [0.1, 0.5, 0.9, 0.95, 0.99, 1.0, 0.3, 0.7]),
    "mixed": ([0.0, 0.7, 0.0, 1.0, 1.5, 0.0, 0.8, 1.0],
              [0, 0, 10, 50, 0, 64, 5, 0],
              [1.0, 0.9, 0.5, 1.0, 0.8, 1.0, 0.95, 0.6]),
    # one sampled row switches the arm: the other seven are the sampled
    # arm's greedy rows
    "one_of_eight": ([0.0, 0.0, 0.0, 0.9, 0.0, 0.0, 0.0, 0.0],
                     [0, 0, 0, 30, 0, 0, 0, 0],
                     [1.0, 1.0, 1.0, 0.9, 1.0, 1.0, 1.0, 1.0]),
    "last_alone": ([0.0] * 7 + [1.2], [0] * 8, [1.0] * 7 + [0.8]),
}


def _logits(B, V, seed, tied):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((B, V)) * 3.0).astype(np.float32)
    if tied:
        # bfloat16-grained logits tie all the time, the maximum included
        x = np.round(x * 2.0) / 2.0
    return jnp.asarray(x)


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("mix", list(ROW_MIXES))
@pytest.mark.parametrize("V", [151936, 32000, 128815])
def test_sample_tokens_draws_the_same_tokens(V, mix, tied):
    temperature, top_k, top_p = ROW_MIXES[mix]
    args = (jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.arange(B_TOK, dtype=jnp.uint32) * 7919 + 11)
    ref = jax.jit(_ref_sample_tokens)
    for step in (0, 1, 5):
        logits = _logits(B_TOK, V, seed=V + step, tied=tied)
        st = jnp.full((B_TOK,), step, jnp.int32)
        got = sample_tokens(logits, *args, st)
        want = ref(logits, *args, st)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(jnp.max(got)) < V


@pytest.mark.parametrize("V", [151936, 32000])
def test_sample_tokens_with_penalties_and_bias_draws_the_same(V):
    """The eager `_sample_device` path: penalties and a `logit_bias`
    that forbids (-inf) all but a handful of tokens."""
    rng = np.random.default_rng(V)
    logits = _logits(4, V, seed=V, tied=True)
    counts = jnp.asarray(rng.integers(0, 3, (4, V)).astype(np.int32))
    presence = (counts > 0).astype(jnp.int32)
    bias = np.zeros((4, V), np.float32)
    bias[2] = -np.inf
    bias[2, rng.choice(V, 5, replace=False)] = 0.0
    bias[3] = -np.inf
    penalties = (counts, presence, jnp.asarray([1.0, 1.2, 1.0, 1.1]),
                 jnp.asarray([0.0, 0.5, 0.0, 0.0]),
                 jnp.asarray([0.0, 0.0, 0.3, 0.0]), jnp.asarray(bias))
    args = (jnp.asarray([0.0, 0.8, 1.0, 0.0], jnp.float32),
            jnp.asarray([0, 20, 0, 0], jnp.int32),
            jnp.asarray([1.0, 0.9, 1.0, 1.0], jnp.float32),
            jnp.asarray([1, 2, 3, 4], jnp.uint32), jnp.asarray(2, jnp.int32))
    got = sample_tokens(logits, *args, penalties=penalties)
    want = _ref_sample_tokens(sampling.apply_penalties(logits, *penalties),
                              *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("V", [151936, 32000])
def test_verify_greedy_draft_accepts_the_same(V):
    B, K = 3, 4
    logits = _logits(B * (K + 1), V, seed=V + 1, tied=True).reshape(
        B, K + 1, V)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    draft = greedy[:, :K].copy()
    draft[1, 2] = (draft[1, 2] + 1) % V       # row 1 diverges at step 2
    draft[2, 0] = (draft[2, 0] + 1) % V       # row 2 at once
    draft_len = jnp.asarray([K, K, 2], jnp.int32)
    got = verify_greedy_draft(logits, jnp.asarray(draft), draft_len)
    want = _ref_verify_greedy_draft(logits, jnp.asarray(draft), draft_len)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert list(np.asarray(got[1])) == [K, 2, 0]


@pytest.mark.parametrize("topn", [1, 5, 20])
@pytest.mark.parametrize("V", [151936, 32000, 128815])
def test_logprob_aux_reports_the_same_top(V, topn):
    logits = _logits(4, V, seed=V + 2, tied=True)
    chosen = jnp.asarray([0, 5, V - 1, V // 2], jnp.int32)
    got = jax.jit(logprob_aux, static_argnums=2)(logits, chosen, topn)
    want = jax.jit(_ref_logprob_aux, static_argnums=2)(logits, chosen, topn)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------
# structure: no selection over the whole vocabulary in the programs


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _selections_in(jaxpr):
    """[(primitive, width of its operand)] for every top_k and sort."""
    return [(e.primitive.name, e.invars[0].aval.shape[-1])
            for e in _eqns(jaxpr) if e.primitive.name in ("top_k", "sort")]


def _selections(fn, *shapes):
    return _selections_in(jax.make_jaxpr(fn)(*shapes).jaxpr)


def _users(B, V):
    f32, i32 = jnp.float32, jnp.int32
    sd = jax.ShapeDtypeStruct
    row = lambda dt: sd((B,), dt)
    return {
        "sample_tokens": (64, lambda *a: sample_tokens(*a, max_top_k=64),
                          (sd((B, V), f32), row(f32), row(i32), row(f32),
                           row(jnp.uint32), row(i32))),
        "verify_greedy_draft": (64, verify_greedy_draft,
                                (sd((B, 5, V), f32), sd((B, 4), i32),
                                 row(i32))),
        "logprob_aux": (20, lambda lg, ch: logprob_aux(lg, ch, 20),
                        (sd((B, V), f32), row(i32))),
        "greedy_tokens": (64, greedy_tokens, (sd((B, V), f32),)),
    }


# a greedy draw selects nothing: one argmax, at any V
GREEDY_USERS = ("verify_greedy_draft", "greedy_tokens")
USERS = ["sample_tokens", "verify_greedy_draft", "logprob_aux",
         "greedy_tokens"]


def _arms(V):
    """(greedy arm, sampled arm) of ``sample_tokens``' branch, each a
    jaxpr to read alone."""
    _, fn, shapes = _users(8, V)["sample_tokens"]
    conds = [e for e in _eqns(jax.make_jaxpr(fn)(*shapes).jaxpr)
             if e.primitive.name == "cond"]
    assert len(conds) == 1, conds
    # lax.cond(pred, true, false) keeps them as (false, true)
    return conds[0].params["branches"]


@pytest.mark.parametrize("user", USERS)
@pytest.mark.parametrize("V", [151936, 32000, 128815])
def test_no_selection_is_wider_than_the_candidates(V, user):
    k, fn, shapes = _users(8, V)[user]
    found = _selections(fn, *shapes)
    if user in GREEDY_USERS:
        assert found == []
        return
    widest = max(k * C, -(-V // C))
    assert found and all(w <= widest for _, w in found), found
    # the chunk maxima, then the candidates
    assert found == [("sort", -(-V // C)), ("sort", k * C)]


@pytest.mark.parametrize("user", USERS)
def test_a_tiny_vocabulary_takes_the_plain_call(user):
    V = 512
    _, fn, shapes = _users(8, V)[user]
    assert _selections(fn, *shapes) == (
        [] if user in GREEDY_USERS else [("top_k", V)])


@pytest.mark.parametrize("V", [151936, 32000, 128815, 512])
def test_the_greedy_arm_of_sample_tokens_selects_nothing(V):
    """Every selection of ``sample_tokens`` sits in the arm a sampled
    row switches on; the arm of an all-greedy batch, read alone, holds
    one argmax and nothing the width of the vocabulary but its input."""
    greedy_arm, sampled_arm = _arms(V)
    assert _selections_in(greedy_arm.jaxpr) == []
    assert _selections_in(sampled_arm.jaxpr) == (
        [("top_k", V)] if V == 512 else
        [("sort", -(-V // C)), ("sort", 64 * C)])
    names = [e.primitive.name for e in _eqns(greedy_arm.jaxpr)]
    assert "argmax" in names
    assert not {"div", "pad", "gather", "cumsum"} & set(names), names
    # the temperature's divide stands ABOVE the branch, once, where a
    # head's output fusion takes it in: neither arm divides the logits
    _, fn, shapes = _users(8, V)["sample_tokens"]
    wide = lambda jaxpr: [e for e in _eqns(jaxpr) if e.primitive.name == "div"
                          and e.outvars[0].aval.shape == (8, V)]
    assert len(wide(jax.make_jaxpr(fn)(*shapes).jaxpr)) == 1
    assert wide(greedy_arm.jaxpr) == wide(sampled_arm.jaxpr) == []


@pytest.mark.parametrize("mix", ["greedy", "one_of_eight", "last_alone"])
def test_the_branch_inside_a_while_loop_draws_the_same(mix):
    """The block window's place for the draw: the body of a
    ``lax.while_loop`` under ``jit``. Same tokens as outside, on either
    arm."""
    V, n = 32000, 3
    temperature, top_k, top_p = ROW_MIXES[mix]
    args = (jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.arange(B_TOK, dtype=jnp.uint32) * 7919 + 11)
    logits = jnp.stack([_logits(B_TOK, V, seed=V + i, tied=True)
                        for i in range(n)])

    @jax.jit
    def looped(logits):
        def body(c):
            i, out = c
            tok = sample_tokens(logits[i], *args,
                                jnp.full((B_TOK,), i, jnp.int32))
            return i + 1, out.at[i].set(tok)
        return jax.lax.while_loop(
            lambda c: c[0] < n, body,
            (jnp.int32(0), jnp.zeros((n, B_TOK), jnp.int32)))[1]

    want = np.stack([np.asarray(_ref_sample_tokens(
        logits[i], *args, jnp.full((B_TOK,), i, jnp.int32)))
        for i in range(n)])
    np.testing.assert_array_equal(np.asarray(looped(logits)), want)


def test_the_shape_alone_decides():
    """N <= 2k is the plain call, one chunk more is the two stages."""
    k = 4
    x = jax.ShapeDtypeStruct((2, 2 * k * C), jnp.float32)
    assert _selections(lambda a: exact_top_k(a, k), x) == [
        ("top_k", 2 * k * C)]
    x = jax.ShapeDtypeStruct((2, 2 * k * C + 1), jnp.float32)
    assert _selections(lambda a: exact_top_k(a, k), x) == [
        ("sort", 2 * k + 1), ("sort", k * C)]
