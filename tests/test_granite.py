"""Granite 4.0-H (Mamba-2 + attention + routed and shared experts,
models/granite.py): the step programs, the chip's share of a layer's
experts and the engine's recurrent-state pool against the plain
reference (benchmark/configs/granite-4.0-h-small/reference.py), on the
CPU at a small size with every kind of layer: float32, ONE whole period
of 10 layers (attention at layer 5), hidden 64, 8 Mamba heads of 16 x
16, 12 experts top-3 of which 6 are held, chunk 8, the four multipliers
as published, seeded random weights at the cell's weight scales
(benchmark/harness/weights.py with about.json's ``weight_scales``; the
embedding's follows the vocabulary).

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in chunks of matrix products with a carried state
and in rows, the reference token by token from zero), so logits of
magnitude ~3 differ by a few 1e-6; ATOL = 1e-4 leaves room and is far
under what a dropped state, a state pool rounded to bf16, a wrong conv
tail or an expert of the wrong share moves (1e-3 and more: see the
tests that provoke them)."""

import asyncio
import importlib.util
import json
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from benchmark.reference import AGREE_ATOL
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import granite, jamba, llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import DROP_SLOT, KVCacheSpec
from dynamo_tpu.models.registry import get_model_module
from dynamo_tpu.ops.selective_scan import ssd_step
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "granite-4.0-h-small")
ATOL = 1e-4
PS = 8
KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "granite_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)


def tiny(**over) -> ModelConfig:
    hf = dict(model_type="granitemoehybrid", vocab_size=512, hidden_size=64,
              intermediate_size=32, num_hidden_layers=10, layer_types=KINDS,
              num_attention_heads=4, num_key_value_heads=2,
              mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
              mamba_d_conv=4, mamba_expand=2, mamba_n_groups=1,
              mamba_chunk_size=8, num_local_experts=6,
              router_num_experts=12, first_local_expert=0,
              num_experts_per_tok=3, shared_intermediate_size=48,
              embedding_multiplier=12, attention_multiplier=0.0625,
              residual_multiplier=0.22, logits_scaling=16,
              position_embedding_type="nope", rms_norm_eps=1e-5,
              tie_word_embeddings=False)
    hf.update(over)
    cfg = ModelConfig.from_hf_config(hf)
    cfg.dtype = "float32"
    return cfg


def make_params(cfg, seed=0):
    """The cell's weights at this size: the harness's rule and the
    configuration's scales (12 x embed of unit RMS at this vocabulary)."""
    scales = dict(ABOUT["weight_scales"],
                  embed=math.sqrt(cfg.vocab_size) / cfg.embedding_multiplier)
    if cfg.tie_word_embeddings:
        del scales["lm_head"]
    return weights.build_tree(granite, cfg, weights.seed_key(seed), scales)


def ref_logits(params, cfg, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.reference_logits(params, cfg, tokens))


class Pools:
    """One sequence's pages and state slot in small pools, driven the way
    the engine drives them."""

    def __init__(self, cfg, pages=(3, 5, 7, 9, 11, 2), slot=2, slots=5):
        self.cfg = cfg
        self.kv_k, self.kv_v = granite.init_kv_cache(cfg, KVCacheSpec(16, PS))
        ssm, conv = granite.init_state(cfg, slots)
        # what a previous owner left in the slot must not matter
        self.state = (ssm.at[slot].set(7.0), conv.at[:, slot].set(3.0))
        self.pages, self.slot, self.drop = list(pages), slot, slots - 1
        self.prefill, self.decode = granite.make_step_fns(cfg)

    def table(self, rows, width=8):
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
            jnp.asarray([self.flat(np.asarray(pos)), DROP_SLOT], jnp.int32),
            self.state, jnp.asarray([self.slot, self.drop], jnp.int32))
        return np.asarray(logits[0])


def test_from_hf_config_on_the_catalog_config():
    """The published config and the file as it is run: Mamba-2 sizes and
    multipliers as published, attention at 5, 15, 25, 35 (at 5 of the 10
    run), the share beside the router's width, the registry's dispatch
    by what the configuration has; and what the module does not compute
    is refused, each by name."""
    published = ABOUT["published"]
    cfg = ModelConfig.from_hf_config(published)
    assert cfg.attn_layer_ids == (5, 15, 25, 35) and cfg.num_layers == 40
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_chunk_size, cfg.mamba_d_inner) \
        == (128, 64, 128, 4, 256, 8192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (32, 8, 128)
    assert (cfg.embedding_multiplier, cfg.attn_scale,
            cfg.residual_multiplier, cfg.logits_scaling) \
        == (12.0, 1 / 128, 0.22, 16.0)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.shared_intermediate_size) \
        == (72, 72, 0, 10, 1536)
    assert granite.held_first(cfg) is None      # every expert is here
    assert cfg.tie_word_embeddings and cfg.has_recurrent_state
    assert get_model_module(cfg) is granite

    run = ModelConfig.from_local_path(CONFIG_DIR)
    assert run.num_layers == 10 and run.attn_layer_ids == (5,)
    assert (run.num_experts, run.router_width, run.first_expert) \
        == (36, 72, 0)
    assert granite.held_first(run) == 0 and not run.tie_word_embeddings
    assert granite.segments(run) == [("mamba", 0, 0, 5), ("attn", 0, 5),
                                     ("mamba", 5, 6, 4)]
    ssm, conv = jax.eval_shape(lambda: granite.init_state(run, 65))
    assert ssm.shape == (65, 9, 128, 8192) and ssm.dtype == jnp.float32
    assert conv.shape == (9, 65, 3 * 8448)         # layer-major
    assert not hasattr(granite, "init_state_snapshots")

    for key, value, match in [
            ("mamba_n_groups", 3, "mamba_n_groups"),   # no divisor of 128
            ("position_embedding_type", "rope", "position_embedding_type"),
            ("mamba_proj_bias", True, "mamba_proj_bias"),
            ("mamba_d_head", 32, "mamba_n_heads x mamba_d_head"),
            ("first_local_expert", 1, "first_local_expert"),
            ("layer_types", ["mamba", "conv"] * 20, "layer_types")]:
        with pytest.raises(NotImplementedError, match=match):
            ModelConfig.from_hf_config(dict(published, **{key: value}))


@pytest.mark.parametrize("program", ["chunk", "step"])
def test_one_group_lowers_without_a_group_axis(program, monkeypatch):
    """Since PR 60 the mixer computes B and C by group
    (models/nemotron_h.py runs it with 8), the count taken in Python at
    trace time: at ``mamba_n_groups`` 1, this family's published value,
    the mixer traces what it traced before there were groups. The
    chunked form's C . B product is ONE [B, Q, Q] matrix with the row as
    its only batch axis, the step kernel takes B and C as [B, N, 1] ([B, N,
    G] by group), and
    the gated norm's one reduction is over all of d_inner; with two
    groups (read, no longer refused) each of the three has its group
    axis. (``tools/cell_programs.py --digest`` shows the same of cell
    8's lowered programs, parent | change: CHANGES.md, PR 60.)"""
    from tests.test_sampling_topk import _eqns

    B, T, Q = 2, (16 if program == "chunk" else 1), 8
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")

    def traced(groups):
        cfg = tiny(mamba_n_groups=groups)
        assert cfg.mamba_n_groups == groups
        assert granite.conv_width(cfg) == 128 + 2 * groups * 16
        params = jax.eval_shape(
            lambda: granite.init_params(cfg, jax.random.PRNGKey(0)))
        mp = {k: jax.ShapeDtypeStruct(params[k].shape[1:], params[k].dtype)
              for k in granite.MAMBA2_KEYS}
        s = jax.ShapeDtypeStruct
        pool = s((5, 1, 16, 128), jnp.float32)
        tails = s((1, B, 3 * granite.conv_width(cfg)), jnp.float32)

        def step_mixer(mp, u, valid, pool, tails):
            return granite._mamba2(
                cfg, mp, u, valid, pool, tails,
                lambda pool, *row: ssd_step(
                    pool, jnp.arange(B), jnp.int32(0), *row, None,
                    interpret=True),
                tail_step=lambda t, *row: jamba.conv_tail_step(
                    t, jnp.int32(0), *row, interpret=True))

        def chunk_mixer(mp, u, valid, s0, tail):
            return granite._mamba2(cfg, mp, u, valid, s0, tail)

        if program == "step":
            args = (mp, s((B, 1, 64), jnp.float32), s((B, 1), jnp.bool_),
                    pool, tails)
            return list(_eqns(jax.make_jaxpr(step_mixer)(*args).jaxpr))
        args = (mp, s((B, T, 64), jnp.float32), s((B, T), jnp.bool_),
                s((B, 16, 128), jnp.float32), tails.update(
                    shape=(B, 3 * granite.conv_width(cfg))))
        return list(_eqns(jax.make_jaxpr(chunk_mixer)(*args).jaxpr))

    def shapes_of(eqns, name):
        return [tuple(v.aval.shape for v in e.invars)
                + tuple(v.aval.shape for v in e.outvars)
                for e in eqns if e.primitive.name == name]

    one, two = traced(1), traced(2)
    if program == "chunk":
        cb = lambda eqns: [s for s in shapes_of(eqns, "dot_general")
                           if s[-1][-2:] == (Q, Q)]
        assert [s[-1] for s in cb(one)] == [(B, Q, Q)]
        assert [s[-1] for s in cb(two)] == [(B, 2, Q, Q)]
    else:
        kernel = lambda eqns: [s for s in shapes_of(eqns, "pallas_call")
                               if (5, 1, 16, 128) in s]
        assert (B, 16, 1) in kernel(one)[0]
        assert (B, 16, 2) in kernel(two)[0]         # [N, G] a row
        assert (B, 16, 1) not in kernel(two)[0]
    # the gated norm's mean of squares: over d_inner, or a group's half
    widths = lambda eqns: {s[0][-1] for s in shapes_of(eqns, "reduce_sum")
                           if len(s[0]) >= 3 and s[0][-1] in (64, 128)
                           and s[0][:2] == (B, T)}
    assert 128 in widths(one) and 64 not in widths(one)
    assert 64 in widths(two)


@pytest.mark.parametrize("tied,n_prompt,interpret", [
    (False, 21, False), (True, 21, False), (False, PS - 2, False),
    (False, 21, True)], ids=["untied", "tied", "page-edge",
                             "pallas_interpret"])
def test_prefill_and_window_match_reference(tied, n_prompt, interpret):
    """prefill_step (one chunk: 21 tokens in a bucket of 32 are two whole
    chunks of 8, a short one and padding) then two decode_windows through
    the pools against the reference's full forward, on logits (the
    window's top-8 log-probabilities at each of its steps), with the
    head tied and not. ``pallas_interpret``: the window's kernels under
    interpretation, the matrix state advanced in the pool (ssd_step);
    the second window then starts from what the first one's kernel left
    there."""
    cfg = tiny(tie_word_embeddings=tied)
    params = make_params(cfg)
    assert ("lm_head" in params) == (not tied)
    pools = Pools(cfg)
    prompt = np.random.default_rng(0).integers(1, 512, n_prompt)
    logits = pools.run_prefill(params, prompt, 0, 32)
    want = ref_logits(params, cfg, prompt)
    assert tied or np.abs(want).max() > 1.0     # logits of unit scale
    assert np.abs(logits - want[-1]).max() < ATOL
    # padding rows read and wrote the drop slot, and left it as it was
    assert float(jnp.abs(pools.state[0][pools.drop]).max()) == 0.0

    window = granite.make_decode_window_fn(cfg, True, 64,
                                           pallas_interpret=interpret)
    B, K = 2, 4
    first = int(np.argmax(logits))
    carry = (jnp.asarray([first, 0], jnp.int32),
             jnp.asarray([len(prompt), -1], jnp.int32), jnp.zeros(B, bool),
             jnp.zeros(B, jnp.int32), jnp.asarray([100, 1], jnp.int32))
    kv_k, kv_v, state = pools.kv_k, pools.kv_v, pools.state
    toks, vals, ids = [], [], []
    for _ in range(2):
        t, emitted, aux, carry, kv_k, kv_v, counted, state = window(
            params, *carry, kv_k, kv_v, pools.table(B), jnp.zeros(B),
            jnp.zeros(B, jnp.int32), jnp.ones(B), jnp.zeros(B, jnp.uint32),
            jnp.full((B, 8), -1, jnp.int32), None, state,
            jnp.asarray([pools.slot, pools.drop], jnp.int32),
            k_steps=K, logprobs_topn=8)
        assert list(np.asarray(emitted)) == [K, 0]
        # the live row's K steps chose 3 experts in each of 10 layers
        assert int(counted[0]) == K * 10 * 3 and 0 < int(counted[1]) < K * 30
        toks += [int(x) for x in t[0]]
        vals += list(np.asarray(aux[1][0]))
        ids += list(np.asarray(aux[2][0]))
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
    """A 37-token prompt prefilled whole, in 2 and in 3 prefill chunks
    (on a multiple of the scan's chunk of 8 and off it): the same last
    logits and the same stored state, carried between the programs
    through the pool; then decode steps through the pool agree with the
    reference too. A second chunk that starts from zeros instead of the
    carried state is visible at this tolerance."""
    cfg = tiny()
    params = make_params(cfg, 1)
    prompt = np.random.default_rng(1).integers(1, 512, 40)
    n = 37
    whole = Pools(cfg)
    want = whole.run_prefill(params, prompt[:n], 0, 64)
    ref = ref_logits(params, cfg, prompt)
    assert np.abs(want - ref[n - 1]).max() < ATOL

    parts = Pools(cfg)
    edges = (0, *cuts, n)
    for a, b in zip(edges, edges[1:]):
        got = parts.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(got - want).max() < ATOL
    for x, y in zip(parts.state, whole.state):
        assert np.abs(np.asarray(x[parts.slot], np.float32)
                      - np.asarray(y[whole.slot], np.float32)
                      ).max() < ATOL
    for at in range(n, 40):                 # prefill, then decode
        got = parts.run_decode(params, int(prompt[at]), at)
        assert np.abs(got - ref[at]).max() < ATOL

    lost = Pools(cfg)
    lost.run_prefill(params, prompt[:cuts[0]], 0, 32)
    lost.state = jax.tree.map(jnp.zeros_like, lost.state)
    for a, b in zip(edges[1:], edges[2:]):
        bad = lost.run_prefill(params, prompt[a:b], a, 32)
    assert np.abs(bad - want).max() > 100 * ATOL


def test_the_check_can_see_the_state_and_its_precision():
    """With the cell's weight scales the carried state matters to the
    logits: zeroing a row's matrix state before a decode step moves them
    by more than the agreement's tolerance (a state that random weights
    forgot within two tokens would let a broken pool pass the cell's
    ``correct``), and a pool rounded to bf16 moves them by more than this
    file's: the comparison is tight enough to tell the state's type."""
    cfg = tiny()
    params = make_params(cfg, 2)
    prompt = np.random.default_rng(2).integers(1, 512, 33)
    want = np.asarray(jax.nn.log_softmax(
        ref_logits(params, cfg, prompt), -1))[-1]

    def step(change):
        pools = Pools(cfg)
        pools.run_prefill(params, prompt[:32], 0, 32)
        ssm, conv = pools.state
        pools.state = (change(ssm), conv)
        got = pools.run_decode(params, int(prompt[32]), 32)
        return np.abs(np.asarray(jax.nn.log_softmax(got)) - want).max()

    assert step(lambda s: s) < ATOL
    assert step(jnp.zeros_like) > AGREE_ATOL
    assert step(lambda s: s.astype(jnp.bfloat16).astype(s.dtype)) > 10 * ATOL


# --------------------------------------------------- the two scan forms


def _scan_operands(rng, B, T, H, P, N):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.5, (B, T, H)), jnp.float32)
    a_neg = -jnp.exp(2.0 * f(H))
    return dt, f(B, T, H, P), f(B, T, N), f(B, T, N), a_neg, f(B, N, H * P)


def _token_by_token(s, dt, x, b, c, a_neg):
    """The recurrence of the module's docstring, a token at a time."""
    B, T, H = dt.shape
    P = x.shape[-1]
    ys = []
    for t in range(T):
        dec = jnp.repeat(jnp.exp(dt[:, t] * a_neg), P, axis=-1)
        dtx = jnp.repeat(dt[:, t], P, axis=-1) * x[:, t].reshape(B, H * P)
        s, y = granite._ssd_step(s, dec, dtx, b[:, t], c[:, t])
        ys.append(y.reshape(B, H, P))
    return s, jnp.stack(ys, axis=1)


@pytest.mark.parametrize("T", [3, 8, 24, 20],
                         ids=["below", "at", "across", "no-multiple"])
def test_the_chunked_form_is_the_per_token_recurrence(T):
    """_ssd_chunk (chunk 8) against the recurrence step by step at
    lengths below, at and across the chunk size and at one that is no
    multiple of it (20 = chunks of 4), from a carried state: y at every
    token and the state after the last. A row whose last tokens do not
    count (dt = 0: padding) ends with the state after its last valid
    token, and a row of padding alone keeps its state bit for bit."""
    H, P, N, B = 4, 8, 16, 3
    dt, x, b, c, a_neg, s0 = _scan_operands(np.random.default_rng(T), B, T,
                                            H, P, N)
    valid = np.ones((B, T), bool)
    valid[1, T - T // 3:] = False           # a short row
    valid[2] = False                        # a row of padding
    dt = jnp.where(jnp.asarray(valid)[..., None], dt, 0.0)
    s, y = granite._ssd_chunk(s0, dt, x, b, c, a_neg, 8)
    want_s, want_y = _token_by_token(s0, dt, x, b, c, a_neg)
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-4
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() < 1e-4
    short_s, _ = _token_by_token(s0[1:2], dt[1:2, :T - T // 3],
                                 x[1:2, :T - T // 3], b[1:2, :T - T // 3],
                                 c[1:2, :T - T // 3], a_neg)
    assert np.abs(np.asarray(s[1]) - np.asarray(short_s[0])).max() < 1e-4
    assert (np.asarray(s[2]) == np.asarray(s0[2])).all()
    # exponents are differences <= 0 only: decays of any size are safe
    big = granite._ssd_chunk(s0, 50.0 * dt, x, b, c, 40.0 * a_neg, 8)
    assert all(np.isfinite(np.asarray(v)).all() for v in big)


# ------------------------------------------- the step kernel on the pool


@pytest.mark.parametrize("slots,still", [
    ((3,), ()),                                         # one row
    ((5, 0, 3, 1, 6, 2, 4, 7), ()),                     # eight, permuted
    ((4, 1, 6), (1,)),                                  # a frozen row
    ((2, 12, 5, 12, 12, 0, 12, 9), (0,)),               # rows on the drop slot
], ids=["one", "eight-permuted", "three-one-frozen", "drop-slot-shared"])
def test_step_kernel_in_the_pool_matches_the_recurrence(slots, still):
    """ops/selective_scan.py ssd_step under interpretation against S_t
    and y_t of the module's docstring, on the published [H, P, N] form:
    the rows' new state and y agree to float32 rounding; a row that does
    not advance (dt = 0: frozen by a stop, or padding on the shared drop
    slot) keeps its state BIT FOR BIT; a call on layer m touches no
    other layer and no slot that no row holds; a row marked fresh starts
    from zeros whatever its slot held."""
    S, M, H, P, N = 13, 3, 4, 32, 16
    C, B = H * P, len(slots)
    rng = np.random.default_rng(B)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool, at = f(S, M, N, C), jnp.asarray(slots, jnp.int32)
    dt = rng.uniform(0.05, 1.0, (B, H))
    dt[[r for r in range(B) if r in still or slots[r] == S - 1]] = 0.0
    dt = jnp.asarray(dt, jnp.float32)
    x, b, c, a_neg = f(B, H, P), f(B, N), f(B, N), -jnp.exp(f(H))
    dec = jnp.repeat(jnp.exp(dt * a_neg), P, axis=-1)
    dtx = jnp.repeat(dt, P, axis=-1) * x.reshape(B, C)
    live = [r for r in range(B) if float(dt[r].max()) > 0]
    idx = np.asarray(slots)

    def published(s_nc, fresh=None):
        """S_t[h] = exp(dt A) S[h] + dt (x[h] outer B); y[h] = S_t[h] C."""
        s = jnp.moveaxis(s_nc.reshape(B, N, H, P), 1, 3)    # [B, H, P, N]
        if fresh is not None:
            s = jnp.where(fresh[:, None, None, None], 0.0, s)
        s = (jnp.exp(dt * a_neg)[:, :, None, None] * s
             + (dt[:, :, None] * x)[..., None] * b[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", s, c)
        return jnp.moveaxis(s, 3, 1).reshape(B, N, C), y.reshape(B, C)

    for m in (1, 2):                    # two layers of ONE pool, in turn
        before = np.asarray(pool)
        want_s, want_y = published(pool[at, m])
        pool, y = ssd_step(pool, at, jnp.int32(m), dec, dtx, b, c,
                           interpret=True)
        got = np.asarray(pool)
        assert np.abs(np.asarray(y) - np.asarray(want_y))[live].max() < 1e-4
        assert np.abs(got[idx[live], m]
                      - np.asarray(want_s)[live]).max() < 1e-5
        assert np.abs(got[idx[live], m] - before[idx[live], m]).max() > 1e-3
        for r in set(range(B)) - set(live):
            assert (got[idx[r], m] == before[idx[r], m]).all()
        others = [k for k in range(M) if k != m]
        assert (got[:, others] == before[:, others]).all()
        unheld = sorted(set(range(S)) - set(slots))
        assert (got[unheld] == before[unheld]).all()
    fresh = jnp.arange(B) == 0
    want_s, want_y = published(pool[at, 0], fresh)
    pool, y = ssd_step(pool, at, jnp.int32(0), dec, dtx, b, c, fresh,
                       interpret=True)
    assert np.abs(np.asarray(y[0]) - np.asarray(want_y[0])).max() < 1e-4
    assert np.abs(np.asarray(pool[at[0], 0])
                  - np.asarray(want_s[0])).max() < 1e-5


@pytest.mark.parametrize("program", ["window", "decode_step"])
def test_the_kernel_arm_never_gathers_or_scatters_the_state_pool(
        program, monkeypatch):
    """The traced program, not its timing (tests/test_jamba.py's walk):
    with the kernel arm on, no gather / scatter / dynamic_slice /
    dynamic_update_slice has an operand of the matrix-state pool's
    shape, no value has the gathered rows' shape, and every kernel call
    takes the pool as an operand that IS one of its results. On the XLA
    arm the same walk finds the gather and the scatter."""
    from tests.test_sampling_topk import _eqns

    cfg = tiny()
    S, B, K = 5, 2, 4
    pool_shape = (S, granite.num_mamba_layers(cfg), cfg.mamba_d_state,
                  cfg.mamba_d_inner)
    rows_shape = (B,) + pool_shape[1:]
    params = jax.eval_shape(
        lambda: granite.init_params(cfg, jax.random.PRNGKey(0)))
    kv_k, kv_v = jax.eval_shape(
        lambda: granite.init_kv_cache(cfg, KVCacheSpec(16, PS)))
    state = jax.eval_shape(lambda: granite.init_state(cfg, S))
    s = jax.ShapeDtypeStruct
    i32, f32 = s((B,), jnp.int32), s((B,), jnp.float32)

    def trace(interpret):
        if program == "window":
            fn = granite.make_decode_window_fn(cfg, True, 64,
                                               pallas_interpret=interpret)
            return jax.make_jaxpr(partial(fn, k_steps=K, logprobs_topn=0))(
                params, i32, i32, s((B,), jnp.bool_), i32, i32, kv_k, kv_v,
                s((B, 8), jnp.int32), f32, i32, f32, s((B,), jnp.uint32),
                s((B, 8), jnp.int32), None, state, i32)
        _, fn = granite.make_step_fns(cfg)
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
    runs = sum(seg[0] == "mamba" for seg in granite.segments(cfg))
    assert len(kernels) == runs * (K if program == "window" else 1)
    for operand, eqn in kernels:
        aliases = dict(eqn.params["input_output_aliases"])
        assert operand in aliases
        assert eqn.outvars[aliases[operand]].aval.shape == pool_shape


# ------------------------------------- the chip's share of the experts


def _share(cfg_hf, params, first, held):
    """The configuration and parameters of the chip that holds experts
    [first, first + held) of an uncut layer's."""
    cfg = tiny(**cfg_hf, num_local_experts=held, first_local_expert=first)
    cut = dict(params)
    for name in granite.EXPERT_KEYS:
        cut[name] = params[name][:, first:first + held]
    return cfg, cut


@pytest.mark.parametrize("tokens,is_sorted",
                         [(24, False), (256, True)],
                         ids=["dense", "sorted"])
def test_the_share_adds_up(tokens, is_sorted):
    """The guide's test of the cut (section 4): at the small size, the
    routed parts that share 0 (experts 0-5) and share 1 (experts 6-11)
    compute, plus the shared expert counted once, equal what the UNCUT
    reference gives for the whole layer; in both execution forms (24
    tokens run dense-over-experts, 256 the sorted dispatch: a PB 1 x T
    256 chunk's rows, the first bucket past the chip's ridge, 288 before
    PR 66 moved the rule's edge there), with
    padding rows that count for nothing; and each share's program equals
    the reference given the same share."""
    uncut = tiny(num_local_experts=12, router_num_experts=12)
    params = make_params(uncut, 3)
    assert params["w_gate"].shape[1] == 12
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, tokens // 2,
                                                          64)), jnp.float32)
    valid = jnp.ones(h.shape[:2], bool).at[1, -5:].set(False)
    l = 3
    r = uncut.residual_multiplier

    def norm(x, w):
        return llama.rms_norm(x, w.astype(jnp.float32), uncut.rms_norm_eps)

    def program(cfg, p):            # r * (routed held + shared)
        out = granite._moe_ff(p, cfg, norm, h, jnp.int32(l), valid)[0] - h
        return jnp.where(valid[..., None], out, 0.0)    # padding: nothing

    def reference(cfg, p):
        with jax.default_matmul_precision("highest"):
            out = jnp.stack([REF._experts(cfg, p, row, l) for row in h]) - h
        return jnp.where(valid[..., None], out, 0.0)

    assert llama._moe_use_blocked(None, tokens, 6, 3) is is_sorted
    whole = reference(uncut, params)
    parts = []
    for first in (0, 6):
        cfg, cut = _share({}, params, first, 6)
        assert granite.held_first(cfg) == first
        got = program(cfg, cut)
        assert np.abs(np.asarray(got - reference(cfg, cut))).max() < ATOL
        parts.append(got)
    # the shared expert alone: a share that holds no routed pair's expert
    x = norm(h, params["ln_mlp"][l])
    shared = r * (jax.nn.silu(x @ params["w_gate_s"][l])
                  * (x @ params["w_up_s"][l])) @ params["w_down_s"][l]
    total = parts[0] + parts[1] - jnp.where(valid[..., None], shared, 0.0)
    assert np.abs(np.asarray(total - whole)).max() < ATOL
    assert np.abs(np.asarray(parts[0] - whole)).max() > 100 * ATOL
    assert np.abs(np.asarray(program(uncut, params) - whole)).max() < ATOL


@pytest.mark.parametrize("blocked", [False, True], ids=["dense", "sorted"])
def test_expert_forms_with_every_expert_absent_and_every_expert_present(
        blocked):
    """llama.moe_experts told which experts it holds (``first`` 4 of a
    router of 12, 5 held: experts 4-8), in both execution forms: a token
    all of whose experts are absent gets zeros, one all of whose experts
    are present gets the whole sum, a mixed one its held part, each
    against a loop over the pairs by hand; a padding row gets zeros in
    the sorted form. ``first`` None is the layer that holds everything
    (today's programs: nothing subtracted, nothing masked)."""
    rng = np.random.default_rng(5)
    D, I, E, k, first = 16, 8, 5, 3, 4
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    wg, wu, wd = f(E, D, I), f(E, D, I), f(E, I, D)
    idx = jnp.asarray([[[0, 11, 9], [4, 8, 6], [5, 2, 7], [3, 4, 10]]],
                      jnp.int32)                    # none / all / 2 / 1
    w = jax.nn.softmax(f(1, 4, k), -1)
    x = f(1, 4, D)
    live = jnp.asarray([[True, True, True, True]])
    got = llama.moe_experts(x, w, idx, wg, wu, wd, blocked, live=live,
                            first=first)
    want = np.zeros((4, D), np.float32)
    for t in range(4):
        for j in range(k):
            e = int(idx[0, t, j]) - first
            if 0 <= e < E:
                y = (jax.nn.silu(x[0, t] @ wg[e]) * (x[0, t] @ wu[e])) @ wd[e]
                want[t] += float(w[0, t, j]) * np.asarray(y)
    assert np.abs(np.asarray(got[0]) - want).max() < ATOL
    assert (np.asarray(got[0, 0]) == 0).all()
    assert np.abs(want[1]).max() > 0.1
    if blocked:
        dead = llama.moe_experts(x, w, idx, wg, wu, wd, True,
                                 live=live.at[0, 1].set(False), first=first)
        assert (np.asarray(dead[0, 1]) == 0).all()
        assert np.abs(np.asarray(dead[0, 2]) - want[2]).max() < ATOL
    every = llama.moe_experts(x, w, jnp.clip(idx - first, 0, E - 1), wg, wu,
                              wd, blocked, live=live)
    same = llama.moe_experts(x, w, jnp.clip(idx - first, 0, E - 1) + first,
                             wg, wu, wd, blocked, live=live, first=first)
    assert np.abs(np.asarray(every - same)).max() < 1e-6


# ------------------------------------------------------ through JaxEngine


def _engine(cfg=None, **over) -> JaxEngine:
    base = dict(page_size=PS, num_pages=64, max_batch=4, prefill_chunk=16,
                batch_buckets=(4,), prefill_buckets=(16,),
                page_buckets=(16,), max_prefill_batch=2, decode_steps=4,
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


def test_generate_matches_reference_and_interleaving_changes_nothing(
        run_async):
    """A 37-token prompt crosses three prefill chunks of 16 and then four
    windows, through JaxEngine, the page manager and the state pool: the
    engine's top-5 log-probabilities agree with the reference at every
    position; two sequences interleaved give what each gives alone; the
    pool's slots are counted, and the window counts the pairs the router
    chose beside those that lay in the held range."""
    eng = _engine()
    assert isinstance(eng.state, tuple) and eng.state[0].shape[1:] == (
        9, 16, 128)
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
    # every decoded row-step chose 3 experts in each of 10 layers; about
    # half of them lie among the 6 held of 12
    routed, held = (stats["moe_pairs_routed_total"],
                    stats["moe_pairs_held_total"])
    assert routed == 2 * (12 + 8) * 10 * 3
    assert 0.3 * routed < held < 0.7 * routed


def test_a_prefix_hit_counts_as_a_miss(run_async):
    """No prefix hit for a model whose state has no snapshot (36 MiB a
    row at the published widths): the second request computes every
    prompt token again and answers alike; no page is ever published."""
    eng = _engine()
    assert not eng.pm.prefix_reuse
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


def test_warmup_covers_the_serving_forms(run_async):
    """warmup() goes through the same helpers as serving: nothing
    compiles after it."""
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


# ------------------------------------------------------------- refusals


def _refused(what):
    return pytest.raises(NotImplementedError,
                         match=f"{what}.*recurrent state")


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
    """The three classes that move KV pages between places refuse an
    engine of THIS module (a real one: its state pool is what they look
    at), as they refuse Jamba's."""
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


def test_jamba_keeps_its_blocks():
    """jamba.py's own programs run on its own blocks by default: the
    second family is an argument, not a flag."""
    from dynamo_tpu.ops.selective_scan import selective_scan_step

    assert jamba.MAMBA1.mixer is jamba._mamba
    assert jamba.MAMBA1.step is selective_scan_step
    assert jamba.MAMBA1.counts == ()
    assert granite.BLOCKS.step is ssd_step
    assert granite.BLOCKS.counts == granite.WINDOW_COUNTS
