"""Phi-4-mini-flash (models/phi4flash.py: a self-decoder of Mamba-1 and
differential window attention, ONE full-attention layer whose K/V pages
the cross layers read, gated memory units that reuse a Mamba layer's scan
output; a sequence that owns a state slot AND pages of two pools) against
its plain reference
(benchmark/configs/phi-4-mini-flash-reasoning/reference.py), through
``JaxEngine.generate``, on the CPU at a small size: float32, hidden 64,
12 layers by the family's rule = 3 x (Mamba, window), (Mamba that hands
down m, full), 2 x (memory unit, cross), so that two cross layers read
one pool; 8 / 4 heads of 8, state 8, a window of 8, pages of 4, prefill
chunks of 8, non-zero biases and lambdas, tied embeddings.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in pages, chunks, time blocks and windows with an
online softmax, the reference over the whole sequence at once, token by
token), so log-probabilities of magnitude ~6 differ by about 1e-5;
ATOL = 1e-4 leaves room and is far under what anything systematic
moves: every fault of the reference's ``FAULTS`` reads 1 and more (the
tests that provoke them ask for 100 x ATOL)."""

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
from dynamo_tpu.models import jamba, llama, phi4flash, registry
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "phi-4-mini-flash-reasoning")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ATOL = 1e-4
WINDOW, PS, CHUNK = 8, 4, 8
SLOTS = 5       # ceil((8 + 8) / 4) + 1


def _reference():
    spec = importlib.util.spec_from_file_location(
        "phi4flash_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny_hf(**over) -> dict:
    hf = dict(model_type="phi4flash", vocab_size=512, hidden_size=64,
              intermediate_size=128, num_hidden_layers=12,
              num_attention_heads=8, num_key_value_heads=4,
              sliding_window=WINDOW, layer_norm_eps=1e-5, mb_per_layer=2,
              tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
              embd_pdrop=0, resid_pdrop=0, mamba_d_state=8, mamba_dt_rank=4)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    return cfg


def _params(cfg):
    """init_params with what it draws as zeros or small made large enough
    to matter: every bias N(0, 0.1) (b_dt keeps the published init), the
    lambdas x3 (lam - lam0 of 0.1-0.6 a layer), rows of the embedding of
    unit RMS."""
    rng = np.random.default_rng(0)
    p = phi4flash.init_params(cfg, jax.random.PRNGKey(0))
    for k, v in p.items():
        if k.startswith("b") and k != "b_dt":
            p[k] = v + 0.1 * jnp.asarray(rng.standard_normal(v.shape),
                                         v.dtype)
        elif k in ("lq1", "lk1", "lq2", "lk2"):
            p[k] = v * 3
    p["embed"] = p["embed"] * 8
    return p


CFG = tiny()
PARAMS = _params(CFG)


@pytest.fixture(autouse=True, scope="module")
def _one_trace_a_program():
    """Engines of one configuration share their jitted programs in this
    file; the module is restored afterwards."""
    made, sound = {}, {}

    def shared(name):
        make = sound[name] = getattr(phi4flash, name)

        def cached(cfg, *args, **kw):
            key = (name, id(cfg), args, tuple(sorted(kw.items())),
                   os.environ.get("DYN_PALLAS_INTERPRET"))
            if key not in made:
                made[key] = make(cfg, *args, **kw)
            return made[key]

        setattr(phi4flash, name, cached)

    shared("make_step_fns")
    shared("make_decode_window_fn")
    yield
    for name, make in sound.items():
        setattr(phi4flash, name, make)


def _engine(cfg=CFG, params=PARAMS, **over) -> JaxEngine:
    ecfg = dict(page_size=PS, num_pages=64, max_batch=4,
                prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
                batch_buckets=(1, 4), page_buckets=(32,), decode_steps=2,
                max_prefill_batch=2, warmup_logprobs=False)
    ecfg.update(over)
    return JaxEngine(cfg, EngineConfig(**ecfg), params=params, seed=0)


def _req(prompt, n, logprobs=None):
    return PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
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


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def ref_logprobs(prompt, toks, params=PARAMS, cfg=CFG, **kw):
    """Reference log-probabilities at the positions the engine sampled
    from, teacher-forced on its tokens: [len(toks), V]."""
    with jax.default_matmul_precision("highest"):
        logits = REF.reference_logits(params, cfg, prompt + toks[:-1],
                                      last=len(toks), **kw)
    return np.asarray(jax.nn.log_softmax(logits, -1))


def gap(want, tops) -> float:
    """The largest |d logprob| over the engine's top-20 at any position."""
    return max(abs(want[j][i] - v) for j, top in enumerate(tops)
               for i, v in top.items())


def _run(run_async, engine, prompt, n):
    async def main():
        out = await _gen(engine, prompt, n, logprobs=20)
        stats = engine.stats()
        await engine.stop()
        return out, stats

    return run_async(main())


def _all_back(eng, stats):
    """Nothing of a sequence is left in any of the three places."""
    assert stats["kv_active_blocks"] == 0
    assert stats["kv_window_active_blocks"] == 0
    assert stats["kv_window_reserved_blocks"] == 0
    assert stats["state_slots_active"] == 0
    assert sorted(eng._state_free) == list(range(eng.ecfg.max_batch))


# ---------------------------------------------------------- configuration


def test_from_hf_config_on_the_catalog_config():
    """The published keys as they are: the kinds by the family's rule,
    the Mamba sizes from the family's defaults, one record."""
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        hf = json.load(f)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert {k for k in row["config"] if row["config"][k] != hf.get(k)
                } == {"tie_word_embeddings"}
    cfg = ModelConfig.from_hf_config(hf)
    fam = registry.family_of(cfg)
    assert fam.name == "phi4flash" and fam.module is phi4flash
    assert fam.init_state is not None and fam.pool_by_kind
    assert fam.cross_on_last and not fam.window_counts
    of = phi4flash.kinds(32)
    assert [of.count(k) for k in ("mamba", "window", "full", "gmu",
                                  "cross")] == [9, 8, 1, 7, 7]
    assert of[16] == "mamba" and of[17] == "full" and of[18] == "gmu" \
        and of[19] == "cross" and of[15] == "window" and of[31] == "cross"
    assert phi4flash.counts(cfg) == (8, 7)
    assert cfg.window_layer_ids == (1, 3, 5, 7, 9, 11, 13, 15)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.sliding_window) == (40, 20, 64, 512)
    assert cfg.attn_scale == 0.125 and cfg.rms_norm_eps == 1e-5
    assert not cfg.tie_word_embeddings and cfg.kv_pool_by_kind
    assert float(phi4flash.lam0(0)) == pytest.approx(0.2)
    assert float(phi4flash.lam0(17)) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1))
    shapes = jax.eval_shape(
        lambda: phi4flash.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for k, s in shapes.items()
            if k != "lm_head")
    assert 3.84e9 < n < 3.86e9          # the name's 3.8B, head tied


@pytest.mark.parametrize("over, said", [
    (dict(mb_per_layer=3), "mb_per_layer 3"),
    (dict(num_hidden_layers=14), "num_hidden_layers 14"),
    (dict(mlp_bias=True), "mlp_bias true"),
    (dict(lm_head_bias=True), "lm_head_bias true"),
    (dict(resid_pdrop=0.1), "resid_pdrop 0.1"),
    (dict(embd_pdrop=0.1), "embd_pdrop 0.1"),
    (dict(num_key_value_heads=1), "8 / 1 heads"),
    (dict(sliding_window=None), "no sliding_window"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_reader_does_not_compute_is_refused_by_name(over, said):
    with pytest.raises(NotImplementedError, match="phi4flash with " + said):
        ModelConfig.from_hf_config(tiny_hf(**over))


def test_every_refusal_of_either_column_is_this_familys():
    """REFUSALS refuses this family what the state column OR the
    pool-by-kind column refuses, in those columns' own sentences."""
    fam = registry.family_of(CFG)
    for feature, (_, whys) in registry.REFUSALS.items():
        said = fam.refusal(feature)
        assert (said is not None) == bool({"state", "pool_by_kind"}
                                          & set(whys)), feature
    assert "models/phi4flash.py" in registry.CAPABILITIES["state"][1]
    assert "recurrent state" in fam.refusal("host_pages")
    assert "K/V pool of their own" in fam.refusal("long_prefill_threshold")


@pytest.mark.parametrize("over, what", [
    (dict(host_pages=8), "host KV tier"),
    (dict(spec_decode=True), "spec_decode"),
])
def test_the_engine_refuses_what_it_does_not_build(over, what):
    with pytest.raises(NotImplementedError, match=what):
        _engine(**over)


# ------------------------------------------------- engine = the reference


@pytest.fixture(scope="module")
def sound():
    """One run past the window: a prompt of 37 tokens in five chunks of
    8 (every chunk edge crossed, pages of the window layers given back
    between chunks), then 12 tokens through windows of 2 steps."""
    eng = _engine()
    prompt = _prompt(0, 37)
    (toks, tops), stats = _run(asyncio.run, eng, prompt, 12)
    return eng, prompt, toks, tops, stats


def test_generate_matches_the_reference_past_the_window(sound):
    eng, prompt, toks, tops, stats = sound
    assert len(toks) == 12 == len(tops)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    assert stats["kv_window_pages_released_total"] > 0
    assert stats["state_slots_held_total"] > 0
    _all_back(eng, stats)


@pytest.mark.parametrize("n_prompt", [5, WINDOW, 2 * WINDOW + 3],
                         ids=["under", "1x", "2x"])
def test_other_lengths_match_too(run_async, n_prompt):
    eng = _engine()
    prompt = _prompt(n_prompt, n_prompt)
    (toks, tops), stats = _run(run_async, eng, prompt, 6)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    _all_back(eng, stats)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_control_fails(sound, fault):
    """The same engine output against the reference with ONE thing
    computed wrong: the window ignored, lam = lam0, the pair norm left
    out, m taken after the gate, m of the previous position, the cross
    layers held to a window, Jamba's inner norms, a2 from k1."""
    _, prompt, toks, tops, _ = sound
    assert gap(ref_logprobs(prompt, toks, fault=fault), tops) > 100 * ATOL


def test_the_tolerance_sees_bf16_where_float32_is_stated(sound):
    _, prompt, toks, tops, _ = sound
    rounded = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), PARAMS)
    assert gap(ref_logprobs(prompt, toks, params=rounded), tops) > 100 * ATOL


def test_a_window_and_a_prefill_an_iteration_is_the_reference_too(run_async):
    """``prefill_token_budget`` (the cell's policy: every iteration ships
    a decode window AND a prefill trimmed to the budget): rows that
    arrive together are prefilled a chunk an iteration while the earlier
    ones decode, through both pools and the state pool."""
    eng = _engine(prefill_token_budget=CHUNK)
    prompts = [_prompt(20 + i, n) for i, n in enumerate((37, 21, 9))]

    async def main():
        got = await asyncio.gather(*(_gen(eng, p, 10, 20) for p in prompts))
        stats = eng.stats()
        await eng.stop()
        return got, stats

    got, stats = run_async(main())
    for p, (toks, tops) in zip(prompts, got):
        assert len(toks) == 10
        assert gap(ref_logprobs(p, toks), tops) < ATOL
    assert eng.mixed_dispatches > 0
    _all_back(eng, stats)


def test_the_single_step_arm_is_the_reference_too(run_async):
    """decode_steps = 1 (``decode_step``) through both pools and the
    state."""
    eng = _engine(decode_steps=1)
    prompt = _prompt(11, 29)
    (toks, tops), stats = _run(run_async, eng, prompt, 9)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    assert stats["kv_window_pages_released_total"] > 0
    _all_back(eng, stats)


def test_the_kernels_read_both_pools_and_the_state_pool(run_async,
                                                        monkeypatch):
    """The Pallas arms (interpreted): the prefill and decode attention
    kernels at the paired head size on each kind's pool, the cross
    layers' reads of the full layer's pages, the scan step and the conv
    tails advanced in the pool."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    eng = _engine()
    prompt = _prompt(12, 2 * WINDOW + 5)
    (toks, tops), stats = _run(run_async, eng, prompt, 6)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    _all_back(eng, stats)


def test_an_untied_head_is_read_where_there_is_one(run_async):
    cfg = tiny(tie_word_embeddings=False)
    params = _params(cfg)
    assert "lm_head" in params
    eng = _engine(cfg, params)
    prompt = _prompt(13, 19)
    (toks, tops), _ = _run(run_async, eng, prompt, 4)
    assert gap(ref_logprobs(prompt, toks, params, cfg), tops) < ATOL


def _chunk_operands(cfg, B, T, n):
    """A first chunk of n tokens a row in a program of [B, T]."""
    spec = llama.KVCacheSpec(16, PS)
    kv_k, kv_v = phi4flash.init_kv_cache(cfg, spec)
    wkv = phi4flash.init_window_kv_cache(cfg, spec)
    state = phi4flash.init_state(cfg, B + 1)
    rng = np.random.default_rng(3)
    tokens = np.zeros((B, T), np.int32)
    pos = np.full((B, T), -1, np.int32)
    table = np.zeros((B, 4), np.int32)
    for b in range(B):
        tokens[b, :n[b]] = rng.integers(1, 500, n[b])
        pos[b, :n[b]] = np.arange(n[b])
        table[b] = 1 + 4 * b + np.arange(4)
    slots = np.where(pos >= 0, table[:, :1] * PS + np.maximum(pos, 0) % PS
                     + (np.maximum(pos, 0) // PS) * PS, 2 ** 30)
    return (jnp.asarray(tokens), jnp.asarray(pos), kv_k, kv_v,
            jnp.asarray(table), jnp.asarray(slots, jnp.int32),
            jnp.asarray(np.asarray(n) - 1, jnp.int32), None,
            (wkv, state),
            ((jnp.asarray(table), jnp.zeros(B, jnp.int32),
              jnp.asarray(slots, jnp.int32)), jnp.arange(B, dtype=jnp.int32)))


def test_the_cross_half_on_one_position_is_the_cross_half_on_all():
    """The shortcut is exact: logits with layers past the full layer run
    on every position of the chunk equal, at each row's last position,
    those with them run on that position alone. To float32 rounding, not
    bit for bit: the two programs' products have other shapes ([B, T, D]
    against [B, 1, D]) and XLA tiles their sums differently."""
    one, _ = phi4flash.make_step_fns(CFG)

    @jax.jit
    def every(params, tokens, positions, kv_k, kv_v, table, slots, last_idx,
              page_slots, pools, places):
        h = phi4flash.forward(params, CFG, tokens, positions, kv_k, kv_v,
                              table, slots, last_idx, pools, places,
                              page_slots=page_slots, cross_all=True)[0]
        return llama.logits_at(params, CFG, h, last_idx)

    got = one(PARAMS, *_chunk_operands(CFG, 2, 8, [8, 5]))[0]
    want = every(PARAMS, *_chunk_operands(CFG, 2, 8, [8, 5]))
    assert got.shape == want.shape == (2, CFG.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 1.0


def test_jambas_mixer_keeps_its_norms_and_hands_nothing_down():
    """jamba._mamba with Jamba's leaves is what it was (its three norms
    applied, three results); without them (this family's leaves) dt_r, B
    and C go on as they are, and ``hand_down`` adds the scan output."""
    cfg = tiny()
    rng = np.random.default_rng(1)
    mp = {k: v[0] for k, v in PARAMS.items() if k in phi4flash.MAMBA_KEYS}
    u = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.float32)
    valid = jnp.ones((2, 4), bool)
    s = jnp.zeros((2, 8, 128), jnp.float32)
    tail = jnp.zeros((2, 3 * 128), jnp.float32)
    out, s1, t1, y = jamba._mamba(cfg, mp, u, valid, s, tail,
                                  hand_down=True)
    assert y.shape == (2, 4, 128) and y.dtype == jnp.float32
    plain = jamba._mamba(cfg, mp, u, valid, s, tail)
    assert len(plain) == 3
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(out))
    normed = dict(mp, dt_norm=jnp.ones(4), ssm_b_norm=jnp.ones(8),
                  ssm_c_norm=jnp.ones(8))
    other = jamba._mamba(cfg, normed, u, valid, s, tail)[0]
    assert float(jnp.max(jnp.abs(other - out))) > 1e-3


# ------------------------------------------------------ the three places


def test_the_books_show_the_architecture(sound):
    """ONE layer of K/V a token of context in the full pool, the window
    pool bounded by rows x (window + chunk) x the window layers, a state
    slot a row + the drop slot, and one stats() with both sets of keys;
    of the prompt's five prefill programs the one that ended it ran the
    cross half, on 1 position in 8, and the four before on none."""
    eng, *_, stats = sound
    H, KV, hd = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim_
    per_token = (eng.kv_k.nbytes + eng.kv_v.nbytes) / (64 * PS)
    assert per_token == 2 * KV * hd * 4          # one layer's K and V
    assert eng.kv_k.shape == (1, 64, KV // 2, PS, 2 * hd)
    assert eng.wpm.table_slots == SLOTS
    assert eng.wkv[0].shape == (3, 4 * SLOTS + 1, KV // 2, PS, 2 * hd)
    assert eng.state[0].shape == (5, 4, 8, 128)
    assert eng.state[1].shape == (4, 5, 3 * 128)
    for key in ("state_slots_total", "state_slots_held_total",
                "state_pool_bytes", "kv_window_total_blocks",
                "kv_window_pages_held_total",
                "kv_window_pages_released_total",
                "decode_row_steps_past_window_total", "self_rows_total",
                "cross_rows_total"):
        assert key in stats, key
    assert stats["self_rows_total"] == 5 * CHUNK * stats["cross_rows_total"] > 0
    assert stats["prefill_logits_skipped_total"] == 4
    assert stats["prefill_dispatches_total"] == 5
    assert stats["state_slots_total"] == 4


def test_rows_admitted_preempted_and_finished_return_everything(run_async):
    """The full layer's pool runs out under rows of mixed lengths: the
    newer long one is preempted (slot, pages of both pools and its
    reservation released), prefills again from position 0 and answers
    what it answers alone; afterwards nothing is held anywhere."""
    a, b, c = _prompt(7, 40), _prompt(8, 44), _prompt(9, 6)
    alone = _engine()
    (want, _), _ = _run(run_async, alone, b, 24)
    eng = _engine(num_pages=33, watermark_pages=0)
    preempted = []
    grow = eng._grow_or_preempt

    def spy(batch, lookahead):
        before = {id(s) for s in eng.running}
        grow(batch, lookahead)
        preempted.extend(s for s in eng.waiting if id(s) in before)

    eng._grow_or_preempt = spy

    async def main():
        got = await asyncio.gather(_gen(eng, a, 24, 20), _gen(eng, b, 24),
                                   _gen(eng, c, 5, 20))
        stats = eng.stats()
        await eng.stop()
        return got, stats

    ((ta, a_tops), (tb, _), (tc, c_tops)), stats = run_async(main())
    assert preempted, "the pool was meant to run out"
    assert all(s.state_slot is None for s in preempted)
    assert tb == want and len(ta) == 24 and len(tc) == 5
    assert gap(ref_logprobs(a, ta), a_tops) < ATOL
    assert gap(ref_logprobs(c, tc), c_tops) < ATOL
    _all_back(eng, stats)


@pytest.mark.parametrize("pool", ["window", "full", "state"])
def test_any_of_the_three_alone_defers_admission_and_nothing_deadlocks(
        run_async, pool):
    """Three long rows and ONE place that holds one of them (a window
    pool of one row's pages, a full pool of one row's pages, one state
    slot): the others wait for admission, a row that waits for a window
    page holds no state slot while it waits, and every one is answered
    as alone."""
    prompts = [_prompt(10 + i, 36) for i in range(3)]
    alone = _engine()

    async def each():
        out = [await _gen(alone, p, 8) for p in prompts]
        await alone.stop()
        return out

    want = [t for t, _ in run_async(each())]
    over = {"window": dict(window_pages=SLOTS + 1),
            "full": dict(num_pages=14, watermark_pages=0),
            "state": dict(max_batch=1, batch_buckets=(1,),
                          max_prefill_batch=1)}[pool]
    eng = _engine(**over)
    most, slots_out = [], []
    admit = eng._admit_waiting

    def spy():
        admit()
        most.append(len(eng.prefilling) + len(eng.running))
        slots_out.append(eng.ecfg.max_batch - len(eng._state_free))

    eng._admit_waiting = spy

    async def main():
        got = await asyncio.wait_for(
            asyncio.gather(*(_gen(eng, p, 8) for p in prompts)), 120)
        stats = eng.stats()
        await eng.stop()
        return got, stats

    got, stats = run_async(main())
    assert [t for t, _ in got] == want
    assert max(most) == 1, "one row's worth admits one row"
    assert max(slots_out) == 1, "a waiting row holds no state slot"
    _all_back(eng, stats)


def test_warmup_covers_the_serving_forms(run_async):
    """warmup() builds the paired operands as serving does: nothing
    compiles after it, in either decode arm."""
    for steps in (2, 1):
        eng = _engine(decode_steps=steps)
        eng.warmup()

        async def main(eng=eng):
            toks, _ = await _gen(eng, _prompt(9, 21), 7)
            stats = eng.stats()
            await eng.stop()
            return toks, stats

        toks, stats = run_async(main())
        assert len(toks) == 7 and stats["post_warmup_compiles_total"] == 0


def test_a_prefix_hit_cannot_happen(run_async):
    """State: a prefix hit counts as a miss; the same prompt twice
    prefills twice and publishes nothing."""
    eng = _engine()
    assert not eng.pm.prefix_reuse
    prompt = _prompt(14, 20)

    async def main():
        a = await _gen(eng, prompt, 4)
        b = await _gen(eng, prompt, 4)
        stats = eng.stats()
        await eng.stop()
        return a, b, stats

    (ta, _), (tb, _), stats = run_async(main())
    assert ta == tb and stats["prefix_hit_tokens_total"] == 0
