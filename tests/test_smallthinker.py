"""SmallThinker (window layers with RoPE beside full layers without
positions, a K/V pool a kind of layer, a router on the layer's input,
ReGLU experts: models/llama.py ``_forward_by_kind``, engine/kv_manager.py
``WindowPagePool``) against its plain reference
(benchmark/configs/smallthinker-21b-a3b/reference.py), through
``JaxEngine.generate``, on the CPU at a small size: float32, hidden 64,
8 layers in two periods of (full, window, window, window), 4 / 2 heads
of 16, 8 experts top-2 of width 32, a window of 16, pages of 4, prefill
chunks of 8: a table of 7 slots into the window layers' pool.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in pages, chunks and windows with an online softmax,
the reference over the whole sequence at once), so log-probabilities of
magnitude ~6 differ by a few 1e-6 (5e-6 at 80 tokens); ATOL = 1e-4
leaves room and is far under what anything systematic moves: the
reference on weights rounded to bf16 reads 1e-2 and more, a window
ignored, a page given back early or the router moved behind the norm
1e-3 and more (the tests that provoke them)."""

import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.kv_manager import WindowPagePool
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import llama, registry
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.runtime.engine import Context
from tools.smallthinker_long_context_check import (early_give_back,
                                                   window_ignored)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "smallthinker-21b-a3b")
ATOL = 1e-4
WINDOW, PS, CHUNK = 16, 4, 8
SLOTS = 7       # ceil((16 + 8) / 4) + 1


def _reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
with open(os.path.join(CONFIG_DIR, "about.json")) as _f:
    ABOUT = json.load(_f)


def tiny_hf(**over) -> dict:
    hf = dict(model_type="smallthinker", vocab_size=512, hidden_size=64,
              num_hidden_layers=8, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16,
              moe_num_primary_experts=8, moe_num_active_primary_experts=2,
              moe_ffn_hidden_size=32, sliding_window_size=WINDOW,
              sliding_window_layout=[0, 1, 1, 1] * 2,
              rope_layout=[0, 1, 1, 1] * 2, rope_theta=10000.0,
              rms_norm_eps=1e-6, tie_word_embeddings=False,
              moe_primary_router_apply_softmax=True, norm_topk_prob=True)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    return cfg


CFG = tiny()
# the cell's weights at this size: the harness's rule and the
# configuration's scales (the embedding's follows the vocabulary: unit
# RMS a token)
PARAMS = weights.build_tree(
    llama, CFG, weights.seed_key(0),
    dict(ABOUT["weight_scales"], embed=float(np.sqrt(CFG.vocab_size))))


@pytest.fixture(autouse=True, scope="module")
def _one_trace_a_program():
    """Engines of one configuration share their jitted programs in this
    file (an engine builds its own, and two dozen engines would compile
    the same programs two dozen times); llama is restored afterwards."""
    made, sound = {}, {}

    def shared(name):
        make = sound[name] = getattr(llama, name)

        def cached(cfg, *args, **kw):
            key = (name, id(cfg), args, tuple(sorted(kw.items())),
                   os.environ.get("DYN_PALLAS_INTERPRET"))
            if key not in made:
                made[key] = make(cfg, *args, **kw)
            return made[key]

        setattr(llama, name, cached)

    shared("make_step_fns")
    shared("make_decode_window_fn")
    yield
    for name, make in sound.items():
        setattr(llama, name, make)


def _engine(cfg=CFG, **over) -> JaxEngine:
    ecfg = dict(page_size=PS, num_pages=64, max_batch=4,
                prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
                batch_buckets=(1, 4), page_buckets=(32,), decode_steps=2,
                max_prefill_batch=2, warmup_logprobs=False)
    ecfg.update(over)
    return JaxEngine(cfg, EngineConfig(**ecfg), params=PARAMS, seed=0)


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


# ---------------------------------------------------------- configuration


def test_from_hf_config_on_the_catalog_config():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert cfg.model_type == "smallthinker" and cfg.kv_pool_by_kind
    assert cfg.num_layers == 8 and cfg.sliding_window == 4096
    assert cfg.layer_window == (None, 4096, 4096, 4096) * 2
    assert cfg.layer_rope == (False, True, True, True) * 2
    assert cfg.full_layer_ids == (0, 4)
    assert cfg.window_layer_ids == (1, 2, 3, 5, 6, 7)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.intermediate_size) == (64, 6, 768)
    assert cfg.moe_early_router and cfg.hidden_act == "relu"
    assert llama.layer_period(cfg) == 4
    assert llama.window_table_slots(cfg, 64, 512) == 73
    k, _ = jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, llama.KVCacheSpec(5632, 64)))
    w, _ = jax.eval_shape(lambda: llama.init_window_kv_cache(
        cfg, llama.KVCacheSpec(3520, 64)))
    assert k.shape == (2, 5632, 4, 64, 128)
    assert w.shape == (6, 3520, 4, 64, 128)


@pytest.mark.parametrize("over, said", [
    (dict(rope_layout=[1, 1, 1, 1] * 2), "sliding_window_layout != rope"),
    (dict(sliding_window_layout=[0, 1, 1]), "num_hidden_layers = 8"),
    (dict(sliding_window_layout=[1] * 8, rope_layout=[1] * 8),
     "layers of one kind only"),
    (dict(moe_num_secondary_experts=4), "moe_num_secondary_experts = 4"),
    (dict(moe_secondary_ffn_hidden_size=64),
     "moe_secondary_ffn_hidden_size = 64"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(hidden_act="silu"), "hidden_act 'silu'"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling")],
    ids=["layouts-disagree", "layout-short", "one-kind", "secondary",
         "secondary-width", "no-renorm", "silu", "rope-scaling"])
def test_what_the_configuration_refuses_is_refused_by_name(over, said):
    with pytest.raises(NotImplementedError, match=said):
        ModelConfig.from_hf_config(tiny_hf(**over))


def test_gemma2_is_one_layout_under_one_pool():
    """Gemma-2's even-layer rule fills ``layer_window``; its layers keep
    one pool under a mask and rotate everywhere."""
    cfg = ModelConfig.tiny(model_type="gemma2", sliding_window=8,
                           num_layers=4)
    assert cfg.layer_window == (8, None, 8, None)
    assert not cfg.kv_pool_by_kind and cfg.rotates(0) and cfg.rotates(1)
    assert ModelConfig.tiny().layer_window == ()
    assert ModelConfig.tiny().window_layer_ids == ()


# ------------------------------------------------- engine = the reference


@pytest.mark.parametrize("n_prompt", [WINDOW, 2 * WINDOW, 4 * WINDOW + 6],
                         ids=["1x", "2x", "4x"])
def test_generate_matches_the_reference_past_the_window(run_async, n_prompt):
    """Prefill in chunks of 8 (pages given back between chunks), then 12
    tokens through windows of 2 steps (pages given back between
    windows), at 1, 2 and 4 times the window: the engine's top-20
    log-probabilities are the reference's at every position."""
    eng = _engine()
    prompt = _prompt(n_prompt, n_prompt)
    (toks, tops), stats = _run(run_async, eng, prompt, 12)
    assert len(toks) == 12 == len(tops)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    gave = stats["kv_window_pages_released_total"]
    assert (gave > 0) == (n_prompt + 12 > WINDOW + PS)
    assert stats["kv_window_active_blocks"] == 0
    assert stats["kv_window_reserved_blocks"] == 0
    # 11 tokens after the first in windows of 2 steps, and the window
    # dispatched ahead of the last read-back; every one past the window
    assert 12 <= stats["decode_row_steps_total"] <= 16
    assert stats["decode_row_steps_past_window_total"] \
        == stats["decode_row_steps_total"]


def test_the_tolerance_sees_bf16_where_float32_is_stated(run_async):
    """The same comparison against the reference on weights rounded to
    bfloat16 fails by two orders of magnitude: ATOL is a float32
    tolerance."""
    eng = _engine()
    prompt = _prompt(5, 40)
    (toks, tops), _ = _run(run_async, eng, prompt, 8)
    rounded = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), PARAMS)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    assert gap(ref_logprobs(prompt, toks, params=rounded), tops) > 100 * ATOL


def test_a_short_and_a_long_row_together(run_async):
    """One row inside the window and one at 4 times it in the same
    prefill batches and decode windows: each reads what it reads
    alone, and both are the reference's."""
    eng = _engine()
    short, long_ = _prompt(1, 9), _prompt(2, 70)

    async def main():
        both = await asyncio.gather(_gen(eng, short, 10, 20),
                                    _gen(eng, long_, 10, 20))
        stats = eng.stats()
        await eng.stop()
        return both, stats

    ((a, a_tops), (b, b_tops)), stats = run_async(main())
    assert gap(ref_logprobs(short, a), a_tops) < ATOL
    assert gap(ref_logprobs(long_, b), b_tops) < ATOL
    # the short row passes the window only with its last tokens
    assert 0.5 * stats["decode_row_steps_total"] \
        < stats["decode_row_steps_past_window_total"] \
        < stats["decode_row_steps_total"]


def test_the_router_reads_the_unnormed_input(run_async):
    """A reference whose router reads the normed input is another model:
    past the tolerance at once."""
    eng = _engine()
    prompt = _prompt(3, 24)
    (toks, tops), _ = _run(run_async, eng, prompt, 6)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    assert gap(ref_logprobs(prompt, toks, router_on_normed=True),
               tops) > 10 * ATOL


# ------------------------------------------------------ the two pools


def test_the_window_pool_is_bounded_and_the_release_changes_nothing(
        run_async, monkeypatch):
    """A prompt of 4 times the window in chunks + 10 tokens: the row
    never holds more than the table's 7 pages of the window layers' pool
    while the full layers' pool holds every page of it; and the logits
    are those of a run that gives nothing back (the release stubbed out
    here, its table as wide as the context)."""
    prompt = _prompt(4, 4 * WINDOW)
    eng = _engine()
    assert eng.wpm.table_slots == SLOTS
    assert eng.wkv[0].shape[:2] == (6, 4 * SLOTS + 1)
    assert eng.kv_k.shape[:2] == (2, 64)
    held, full = [], []
    cover = eng.wpm.cover

    def spy(pages, first, upto):
        cover(pages, first, upto)
        held.append(len(pages))
        full.append(max(len(s.pages) for s in eng.prefilling + eng.running))

    eng.wpm.cover = spy
    (toks, tops), stats = _run(run_async, eng, prompt, 10)
    assert max(held) <= SLOTS and max(held) >= WINDOW // PS + 1
    assert max(full) >= (len(prompt) + 10) // PS       # every page kept
    assert stats["kv_window_pages_released_total"] > 0
    assert stats["kv_window_pages_held_total"] \
        <= stats["kv_window_pages_seen_total"]

    monkeypatch.setattr(llama, "window_table_slots",
                        lambda cfg, ps, ahead: 32)
    monkeypatch.setattr(WindowPagePool, "give_back",
                        lambda self, held, first, pos: first)
    kept = _engine(window_pages=40)
    (toks2, tops2), stats2 = _run(run_async, kept, prompt, 10)
    assert stats2["kv_window_pages_released_total"] == 0
    assert toks2 == toks
    assert max(abs(tops[j][i] - tops2[j][i])
               for j in range(10) for i in tops[j]) < 1e-5
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL


@pytest.mark.parametrize("control", ["ignored", "early"])
def test_both_controls_of_the_chip_tool_fail_here_too(run_async, control):
    """tools/smallthinker_long_context_check.py's two controls at this
    size: window layers handed the whole context, and every give-back a
    page early. Each is past the tolerance by an order of magnitude,
    where the sound engine is inside it."""
    prompt = _prompt(6, 3 * WINDOW)
    if control == "ignored":
        eng = _engine(window_ignored(CFG, 128))
        assert eng.wpm.table_slots == 35
        (toks, tops), stats = _run(run_async, eng, prompt, 10)
        assert stats["kv_window_pages_released_total"] == 0
    else:
        eng = _engine()
        with early_give_back(eng):
            (toks, tops), stats = _run(run_async, eng, prompt, 10)
    assert gap(ref_logprobs(prompt, toks), tops) > 10 * ATOL


def test_preemption_past_the_window_and_resume(run_async):
    """The full layers' pool runs out under two long rows: the newer one
    is preempted (both pools released, its reservation too), prefills
    again from position 0 and answers what it answers alone."""
    a, b = _prompt(7, 40), _prompt(8, 44)
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
        both = await asyncio.gather(_gen(eng, a, 24, 20), _gen(eng, b, 24))
        stats = eng.stats()
        await eng.stop()
        return both, stats

    ((ta, a_tops), (tb, _)), stats = run_async(main())
    assert preempted, "the pool was meant to run out"
    assert tb == want and len(ta) == 24
    assert gap(ref_logprobs(a, ta), a_tops) < ATOL
    assert stats["kv_window_active_blocks"] == 0
    assert stats["kv_window_reserved_blocks"] == 0
    assert stats["kv_active_blocks"] == 0


@pytest.mark.parametrize("pool", ["window", "full"])
def test_either_pool_alone_defers_admission_and_nothing_deadlocks(
        run_async, pool):
    """Three long rows and a pool, of one kind, that holds one of them:
    the others wait for admission and every one is answered, as alone."""
    prompts = [_prompt(10 + i, 36) for i in range(3)]
    alone = _engine()

    async def each():
        out = [await _gen(alone, p, 8) for p in prompts]
        await alone.stop()
        return out

    want = [t for t, _ in run_async(each())]
    over = (dict(window_pages=SLOTS + 1) if pool == "window"
            else dict(num_pages=14, watermark_pages=0))
    eng = _engine(**over)
    most = []
    admit = eng._admit_waiting

    def spy():
        admit()
        most.append(len(eng.prefilling) + len(eng.running))

    eng._admit_waiting = spy

    async def main():
        got = await asyncio.wait_for(
            asyncio.gather(*(_gen(eng, p, 8) for p in prompts)), 120)
        stats = eng.stats()
        await eng.stop()
        return got, stats

    got, stats = run_async(main())
    assert [t for t, _ in got] == want
    assert max(most) == 1, "one row's worth of pages admits one row"
    assert stats["kv_window_reserved_blocks"] == 0


def test_warmup_covers_the_serving_forms(run_async):
    """warmup() builds the two pools' operands as serving does: nothing
    compiles after it, in either decode arm."""
    for steps in (2, 1):
        eng = _engine(decode_steps=steps)
        eng.warmup()

        async def main(eng=eng):
            toks, _ = await _gen(eng, _prompt(9, 37), 9)
            stats = eng.stats()
            await eng.stop()
            return toks, stats

        toks, stats = run_async(main())
        assert len(toks) == 9 and stats["post_warmup_compiles_total"] == 0


def test_the_single_step_arm_is_the_reference_too(run_async):
    """decode_steps = 1 (``decode_step``, the arm the window is tested
    against) through both pools."""
    eng = _engine(decode_steps=1)
    prompt = _prompt(11, 40)
    (toks, tops), stats = _run(run_async, eng, prompt, 9)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    assert stats["kv_window_pages_released_total"] > 0


def test_the_window_kernels_read_both_pools(run_async, monkeypatch):
    """The Pallas arms (interpreted): the prefill and decode kernels on
    each kind's pool, the window layers' through its bounded table."""
    monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    eng = _engine()
    prompt = _prompt(12, 2 * WINDOW + 3)
    (toks, tops), _ = _run(run_async, eng, prompt, 6)
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL


# ------------------------------------------------------------- refusals


def _refused(what):
    return pytest.raises(NotImplementedError,
                         match=f"{what}.*pool of their own")


def test_a_prefix_hit_cannot_happen(run_async):
    """The pages a hit would need in the window layers are the ones
    given back: nothing is published or matched, and the same prompt
    again computes every token again and answers alike."""
    eng = _engine()
    assert not eng.pm.prefix_reuse
    p = _prompt(13, 40)

    async def main():
        a, _ = await _gen(eng, p, 6)
        mid = eng.stats()
        b, _ = await _gen(eng, p, 6)
        end = eng.stats()
        await eng.stop()
        return a, b, mid, end

    a, b, mid, end = run_async(main())
    assert a == b
    assert (mid["prefill_tokens_total"], end["prefill_tokens_total"]) \
        == (40, 80)
    assert end["prefix_hit_tokens_total"] == 0 and not eng.pm.by_hash


@pytest.mark.parametrize("over, what", [
    (dict(host_pages=8), "host KV tier"),
    (dict(host_pages=8, host_tier_int8=True), "kv_compress"),
    (dict(spec_decode=True), "spec_decode"),
    (dict(long_prefill_threshold=64), "long_prefill_threshold")])
def test_the_engine_refuses_what_it_does_not_build(over, what):
    with _refused(what):
        _engine(**over)


def test_a_mesh_of_several_devices_is_refused():
    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        pytest.skip("one device")
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with _refused("mesh"):
        JaxEngine(CFG, EngineConfig(page_size=PS, num_pages=16), mesh=mesh)


@pytest.mark.parametrize("what", ["disaggregated prefill worker",
                                  "disaggregated decode engine",
                                  "KV transfer server"])
def test_disagg_and_kv_transfer_refuse_this_engine(what):
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
    assert "ROADMAP B6" in registry.CAPABILITIES["pool_by_kind"][1]


# ------------------------------------------------------ the page books


def test_the_pools_books():
    """WindowPagePool alone: cover, give_back at the window's edge and no
    earlier, reservations against the pool's size."""
    pool = WindowPagePool(num_pages=9, page_size=4, window=16, table_slots=7)
    assert pool.capacity == 8 and pool.peak(100) == 7 and pool.peak(9) == 3
    assert pool.reserve(7) and not pool.reserve(2) and pool.reserve(1)
    held: list = []
    pool.cover(held, 0, 24)                      # positions 0 .. 23
    assert len(held) == 6 and pool.held == 6 and 0 not in held
    # a query at 19 sees 4 .. 19: page 0 (0 .. 3) goes, page 1 stays
    assert pool.give_back(held, 0, 18) == 0 and len(held) == 6
    assert pool.give_back(held, 0, 19) == 1 and len(held) == 5
    assert pool.give_back(held, 1, 19) == 1
    assert pool.give_back(held, 1, 27) == 3 and len(held) == 3
    assert (pool.allocated_total, pool.released_total) == (6, 3)
    pool.release(held)
    pool.unreserve(8)
    assert pool.held == 0 and pool.reserved == 0 and not held
    assert pool.released_total == 3     # a row's end is not a give-back
