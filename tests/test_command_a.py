"""Command A+ (``model_type: cohere2_moe``: a parallel block under one
bias-free LayerNorm, window layers with the interleaved rotation beside
full layers without positions on a K/V pool a kind, the chip's share of
sigmoid-routed experts beside shared experts that are averaged:
models/cohere2_moe.py on models/llama.py's by-kind path) against its
plain reference (benchmark/configs/command-a-plus-05-2026/reference.py),
through ``JaxEngine.generate``, on the CPU at a small size: float32,
hidden 64, one period of (window, window, window, full), 8 / 2 heads of
16, a router of 16 outputs top-4 with experts 4..11 held (width 32), 2
shared experts, a window of 16, pages of 4, prefill chunks of 8: a table
of 7 slots into the window layers' pool.

Tolerance. Both sides are float32 and compute the same sums in another
order (the program in pages, chunks and windows with an online softmax,
the reference over the whole sequence at once), so log-probabilities of
magnitude ~6 differ by a few 1e-6; ATOL = 1e-4 leaves room and is far
under what anything systematic moves: the reference on weights rounded
to bf16 reads 1e-2 and more, and each of the eight controls of
reference.py (a sequential block, RMSNorm, the half-split rotation, a
rotated full layer, an ignored window, summed or halved shared experts,
a softmax gate) 1e-3 and more (the tests that provoke them)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
from dynamo_tpu.llm.protocols.common import (OutputOptions,
                                             PreprocessedRequest,
                                             SamplingOptions, StopConditions)
from dynamo_tpu.models import cohere2_moe, llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.registry import get_model_module
from dynamo_tpu.runtime.engine import Context
from tools.command_a_long_context_check import verdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(ROOT, "benchmark", "configs",
                          "command-a-plus-05-2026")
ATOL = 1e-4
WINDOW, PS, CHUNK = 16, 4, 8
SLOTS = 7       # ceil((16 + 8) / 4) + 1


def _reference():
    spec = importlib.util.spec_from_file_location(
        "command_a_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny_hf(**over) -> dict:
    hf = dict(model_type="cohere2_moe", vocab_size=512, hidden_size=64,
              intermediate_size=32, num_hidden_layers=4,
              num_attention_heads=8, num_key_value_heads=2, head_dim=16,
              layer_types=["sliding_attention"] * 3 + ["full_attention"],
              sliding_window=WINDOW, rope_theta=10000.0, rotary_pct=1,
              position_embedding_type="rope_gptj", layer_norm_eps=1e-5,
              use_parallel_block=True, use_qk_norm=False,
              first_k_dense_replace=0, num_experts=8, router_num_experts=16,
              first_local_expert=4, num_experts_per_tok=4,
              expert_selection_fn="sigmoid", norm_topk_prob=True,
              num_shared_experts=2,
              shared_expert_combination_strategy="average",
              hidden_act="silu", use_gated_activation=True,
              tie_word_embeddings=False, logit_scale=1)
    hf.update(over)
    return hf


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig.from_hf_config(tiny_hf(**over))
    cfg.dtype = "float32"
    return cfg


def _params(cfg, seed=0):
    """The harness's weights at this size (unit-RMS embeddings)."""
    return weights.build_tree(
        cohere2_moe, cfg, weights.seed_key(seed),
        {"embed": float(np.sqrt(cfg.vocab_size))})


VARIANTS = {
    "untied": tiny(),
    "tied": tiny(tie_word_embeddings=True),
    "tied-logit-scale": tiny(tie_word_embeddings=True, logit_scale=0.25),
}
PARAMS = {name: _params(cfg) for name, cfg in VARIANTS.items()}
CFG, BASE = VARIANTS["untied"], PARAMS["untied"]


@pytest.fixture(autouse=True, scope="module")
def _one_trace_a_program():
    """Engines of one configuration share their jitted programs in this
    file; the module is restored afterwards."""
    made, sound = {}, {}

    def shared(name):
        make = sound[name] = getattr(cohere2_moe, name)

        def cached(cfg, *args, **kw):
            key = (name, id(cfg), args, tuple(sorted(kw.items())))
            if key not in made:
                made[key] = make(cfg, *args, **kw)
            return made[key]

        setattr(cohere2_moe, name, cached)

    shared("make_step_fns")
    shared("make_decode_window_fn")
    yield
    for name, make in sound.items():
        setattr(cohere2_moe, name, make)


def _engine(cfg=CFG, params=BASE, **over) -> JaxEngine:
    ecfg = dict(page_size=PS, num_pages=64, max_batch=4,
                prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
                batch_buckets=(1, 4), page_buckets=(32,), decode_steps=2,
                max_prefill_batch=2, warmup_logprobs=False)
    ecfg.update(over)
    return JaxEngine(cfg, EngineConfig(**ecfg), params=params, seed=0)


async def _gen(engine, prompt, n):
    req = PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=n, ignore_eos=True),
        output=OutputOptions(logprobs=20))
    toks, tops = [], []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
        tops.extend(out.top_logprobs or [])
        if out.finish_reason is not None:
            break
    return toks, tops


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def ref_logprobs(prompt, toks, params=BASE, cfg=CFG, **kw):
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
        out = await _gen(engine, prompt, n)
        stats = engine.stats()
        await engine.stop()
        return out, stats

    return run_async(main())


# ---------------------------------------------------------- configuration


def test_from_hf_config_on_the_catalog_config():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    assert cfg.model_type == "cohere2_moe" and cfg.kv_pool_by_kind
    assert cfg.num_layers == 4 and cfg.sliding_window == 4096
    # layer_types gives window, window, window, full: the window layers
    # rotate, the full layer applies no positional embedding
    assert cfg.layer_window == (4096, 4096, 4096, None)
    assert cfg.layer_rope == (True, True, True, False)
    assert cfg.full_layer_ids == (3,)
    assert cfg.window_layer_ids == (0, 1, 2)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (128, 8, 128)
    assert (cfg.num_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.intermediate_size) \
        == (16, 128, 0, 8, 4096)
    assert cfg.moe_router == "deepseek_v3" and cfg.norm_topk_prob
    assert cfg.n_shared_experts == 4 and cfg.shared_expert_scale == 0.25
    assert cfg.parallel_block and cfg.layer_norm and cfg.rope_interleave
    assert cfg.rms_norm_eps == 1e-5 and cfg.rope_theta == 50000
    assert cfg.logits_scaling == 1.0 and not cfg.tie_word_embeddings
    assert not cfg.moe_early_router and not cfg.qk_norm
    assert llama.layer_period(cfg) == 4
    assert llama.window_table_slots(cfg, 64, 512) == 73
    model = get_model_module(cfg)
    assert model is cohere2_moe
    assert model.WINDOW_COUNTS == ("moe_pairs_routed_total",
                                   "moe_pairs_held_total")
    k, _ = jax.eval_shape(lambda: model.init_kv_cache(
        cfg, llama.KVCacheSpec(7232, 64)))
    w, _ = jax.eval_shape(lambda: model.init_window_kv_cache(
        cfg, llama.KVCacheSpec(2400, 64)))
    assert k.shape == (1, 7232, 8, 64, 128)
    assert w.shape == (3, 2400, 8, 64, 128)
    tree = jax.eval_shape(lambda: model.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    assert "ln_mlp" not in tree and "router_bias" not in tree
    assert tree["w_router"].shape == (4, 4096, 128)
    assert tree["w_gate"].shape == (4, 16, 4096, 4096)
    assert tree["w_gate_s"].shape == (4, 4096, 4 * 4096)
    assert tree["w_down_s"].shape == (4, 4 * 4096, 4096)
    assert tree["lm_head"].shape == (4096, 32768)
    # 9.07 GiB of bf16 weights (about.json reduced_why)
    n = sum(int(np.prod(x.shape)) for x in tree.values())
    assert abs(n * 2 / 2 ** 30 - 9.07) < 0.01


def test_the_family_is_read_as_neither_llama_nor_gemma2():
    """A ``model_type`` that no family claims is refused (before PR 58 it
    fell through ``from_hf_config``'s Llama defaults), and
    ``sliding_window`` without a layout takes Gemma-2's even-layer rule:
    this family's file read so would be a sequential RMSNorm model with
    a window on layers 0 and 2."""
    hf = tiny_hf()
    with pytest.raises(NotImplementedError, match="mystery.*cohere2_moe"):
        ModelConfig.from_hf_config(dict(hf, model_type="mystery"))
    as_llama = ModelConfig.from_hf_config(dict(hf, model_type="llama"))
    assert as_llama.model_type == "llama" and not as_llama.parallel_block
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.layer_window == (WINDOW, WINDOW, WINDOW, None)
    gemma = ModelConfig.tiny(sliding_window=WINDOW, num_layers=4)
    assert gemma.layer_window == (WINDOW, None, WINDOW, None)
    assert cfg.layer_window != gemma.layer_window
    assert cfg.tie_word_embeddings is False
    assert tiny(tie_word_embeddings=True).tie_word_embeddings
    assert tiny(logit_scale=0.25).logits_scaling == 4.0


@pytest.mark.parametrize("over, said", [
    (dict(use_parallel_block=False), "use_parallel_block false"),
    (dict(use_qk_norm=True), "use_qk_norm true"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace 1"),
    (dict(rotary_pct=0.5), "rotary_pct 0.5"),
    (dict(shared_expert_combination_strategy="sum"),
     "shared_expert_combination_strategy 'sum'"),
    (dict(layer_types=["sliding_attention"] * 3 + ["chunked_attention"]),
     "chunked_attention"),
    (dict(layer_types=["sliding_attention"] * 3), "num_hidden_layers = 4"),
    (dict(layer_types=["full_attention"] * 4), "layers of one kind only"),
    (dict(expert_selection_fn="softmax"), "expert_selection_fn 'softmax'"),
    (dict(norm_topk_prob=False), "norm_topk_prob false"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
    (dict(position_embedding_type="rope_neox"), "rope_neox"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(first_local_expert=9), "first_local_expert 9"),
    (dict(num_experts_per_tok=17), "num_experts_per_tok 17")],
    ids=["sequential", "qk-norm", "dense-prefix", "rotary-pct",
         "shared-sum", "third-kind", "layout-short", "one-kind",
         "softmax", "no-renorm", "gelu", "neox", "bias", "share-outside",
         "top-k-wide"])
def test_what_the_configuration_refuses_is_refused_by_name(over, said):
    with pytest.raises(NotImplementedError, match=said):
        ModelConfig.from_hf_config(tiny_hf(**over))


# ------------------------------------------------------------- primitives


def test_the_interleaved_rotation_is_the_half_split_one_on_moved_columns():
    """Pair i of the interleaved rotation is the columns (2i, 2i + 1):
    the half-split rotation of the same vector with its even columns
    moved to the front, moved back."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 16))
    pos = jnp.arange(5)[None] + jnp.asarray([[0], [40]])
    inv = llama.rope_freqs(CFG)
    got = llama.apply_rope(x, pos, inv, True)
    moved = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = llama.apply_rope(moved, pos, inv)
    want = jnp.stack([half[..., :8], half[..., 8:]], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - llama.apply_rope(x, pos, inv)))) > 0.1
    np.testing.assert_allclose(got[0, 0], x[0, 0], atol=1e-6)  # position 0


@pytest.mark.parametrize("heads", [128, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_pairs_rotate_on_whole_heads_as_the_reference_rotates_them(
        dtype, heads):
    """``apply_rope``'s interleaved arm is written on whole heads of 128
    lanes (x cos2 + partner(x) sin2, the swap a product with a constant
    permutation): at the cell's head counts and positions up to the end
    of its longest context it is the reference's rotation of the pairs
    (2i, 2i + 1), the same float32 products, and far from the
    half-split control."""
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    pos = jnp.asarray([0, 1, 63, 4095, 4096, 20000, 33918, 33919])
    x = jax.random.normal(jax.random.PRNGKey(heads), (len(pos), heads, 128)
                          ).astype(dtype)
    got = llama.apply_rope(x[None], pos[None], llama.rope_freqs(cfg),
                           True)[0]
    assert got.dtype == dtype
    want = REF._rope(x, pos, cfg.rope_theta)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(dtype).astype(jnp.float32),
                               atol=1e-6)
    half = REF._rope(x, pos, cfg.rope_theta, half_split=True)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - half))) > 0.1


def test_layer_norm_subtracts_the_mean_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 64)) + 2.0
    w = jax.random.normal(jax.random.PRNGKey(3), (64,))
    got = llama.layer_norm(x, w, 1e-5)
    c = x - x.mean(-1, keepdims=True)
    want = w * c / jnp.sqrt((c * c).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    rms = llama.rms_norm(x, w, 1e-5)
    assert float(jnp.max(jnp.abs(got - rms))) > 0.1
    np.testing.assert_allclose(got, REF._ln(x, w, 1e-5), atol=1e-5)


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """A layer of 16 experts top-4, cut into eight shares of two: the
    program's second half on each share (``llama._ff_out`` told which
    experts it holds) summed, with the shared experts counted once, is
    the reference's second half on the uncut layer, and every pair the
    router chose is held by exactly one share."""
    whole = tiny(num_experts=16, first_local_expert=0)
    p = _params(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 64))
    lp = {k: p[k][1] for k in llama._layer_keys(whole)}      # layer 1
    stacks = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        want = REF._ff(whole, None, lambda name: p[name][1], x[0])
        total, held = jnp.zeros_like(x), 0
        for share in range(8):
            cfg = tiny(num_experts=2, first_local_expert=2 * share)
            cut = {**lp, **{k: lp[k][2 * share:2 * share + 2]
                            for k in stacks}}
            out, counted = llama._ff_out(x, cut, cfg, None,
                                         valid=jnp.ones((1, 12), bool))
            total, held = total + out, held + int(counted[1])
            assert int(counted[0]) == 12 * 4
        shared = ((jax.nn.silu(x @ lp["w_gate_s"]) * (x @ lp["w_up_s"]))
                  @ lp["w_down_s"]) * whole.shared_expert_scale
    assert held == 12 * 4
    # eight outputs hold the mean of the shared experts eight times
    np.testing.assert_allclose((total - 7 * shared)[0], want, atol=2e-5)
    uncut, _ = llama._ff_out(x, lp, whole, None)
    np.testing.assert_allclose(uncut[0], want, atol=2e-5)


# ------------------------------------------------- engine = the reference


@pytest.mark.parametrize("name, n_prompt", [
    ("untied", WINDOW), ("untied", 4 * WINDOW + 6), ("tied", 2 * WINDOW + 3),
    ("tied-logit-scale", 3 * WINDOW)],
    ids=["untied-1x", "untied-4x", "tied-2x", "tied-logit-scale-3x"])
def test_generate_matches_the_reference_past_the_window(run_async, name,
                                                        n_prompt):
    """Prefill in chunks of 8 (at least two; window-pool pages given
    back between chunks), then 12 tokens through windows of 2 steps
    (pages given back between windows) over both pools: the engine's
    top-20 log-probabilities are the reference's at every position,
    tied and untied, and with a logit_scale that is not 1."""
    cfg, params = VARIANTS[name], PARAMS[name]
    eng = _engine(cfg, params)
    prompt = _prompt(n_prompt, n_prompt)
    (toks, tops), stats = _run(run_async, eng, prompt, 12)
    assert len(toks) == 12 == len(tops)
    assert gap(ref_logprobs(prompt, toks, params, cfg), tops) < ATOL
    gave = stats["kv_window_pages_released_total"]
    assert (gave > 0) == (n_prompt + 12 > WINDOW + PS)
    assert stats["kv_window_active_blocks"] == 0
    assert stats["kv_window_reserved_blocks"] == 0
    assert 12 <= stats["decode_row_steps_total"] <= 16
    # the window counts, a live row-step a layer: 4 pairs chosen, and of
    # those the ones routed to experts 4..11 of the router's 16
    routed = stats["moe_pairs_routed_total"]
    assert 11 * 4 * 4 <= routed <= 16 * 4 * 4 and routed % 16 == 0
    assert 0.25 * routed < stats["moe_pairs_held_total"] < 0.75 * routed


def test_logit_scale_scales_the_logits(run_async):
    """The same weights under logit_scale 0.25: other log-probabilities,
    which the reference follows (the case above) and a reference without
    the scale does not."""
    cfg, params = VARIANTS["tied-logit-scale"], PARAMS["tied-logit-scale"]
    eng = _engine(cfg, params)
    prompt = _prompt(7, 20)
    (toks, tops), _ = _run(run_async, eng, prompt, 4)
    assert gap(ref_logprobs(prompt, toks, params, cfg), tops) < ATOL
    assert gap(ref_logprobs(prompt, toks, params, VARIANTS["tied"]),
               tops) > 100 * ATOL


_SOUND = {}


def _sound_run(run_async):
    """One run of the base configuration, shared by the controls."""
    if not _SOUND:
        eng = _engine()
        prompt = _prompt(5, 3 * WINDOW + 2)
        (toks, tops), _ = _run(run_async, eng, prompt, 8)
        _SOUND.update(prompt=prompt, toks=toks, tops=tops)
        # the sound reference is inside the tolerance, once for all
        assert gap(ref_logprobs(prompt, toks), tops) < ATOL
    return _SOUND["prompt"], _SOUND["toks"], _SOUND["tops"]


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_control_of_the_reference_fails(run_async, control):
    """A reference with ONE fault (the block run sequentially, RMSNorm
    for LayerNorm, the half-split rotation, a rotated full layer, the
    window ignored, the shared experts summed, (routed + shared) / 2, a
    softmax gate) is another model: past the tolerance at once, where
    the sound reference is inside it."""
    prompt, toks, tops = _sound_run(run_async)
    assert gap(ref_logprobs(prompt, toks, control=control), tops) > 10 * ATOL


def test_the_two_readings_of_average_differ(run_async):
    prompt, toks, _ = _sound_run(run_async)
    a = ref_logprobs(prompt, toks)
    b = ref_logprobs(prompt, toks, control="shared_halved")
    assert float(np.max(np.abs(a - b))) > 100 * ATOL
    with pytest.raises(ValueError, match="one of"):
        REF.reference_logits(BASE, CFG, prompt, control="no-such-fault")


def test_the_tolerance_sees_bf16_where_float32_is_stated(run_async):
    prompt, toks, tops = _sound_run(run_async)
    rounded = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), BASE)
    assert gap(ref_logprobs(prompt, toks, params=rounded), tops) > 100 * ATOL


def test_the_window_pool_is_bounded(run_async):
    """A prompt of 4 times the window + 10 tokens: the row never holds
    more than the table's 7 pages of the window layers' pool (three
    layers) while the full layer's pool holds every page of it."""
    prompt = _prompt(4, 4 * WINDOW)
    eng = _engine()
    assert eng.wpm.table_slots == SLOTS
    assert eng.wkv[0].shape[:2] == (3, 4 * SLOTS + 1)
    assert eng.kv_k.shape[:2] == (1, 64)
    held, full = [], []
    cover = eng.wpm.cover

    def spy(pages, first, upto):
        cover(pages, first, upto)
        held.append(len(pages))
        full.append(max(len(s.pages) for s in eng.prefilling + eng.running))

    eng.wpm.cover = spy
    (toks, tops), stats = _run(run_async, eng, prompt, 10)
    assert max(held) <= SLOTS and max(held) >= WINDOW // PS + 1
    assert max(full) >= (len(prompt) + 10) // PS
    assert stats["kv_window_pages_released_total"] > 0
    assert gap(ref_logprobs(prompt, toks), tops) < ATOL


# ----------------------------------------------------- the builder's tool


def test_the_tools_verdict_holds_controls_to_the_factor():
    """Each control against the sound reading of its own seed and
    length; the limit would lie between the worst sound reading and the
    best control."""
    sound = [dict(case="sound", seed=1, prompt_tokens=p, ok=True,
                  median_abs_logprob_diff=m)
             for p, m in ((512, 0.03), (32768, 0.02))]
    agree = [dict(case="agree", seed=1, prompt_tokens=104, ok=True,
                  median_abs_logprob_diff=0.02)]
    far = [dict(case=c, seed=1, prompt_tokens=32768, ok=False,
                median_abs_logprob_diff=0.2) for c in REF.CONTROLS]
    v = verdict(sound + agree + far, 3.0)
    assert v["ok"] and v["worst_sound_median"] == 0.03
    assert v["limit_lies_between"] == [0.03, 0.2]
    assert v["control_ratios"][REF.CONTROLS[0]] == [10.0]
    near = [dict(far[0], median_abs_logprob_diff=0.05)] + far[1:]
    v = verdict(sound + agree + near, 3.0)
    assert not v["ok"]
    assert v["controls_under_factor"] == [[REF.CONTROLS[0], 1, 32768]]
    assert verdict(sound + agree + near, 2.0)["ok"]
    bad = [dict(sound[0], ok=False)] + sound[1:]
    assert not verdict(bad + agree + far, 3.0)["ok"]
