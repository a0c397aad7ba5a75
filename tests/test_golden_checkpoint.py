"""Golden real-checkpoint validation: loader + model + engine vs
`transformers` on an actual HF Llama checkpoint (generated locally with a
fixed seed — fully offline; VERDICT r2 item 6: nothing previously proved
the loader+engine reproduce transformers logits/tokens for a real
checkpoint).

Also covers the hub front door (models/hub.py resolve_model) for the
local-directory case — the path `--model-id` takes on zero-egress hosts.
"""

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    """A tiny REAL Llama checkpoint written by transformers itself
    (config.json + model.safetensors), plus the live HF model."""
    from transformers import LlamaConfig, LlamaForCausalLM

    tcfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-5,
        rope_theta=10000.0, tie_word_embeddings=False,
        attention_bias=False, torch_dtype="float32")
    torch.manual_seed(7)
    model = LlamaForCausalLM(tcfg).eval()
    path = tmp_path_factory.mktemp("golden") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_hub_resolves_local_dir(hf_checkpoint):
    from dynamo_tpu.models.hub import resolve_model

    path, _ = hf_checkpoint
    assert resolve_model(path) == path


def test_loader_logits_match_transformers(hf_checkpoint):
    """Full-attention forward on the loaded weights == transformers
    logits (f32, tight tolerance), position by position."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    path, hf = hf_checkpoint
    cfg = ModelConfig.from_local_path(path)
    assert cfg.num_layers == 2 and cfg.num_kv_heads == 2
    params = load_params(path, cfg, dtype=jnp.float32)

    rng = np.random.RandomState(0)
    tokens = rng.randint(1, 128, size=(2, 17)).astype(np.int32)
    ours = np.asarray(llama.reference_forward(params, cfg,
                                              jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_engine_generation_matches_transformers_generate(hf_checkpoint,
                                                         run_async):
    """The SERVING path (paged prefill + pipelined fused-window decode)
    greedy-generates exactly what transformers.generate does on the same
    checkpoint — loader, paging, windowing, sampling all on the line."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.runtime.engine import Context

    path, hf = hf_checkpoint
    cfg = ModelConfig.from_local_path(path)
    params = load_params(path, cfg, dtype=jnp.float32)
    N = 12
    prompt = [(i * 11) % 120 + 1 for i in range(21)]
    with torch.no_grad():
        want = hf.generate(torch.tensor([prompt], dtype=torch.long),
                           max_new_tokens=N, do_sample=False,
                           pad_token_id=0)[0, len(prompt):].tolist()

    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=16, prefill_buckets=(16,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    engine = JaxEngine(cfg, ecfg, params=params)

    async def gen():
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=N, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    got = run_async(gen())
    assert got == want, f"engine {got} vs transformers {want}"


@pytest.fixture(scope="module")
def gemma_checkpoint(tmp_path_factory):
    """A tiny REAL Gemma checkpoint (scaled embeddings, (1+w) norm,
    GeGLU, tied head) written by transformers itself."""
    from transformers import GemmaConfig, GemmaForCausalLM

    tcfg = GemmaConfig(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=True,
        hidden_activation="gelu_pytorch_tanh", torch_dtype="float32")
    torch.manual_seed(11)
    model = GemmaForCausalLM(tcfg).eval()
    path = tmp_path_factory.mktemp("golden_gemma") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_gemma_logits_match_transformers(gemma_checkpoint):
    """Gemma family: all four semantic switches (embed scale, unit-offset
    norm, GeGLU, tied head) against the HF oracle."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    path, hf = gemma_checkpoint
    cfg = ModelConfig.from_local_path(path)
    assert cfg.model_type == "gemma"
    assert cfg.embed_scale and cfg.norm_unit_offset
    assert cfg.hidden_act == "gelu_tanh" and cfg.tie_word_embeddings
    params = load_params(path, cfg, dtype=jnp.float32)
    assert "lm_head" not in params

    rng = np.random.RandomState(1)
    tokens = rng.randint(1, 160, size=(2, 15)).astype(np.int32)
    ours = np.asarray(llama.reference_forward(params, cfg,
                                              jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


def test_gemma_engine_generation_matches_transformers(gemma_checkpoint,
                                                      run_async):
    """The full serving path (paged prefill + fused-window decode) on a
    Gemma checkpoint greedy-matches transformers.generate."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.runtime.engine import Context

    path, hf = gemma_checkpoint
    cfg = ModelConfig.from_local_path(path)
    params = load_params(path, cfg, dtype=jnp.float32)
    N = 10
    prompt = [(i * 13) % 150 + 1 for i in range(18)]
    with torch.no_grad():
        want = hf.generate(torch.tensor([prompt], dtype=torch.long),
                           max_new_tokens=N, do_sample=False,
                           pad_token_id=0)[0, len(prompt):].tolist()

    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=16, prefill_buckets=(16,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    engine = JaxEngine(cfg, ecfg, params=params)

    async def gen():
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=N, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    got = run_async(gen())
    assert got == want, f"engine {got} vs transformers {want}"


@pytest.fixture(scope="module")
def gemma2_checkpoint(tmp_path_factory):
    """A tiny REAL Gemma-2 checkpoint: everything Gemma-1 has PLUS
    sandwich norms, attention/final logit softcaps, an explicit
    query_pre_attn_scalar, and a sliding window (set to 8 — well under
    the test sequence lengths, so the window actually masks)."""
    from transformers import Gemma2Config, Gemma2ForCausalLM

    tcfg = Gemma2Config(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=True,
        hidden_activation="gelu_pytorch_tanh", query_pre_attn_scalar=16,
        sliding_window=8, attn_logit_softcapping=30.0,
        final_logit_softcapping=20.0, torch_dtype="float32",
        attn_implementation="eager")
    torch.manual_seed(13)
    model = Gemma2ForCausalLM(tcfg).eval()
    path = tmp_path_factory.mktemp("golden_gemma2") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_gemma2_logits_match_transformers(gemma2_checkpoint):
    """Gemma-2 semantics against the HF oracle: sandwich norms, attention
    softcap, sliding window on layer 0 (global on layer 1), final softcap.
    Sequence length 24 > window 8 so sliding masking is load-bearing."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    path, hf = gemma2_checkpoint
    cfg = ModelConfig.from_local_path(path)
    assert cfg.model_type == "gemma2"
    assert cfg.sandwich_norms and cfg.sliding_window == 8
    assert cfg.attn_logit_softcap == 30.0
    assert cfg.final_logit_softcap == 20.0
    assert cfg.query_pre_attn_scalar == 16
    params = load_params(path, cfg, dtype=jnp.float32)
    assert "ln_attn_post" in params and "ln_mlp_post" in params

    rng = np.random.RandomState(2)
    tokens = rng.randint(1, 160, size=(2, 24)).astype(np.int32)
    ours = np.asarray(llama.reference_forward(params, cfg,
                                              jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("kernels", [False, True])
def test_gemma2_engine_generation_matches_transformers(gemma2_checkpoint,
                                                       run_async,
                                                       monkeypatch,
                                                       kernels):
    """Full serving path on a Gemma-2 checkpoint greedy-matches
    transformers.generate across the sliding-window boundary — on the
    XLA attention paths AND on the Pallas kernel paths (paged prefill +
    fused-window decode in interpret mode), which implement the score
    softcap and per-layer sliding window natively."""
    if kernels:
        monkeypatch.setenv("DYN_PALLAS_INTERPRET", "1")
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.runtime.engine import Context

    path, hf = gemma2_checkpoint
    cfg = ModelConfig.from_local_path(path)
    params = load_params(path, cfg, dtype=jnp.float32)
    N = 10
    prompt = [(i * 17) % 150 + 1 for i in range(18)]  # 18 > window 8
    with torch.no_grad():
        want = hf.generate(torch.tensor([prompt], dtype=torch.long),
                           max_new_tokens=N, do_sample=False,
                           pad_token_id=0)[0, len(prompt):].tolist()

    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=16, prefill_buckets=(16,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    engine = JaxEngine(cfg, ecfg, params=params)

    async def gen():
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=N, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    got = run_async(gen())
    assert got == want, f"engine {got} vs transformers {want}"


@pytest.fixture(scope="module")
def qwen3_checkpoint(tmp_path_factory):
    """A tiny REAL Qwen3 checkpoint: Llama GQA shape + per-head q/k
    RMSNorm before RoPE, no qkv bias."""
    from transformers import Qwen3Config, Qwen3ForCausalLM

    tcfg = Qwen3Config(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        torch_dtype="float32", attn_implementation="eager")
    torch.manual_seed(17)
    model = Qwen3ForCausalLM(tcfg).eval()
    path = tmp_path_factory.mktemp("golden_qwen3") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_qwen3_logits_match_transformers(qwen3_checkpoint):
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    path, hf = qwen3_checkpoint
    cfg = ModelConfig.from_local_path(path)
    assert cfg.model_type == "qwen3" and cfg.qk_norm
    assert not cfg.attn_bias
    params = load_params(path, cfg, dtype=jnp.float32)
    assert "q_norm" in params and "k_norm" in params

    rng = np.random.RandomState(5)
    tokens = rng.randint(1, 160, size=(2, 17)).astype(np.int32)
    ours = np.asarray(llama.reference_forward(params, cfg,
                                              jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


def test_qwen3_engine_generation_matches_transformers(qwen3_checkpoint,
                                                      run_async):
    """Serving path (paged prefill + fused-window decode) on Qwen3
    greedy-matches transformers.generate."""
    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.runtime.engine import Context

    path, hf = qwen3_checkpoint
    cfg = ModelConfig.from_local_path(path)
    params = load_params(path, cfg, dtype=jnp.float32)
    N = 8
    prompt = [(i * 11) % 150 + 1 for i in range(14)]
    with torch.no_grad():
        want = hf.generate(torch.tensor([prompt], dtype=torch.long),
                           max_new_tokens=N, do_sample=False,
                           pad_token_id=0)[0, len(prompt):].tolist()

    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=16, prefill_buckets=(16,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    engine = JaxEngine(cfg, ecfg, params=params)

    async def gen():
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=N, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    got = run_async(gen())
    assert got == want, f"engine {got} vs transformers {want}"


def test_qwen3_moe_logits_match_transformers(tmp_path_factory):
    """Qwen3-MoE: per-head q/k norms + Qwen-named experts (mlp.experts.N
    gate/up/down_proj, router mlp.gate) through the dense-over-experts
    MoE path; logits vs the HF oracle."""
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    tcfg = Qwen3MoeConfig(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, tie_word_embeddings=False,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[],
        torch_dtype="float32", attn_implementation="eager")
    torch.manual_seed(19)
    model = Qwen3MoeForCausalLM(tcfg).eval()
    path = tmp_path_factory.mktemp("golden_qwen3moe") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)

    cfg = ModelConfig.from_local_path(str(path))
    assert cfg.model_type == "qwen3" and cfg.qk_norm
    assert cfg.num_experts == 4 and cfg.intermediate_size == 48
    params = load_params(str(path), cfg, dtype=jnp.float32)

    rng = np.random.RandomState(6)
    tokens = rng.randint(1, 160, size=(2, 13)).astype(np.int32)
    ours = np.asarray(llama.reference_forward(params, cfg,
                                              jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


def _deepseek_v2_cfg(**over):
    from transformers import DeepseekV2Config

    base = dict(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, head_dim=8,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False, n_routed_experts=None,
        # HF builds a MoE block for every layer_idx >= first_k_dense_replace
        # even when n_routed_experts is None — an all-dense model needs the
        # threshold past the last layer
        first_k_dense_replace=99,
        torch_dtype="float32", attn_implementation="eager")
    base.update(over)
    return DeepseekV2Config(**base)


def test_deepseek_v2_dense_logits_match_transformers(tmp_path_factory):
    """Dense MLA against the HF oracle — the first direct transformers
    cross-check of the MLA stack, which also validates the interleaved→
    split-half rope weight permutation real DeepSeek checkpoints need."""
    from transformers import DeepseekV2ForCausalLM

    from dynamo_tpu.models import mla
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    torch.manual_seed(23)
    model = DeepseekV2ForCausalLM(_deepseek_v2_cfg()).eval()
    path = tmp_path_factory.mktemp("golden_dsv2") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)

    cfg = ModelConfig.from_local_path(str(path))
    assert cfg.is_mla and cfg.rope_interleave and cfg.num_experts == 0
    params = load_params(str(path), cfg, dtype=jnp.float32)
    rng = np.random.RandomState(9)
    tokens = rng.randint(1, 160, size=(2, 12)).astype(np.int32)
    ours = np.asarray(mla.reference_forward(params, cfg,
                                            jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)


def test_deepseek_v2_norm_topk_prob_rejected():
    """transformers' DeepseekV2MoEGate ignores norm_topk_prob while
    DeepSeek's remote-code gate renormalizes-instead-of-scales — with
    conflicting oracles (and no published V2 checkpoint setting it) the
    config must be rejected loudly, not silently served either way."""
    from dynamo_tpu.models.config import ModelConfig

    hf = dict(model_type="deepseek_v2", vocab_size=160, hidden_size=64,
              intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=4,
              kv_lora_rank=16, n_routed_experts=8, num_experts_per_tok=2,
              moe_intermediate_size=32, norm_topk_prob=True)
    with pytest.raises(NotImplementedError, match="norm_topk_prob"):
        ModelConfig.from_hf_config(hf)
    hf["norm_topk_prob"] = False
    assert ModelConfig.from_hf_config(hf).moe_router == "deepseek_v2"


def test_deepseek_v2_moe_serving_matches_transformers(tmp_path_factory,
                                                      run_async):
    """DeepSeek-V2 MoE (dense first-k layers, shared experts, group-
    limited softmax routing with scaling): oracle logits AND the full
    serving path (paged prefill + fused-window decode through the
    segmented stack) greedy-match transformers."""
    from transformers import DeepseekV2ForCausalLM

    from dynamo_tpu.engine.jax_engine import EngineConfig, JaxEngine
    from dynamo_tpu.llm.protocols.common import (PreprocessedRequest,
                                                 SamplingOptions,
                                                 StopConditions)
    from dynamo_tpu.models import mla
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params
    from dynamo_tpu.runtime.engine import Context

    torch.manual_seed(29)
    model = DeepseekV2ForCausalLM(_deepseek_v2_cfg(
        q_lora_rank=24, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, n_shared_experts=2,
        first_k_dense_replace=1, moe_layer_freq=1,
        topk_method="group_limited_greedy", n_group=4, topk_group=2,
        routed_scaling_factor=1.5, norm_topk_prob=False,
        aux_loss_alpha=0.0, seq_aux=False)).eval()
    path = tmp_path_factory.mktemp("golden_dsv2moe") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)

    cfg = ModelConfig.from_local_path(str(path))
    assert cfg.num_experts == 8 and cfg.n_shared_experts == 2
    assert cfg.first_k_dense_replace == 1 and cfg.n_group == 4
    assert cfg.moe_router == "deepseek_v2"
    params = load_params(str(path), cfg, dtype=jnp.float32)

    rng = np.random.RandomState(10)
    tokens = rng.randint(1, 160, size=(2, 12)).astype(np.int32)
    ours = np.asarray(mla.reference_forward(params, cfg,
                                            jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)

    N = 8
    prompt = [(i * 7) % 150 + 1 for i in range(11)]
    with torch.no_grad():
        want = model.generate(torch.tensor([prompt], dtype=torch.long),
                              max_new_tokens=N, do_sample=False,
                              pad_token_id=0)[0, len(prompt):].tolist()
    ecfg = EngineConfig(page_size=4, num_pages=64, max_batch=4,
                        prefill_chunk=16, prefill_buckets=(16,),
                        batch_buckets=(4,), page_buckets=(16,),
                        decode_steps=4)
    engine = JaxEngine(cfg, ecfg, params=params)

    async def gen():
        req = PreprocessedRequest(
            token_ids=list(prompt), sampling=SamplingOptions(),
            stop=StopConditions(max_tokens=N, ignore_eos=True),
            eos_token_ids=[])
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
            if out.finish_reason:
                break
        await engine.stop()
        return toks

    got = run_async(gen())
    assert got == want, f"engine {got} vs transformers {want}"


def test_deepseek_v3_moe_logits_match_transformers(tmp_path_factory):
    """DeepSeek-V3 routing (sigmoid scores + e_score_correction_bias
    selection, top-2-sum group limiting, renormalized weights, scaling)
    against the HF oracle."""
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    from dynamo_tpu.models import mla
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.loader import load_params

    tcfg = DeepseekV3Config(
        vocab_size=160, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, head_dim=8,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        n_shared_experts=1, first_k_dense_replace=1, n_group=4,
        topk_group=2, routed_scaling_factor=2.0, norm_topk_prob=True,
        rope_interleave=True, torch_dtype="float32",
        attn_implementation="eager")
    torch.manual_seed(31)
    model = DeepseekV3ForCausalLM(tcfg).eval()
    # give the selection bias real (nonzero) values so the bias-vs-weight
    # distinction is load-bearing
    with torch.no_grad():
        for layer in model.model.layers[1:]:
            layer.mlp.gate.e_score_correction_bias.uniform_(-0.5, 0.5)
    path = tmp_path_factory.mktemp("golden_dsv3") / "ckpt"
    model.save_pretrained(path, safe_serialization=True)

    cfg = ModelConfig.from_local_path(str(path))
    assert cfg.moe_router == "deepseek_v3" and cfg.norm_topk_prob
    params = load_params(str(path), cfg, dtype=jnp.float32)

    rng = np.random.RandomState(11)
    tokens = rng.randint(1, 160, size=(2, 12)).astype(np.int32)
    ours = np.asarray(mla.reference_forward(params, cfg,
                                            jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)
