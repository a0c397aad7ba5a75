"""Weight loading from local HF-style checkpoints (safetensors).

Maps HuggingFace Llama/Mixtral parameter names onto this framework's
stacked-layer layout (models/llama.py). HF ``nn.Linear`` stores ``[out, in]``
weights; our matmuls are ``x @ W`` so every projection is transposed once at
load time (cheaper than transposing per step).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.profiling import setup_span
from .config import ModelConfig


def _index(path: str) -> Dict[str, str]:
    """tensor name → shard file, from the safetensors index (or single file)."""
    idx_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(idx_path):
        with open(idx_path) as f:
            return json.load(f)["weight_map"]
    single = os.path.join(path, "model.safetensors")
    if os.path.exists(single):
        from safetensors import safe_open

        with safe_open(single, framework="np") as f:
            return {k: "model.safetensors" for k in f.keys()}
    raise FileNotFoundError(f"no safetensors checkpoint under {path}")


@setup_span("load_params")
def load_params(path: str, cfg: Optional[ModelConfig] = None,
                dtype=None, quant: Optional[str] = None
                ) -> Dict[str, jax.Array]:
    """Load and restack a local HF checkpoint; returns the params pytree.

    ``quant="int8"`` quantizes the projection weights on the host
    (models/quant.py) so only int8 + scales ever reach the device."""
    from safetensors import safe_open

    cfg = cfg or ModelConfig.from_local_path(path)
    dtype = dtype or cfg.jax_dtype
    wmap = _index(path)
    handles: Dict[str, "safe_open"] = {}

    def get(name: str) -> np.ndarray:
        fname = wmap[name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(path, fname),
                                       framework="np")
        return handles[fname].get_tensor(name)

    def linear(name: str) -> np.ndarray:
        return np.ascontiguousarray(get(name).T)  # [out,in] → [in,out]

    L = cfg.num_layers
    p: Dict[str, np.ndarray] = {
        "embed": get("model.embed_tokens.weight"),
        "ln_final": get("model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = linear("lm_head.weight")

    def stack(fmt: str, fn=linear) -> np.ndarray:
        return np.stack([fn(fmt.format(i)) for i in range(L)])

    p["ln_attn"] = stack("model.layers.{}.input_layernorm.weight", get)
    if cfg.sandwich_norms:
        # Gemma-2: post_attention_layernorm normalizes the ATTENTION
        # OUTPUT (before its residual add); the pre-MLP norm is
        # pre_feedforward_layernorm
        p["ln_mlp"] = stack(
            "model.layers.{}.pre_feedforward_layernorm.weight", get)
        p["ln_attn_post"] = stack(
            "model.layers.{}.post_attention_layernorm.weight", get)
        p["ln_mlp_post"] = stack(
            "model.layers.{}.post_feedforward_layernorm.weight", get)
    else:
        p["ln_mlp"] = stack(
            "model.layers.{}.post_attention_layernorm.weight", get)
    if cfg.is_mla:
        _load_mla_attention(cfg, p, stack, linear, get)
    else:
        p["wq"] = stack("model.layers.{}.self_attn.q_proj.weight")
        p["wk"] = stack("model.layers.{}.self_attn.k_proj.weight")
        p["wv"] = stack("model.layers.{}.self_attn.v_proj.weight")
        p["wo"] = stack("model.layers.{}.self_attn.o_proj.weight")
        if cfg.attn_bias:  # Qwen2-style qkv bias
            p["bq"] = stack("model.layers.{}.self_attn.q_proj.bias", get)
            p["bk"] = stack("model.layers.{}.self_attn.k_proj.bias", get)
            p["bv"] = stack("model.layers.{}.self_attn.v_proj.bias", get)
        if cfg.qk_norm:  # Qwen3 per-head q/k norms
            p["q_norm"] = stack("model.layers.{}.self_attn.q_norm.weight",
                                get)
            p["k_norm"] = stack("model.layers.{}.self_attn.k_norm.weight",
                                get)
    if cfg.num_experts > 0 and cfg.is_mla:
        _load_deepseek_moe(cfg, p, linear, get)
    elif cfg.num_experts > 0:
        E = cfg.num_experts
        # HF names the MoE block differently per family: Mixtral uses
        # block_sparse_moe with w1/w3/w2, Qwen3-MoE uses mlp with
        # gate/up/down_proj
        if cfg.model_type == "qwen3":
            moe, w1, w3, w2 = "mlp", "gate_proj", "up_proj", "down_proj"
        else:
            moe, w1, w3, w2 = "block_sparse_moe", "w1", "w3", "w2"
        p["w_router"] = stack(
            "model.layers.{}.%s.gate.weight" % moe)

        def experts(proj: str) -> np.ndarray:
            return np.stack([
                np.stack([linear(
                    f"model.layers.{i}.{moe}.experts.{e}.{proj}.weight")
                    for e in range(E)])
                for i in range(L)])

        p["w_gate"] = experts(w1)
        p["w_up"] = experts(w3)
        p["w_down"] = experts(w2)
    else:
        p["w_gate"] = stack("model.layers.{}.mlp.gate_proj.weight")
        p["w_up"] = stack("model.layers.{}.mlp.up_proj.weight")
        p["w_down"] = stack("model.layers.{}.mlp.down_proj.weight")

    if quant == "int8":
        from .quant import QuantInt8, quantize_params

        p = quantize_params(p)
        return {k: (QuantInt8(jnp.asarray(v.q), jnp.asarray(v.s))
                    if isinstance(v, QuantInt8) else jnp.asarray(v, dtype))
                for k, v in p.items()}
    if quant is not None:
        raise ValueError(f"unknown quant mode {quant!r} (expected 'int8')")
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), p)


def _rope_perm(dr: int) -> np.ndarray:
    """Interleaved → split-half rope column permutation: DeepSeek
    checkpoints store rope dims as (pair0_re, pair0_im, pair1_re, ...);
    our apply_rope expects all real parts first. Applying the SAME
    permutation to the q and k rope columns leaves q·k scores exactly
    invariant (HF's apply_rotary_pos_emb_interleave is this permutation
    followed by split-half rope)."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _load_deepseek_moe(cfg: ModelConfig, p: Dict[str, np.ndarray],
                       linear, get) -> None:
    """DeepSeek-V2/V3 MoE weights → models/mla.py segmented layout:
    dense first-k layers (mlp.{gate,up,down}_proj → w_*_d), then routed
    experts (mlp.experts.N.* → w_*_e [Lm, E, D, Im], router mlp.gate →
    w_router, V3 e_score_correction_bias → router_bias) plus the
    always-on shared experts (mlp.shared_experts.* → w_*_s)."""
    L, E, kd = cfg.num_layers, cfg.num_experts, cfg.first_k_dense_replace

    def seg(fmt, rng, fn=linear):
        return np.stack([fn(fmt.format(i)) for i in rng])

    if kd > 0:
        p["w_gate_d"] = seg("model.layers.{}.mlp.gate_proj.weight",
                            range(kd))
        p["w_up_d"] = seg("model.layers.{}.mlp.up_proj.weight", range(kd))
        p["w_down_d"] = seg("model.layers.{}.mlp.down_proj.weight",
                            range(kd))
    moe_rng = range(kd, L)
    p["w_router"] = seg("model.layers.{}.mlp.gate.weight", moe_rng)
    if cfg.moe_router == "deepseek_v3":
        p["router_bias"] = seg(
            "model.layers.{}.mlp.gate.e_score_correction_bias", moe_rng,
            get)

    def experts(proj):
        return np.stack([
            np.stack([linear(
                f"model.layers.{i}.mlp.experts.{e}.{proj}.weight")
                for e in range(E)])
            for i in moe_rng])

    p["w_gate_e"] = experts("gate_proj")
    p["w_up_e"] = experts("up_proj")
    p["w_down_e"] = experts("down_proj")
    if cfg.n_shared_experts > 0:
        p["w_gate_s"] = seg(
            "model.layers.{}.mlp.shared_experts.gate_proj.weight", moe_rng)
        p["w_up_s"] = seg(
            "model.layers.{}.mlp.shared_experts.up_proj.weight", moe_rng)
        p["w_down_s"] = seg(
            "model.layers.{}.mlp.shared_experts.down_proj.weight", moe_rng)


def _load_mla_attention(cfg: ModelConfig, p: Dict[str, np.ndarray],
                        stack, linear, get) -> None:
    """DeepSeek-V2/V3 MLA attention weights → models/mla.py layout:
    kv_a_proj_with_mqa → w_dkv ([D, r+dr]); kv_a_layernorm → kv_norm;
    kv_b_proj ([H*(dn+dv), r] in HF) splits into w_uk [r, H*dn] and
    w_uv [r, H*dv]; q path full-rank or LoRA (q_a/q_b + q_a_layernorm)."""
    H = cfg.num_heads
    r, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    L = cfg.num_layers

    p["w_dkv"] = stack("model.layers.{}.self_attn.kv_a_proj_with_mqa.weight")
    p["kv_norm"] = stack("model.layers.{}.self_attn.kv_a_layernorm.weight",
                         get)
    dr = cfg.qk_rope_head_dim
    if cfg.rope_interleave:
        perm = _rope_perm(dr)
        p["w_dkv"] = np.concatenate(
            [p["w_dkv"][..., :r], p["w_dkv"][..., r:][..., perm]], axis=-1)
    uk, uv = [], []
    for i in range(L):
        b = linear(f"model.layers.{i}.self_attn.kv_b_proj.weight")
        b = b.reshape(r, H, dn + dv)
        uk.append(np.ascontiguousarray(b[:, :, :dn]).reshape(r, H * dn))
        uv.append(np.ascontiguousarray(b[:, :, dn:]).reshape(r, H * dv))
    p["w_uk"] = np.stack(uk)
    p["w_uv"] = np.stack(uv)
    p["w_o"] = stack("model.layers.{}.self_attn.o_proj.weight")
    if cfg.q_lora_rank > 0:
        p["w_dq"] = stack("model.layers.{}.self_attn.q_a_proj.weight")
        p["q_norm"] = stack("model.layers.{}.self_attn.q_a_layernorm.weight",
                            get)
        p["w_uq"] = stack("model.layers.{}.self_attn.q_b_proj.weight")
        qk = "w_uq"
    else:
        p["w_q"] = stack("model.layers.{}.self_attn.q_proj.weight")
        qk = "w_q"
    if cfg.rope_interleave:
        # per-head layout [dn | dr]: permute each head's rope block
        w = p[qk]
        shp = w.shape
        w = w.reshape(*shp[:-1], H, dn + cfg.qk_rope_head_dim)
        w = np.concatenate([w[..., :dn], w[..., dn:][..., perm]], axis=-1)
        p[qk] = np.ascontiguousarray(w.reshape(shp))
