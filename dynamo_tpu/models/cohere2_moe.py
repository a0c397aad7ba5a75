"""Cohere2-MoE (Command A+, ``model_type: cohere2_moe``): a PARALLEL
block (one bias-free LayerNorm a layer; attention and the second half
both read it and are added to the residual stream once), GQA with many
query heads a KV head, window layers that rotate q and k over
interleaved pairs beside full layers without positions (a K/V pool a
kind of layer), and in every layer the chip's share of sigmoid-routed
experts beside shared experts that are averaged.

Nothing of a layer is written here. The forward, the decode window, the
pools and their tables are models/llama.py's by-kind path
(``_forward_by_kind``, ``_window_family_by_kind``: SmallThinker's), which
takes its form from what the configuration has, at trace time:
``cfg.parallel_block`` (``_second_half``), ``cfg.layer_norm``
(``_norm``), ``cfg.rope_interleave`` (``apply_rope``), ``cfg.moe_router``
of the DeepSeek kind (``_ff_out``: ``deepseek_gate`` without a selection
bias, ``moe_experts`` told which experts it holds, the shared experts
side by side in one MLP times ``cfg.shared_expert_scale`` = 1 / their
number). This module supplies the params' tree, the entry points under
the names the engine calls, and ``WINDOW_COUNTS``: the window counts the
pairs the router chose and those held here, as granite.py's does.

Scopes: ``attn`` with ``attn.proj`` (the q, k, v and o products),
``attn.window`` / ``attn.full``; ``moe`` with ``moe.router``,
``moe.dispatch``, ``moe.experts``, ``moe.shared``; ``lm_head``,
``sample``, ``kv_carry``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig, held_experts, refuser
from .granite import WINDOW_COUNTS  # noqa: F401  (the engine reads it)
from .kimi_linear import _drawer
from .llama import (Params, init_kv_cache, init_window_kv_cache,  # noqa: F401
                    make_decode_window_fn, make_step_fns,
                    window_table_slots)


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``cohere2_moe`` config.json (Command A+): every
    layer a parallel block under one bias-free LayerNorm; window layers
    (``layer_types`` ``sliding_attention``) rotate q and k over
    interleaved pairs, full layers apply no positional embedding;
    sigmoid-routed experts of width ``intermediate_size`` beside
    ``num_shared_experts`` shared ones that are averaged. ``layer_types``
    is kept whole in a file cut in depth: the first ``num_hidden_layers``
    entries are the layers that run (``num_experts``:
    config.held_experts). Nothing of this family is read through
    config.hf_base: ``sliding_window`` there would take Gemma-2's rule."""
    refuse = refuser("cohere2_moe")
    L = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"][:L])
    odd = sorted(set(kinds) - {"sliding_attention", "full_attention"})
    if odd or len(kinds) != L:
        refuse(f"layer_types {odd or len(kinds)}",
               f"it must name num_hidden_layers = {L} layers, each "
               f"sliding_attention or full_attention")
    if len(set(kinds)) != 2:
        refuse("layers of one kind only",
               "the K/V pools are one a kind of layer; a model whose "
               "layers all see the same is another module's")
    if not cfg.get("use_parallel_block", False):
        refuse("use_parallel_block false",
               "attention and the experts read ONE LayerNorm and are "
               "added once; the family's sequential form has a second "
               "norm that no leaf of this module holds")
    if cfg.get("use_qk_norm", False):
        refuse("use_qk_norm true",
               "q and k are rotated as projected; the family's q/k "
               "norm is a LayerNorm a head that is not computed")
    if cfg.get("first_k_dense_replace", 0):
        refuse(f"first_k_dense_replace {cfg['first_k_dense_replace']}",
               "every layer's second half is routed experts beside the "
               "shared ones; the module builds no dense MLP "
               "(prefix_dense_intermediate_size is read by no layer)")
    if cfg.get("rotary_pct", 1) != 1:
        refuse(f"rotary_pct {cfg['rotary_pct']}",
               "the window layers rotate all head_dim columns")
    if cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj":
        refuse(f"position_embedding_type "
               f"{cfg['position_embedding_type']!r}",
               "the window layers rotate interleaved pairs (rope_gptj)")
    if (cfg.get("rope_parameters") or {}).get("rope_type",
                                              "default") != "default" \
            or cfg.get("rope_scaling"):
        refuse("a rope_type other than default",
               "the window layers rotate by rope_theta alone")
    shared = cfg.get("num_shared_experts", 0)
    strategy = cfg.get("shared_expert_combination_strategy", "average")
    if shared and strategy != "average":
        refuse(f"shared_expert_combination_strategy {strategy!r}",
               "the shared experts' outputs are averaged and the mean "
               "is added to the routed sum")
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        refuse(f"expert_selection_fn {cfg['expert_selection_fn']!r}",
               "the gate scores by a sigmoid")
    if not cfg.get("norm_topk_prob", True):
        refuse("norm_topk_prob false",
               "the chosen sigmoid scores are renormalised")
    if not cfg.get("use_gated_activation", True) \
            or cfg.get("hidden_act", "silu") != "silu":
        refuse(f"hidden_act {cfg.get('hidden_act')!r} / "
               f"use_gated_activation "
               f"{cfg.get('use_gated_activation')}",
               "its experts are SwiGLU")
    if cfg.get("attention_bias", False):
        refuse("attention_bias true",
               "the projections are computed without a bias")
    held, width, first = held_experts(
        cfg, "num_experts", "num_experts_per_tok", refuse)
    window = int(cfg["sliding_window"])
    sliding = [k == "sliding_attention" for k in kinds]
    rope = cfg.get("rope_parameters") or {}
    return ModelConfig(
        model_type="cohere2_moe",
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        # the width of ONE expert (the file has no other key for it)
        intermediate_size=cfg["intermediate_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg.get("num_key_value_heads",
                             cfg["num_attention_heads"]),
        head_dim=cfg.get("head_dim"),
        rope_theta=cfg.get("rope_theta", rope.get("rope_theta", 50000.0)),
        rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True),
        num_experts=held, router_experts=width, first_expert=first,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        # sigmoid scores, the chosen renormalised; no selection bias,
        # no groups, no scaling factor (the file has no key for any)
        moe_router="deepseek_v3", norm_topk_prob=True,
        n_shared_experts=shared,
        shared_expert_scale=1.0 / shared if shared else 1.0,
        sliding_window=window,
        layer_window=tuple(window if s else None for s in sliding),
        layer_rope=tuple(sliding),
        kv_pool_by_kind=True, rope_interleave=True,
        parallel_block=True, layer_norm=True,
        # logits = logit_scale * (h @ E^T): project_logits divides
        logits_scaling=1.0 / float(cfg.get("logit_scale", 1.0)),
    )


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params, every leaf stacked over the L layers: ONE norm
    a layer (``ln_attn``; no ``ln_mlp``), attention without biases, the
    router at its published width, the experts HELD, and the shared
    experts side by side (``n_shared_experts`` x the expert width); the
    head only where the embedding is not tied."""
    dtype = dtype or cfg.jax_dtype
    D, L, V, I = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.intermediate_size)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    E, S = cfg.num_experts, cfg.n_shared_experts * cfg.intermediate_size
    w, _ = _drawer(key, dtype)
    p = {
        "embed": w(V, D),
        "ln_attn": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        "wq": w(L, D, H * hd), "wk": w(L, D, KV * hd),
        "wv": w(L, D, KV * hd), "wo": w(L, H * hd, D),
        "w_router": w(L, D, cfg.router_width),
        "w_gate": w(L, E, D, I), "w_up": w(L, E, D, I),
        "w_down": w(L, E, I, D),
    }
    if S:
        p.update(w_gate_s=w(L, D, S), w_up_s=w(L, D, S), w_down_s=w(L, S, D))
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p
