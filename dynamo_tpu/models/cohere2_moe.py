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

from .config import ModelConfig
from .granite import WINDOW_COUNTS  # noqa: F401  (the engine reads it)
from .kimi_linear import _drawer
from .llama import (Params, init_kv_cache, init_window_kv_cache,  # noqa: F401
                    make_decode_window_fn, make_step_fns,
                    window_table_slots)


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
