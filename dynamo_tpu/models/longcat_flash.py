"""LongCat-Flash's language model (``longcat_flash``: the shortcut-connected
MoE with zero-computation experts of the LongCat-Flash technical report,
over DeepSeek-style latent attention), on models/mla.py's latent cache.

A layer is TWO sub-blocks and ONE routed MoE that spans them. With
``rms`` the RMS norm and ``F`` a SwiGLU MLP, sub-blocks i in (0, 1):

    a = rms(h; ln_attn[2l + i]);  h = h + MLA_{2l+i}(a)
    x = rms(h; ln_mlp[2l + i])
    if i == 0:  s = MoE_l(x)            the shortcut: read here ...
    h = h + F(x; w_*_d[2l + i])          dense MLP, ``ffn_hidden_size`` wide
    after i == 1:  h = h + s            ... and added here

so the MoE's result is live across an attention and an MLP, and the
compiler may place its work anywhere between the two points. The scan
over layers carries ``h``; ``s`` lives inside one iteration.

- **MLA** is mla.py's, not a copy: ``mla._latent_qkv`` (the query-LoRA
  arm, with ``cfg.mla_q_scale`` = sqrt(D / q_lora_rank) on the queries
  and ``cfg.mla_kv_scale`` = sqrt(D / kv_lora_rank) on the normed latent,
  which is what the cache keeps), ``_attend_pool`` / ``_attend_local`` /
  ``_merge`` (the Pallas latent kernels on the chip), ``_latent_out``,
  ``_commit_chunk``. The latent and rope pools have ``2 x num_layers``
  entries: sub-block (l, i) is entry ``2l + i`` (``init_kv_cache``), and
  every attention-side leaf is stacked over the same 2L axis. A page
  holds everything a prefix is, so the prefix cache, the host tier and
  the KV transfer serve this family as they serve ``mla``.
- **MoE**: ``llama.deepseek_gate``'s ``longcat_flash`` arm (softmax over
  ALL the router's outputs in float32, selection by score + bias, no
  groups, the chosen scores times ``routed_scaling_factor`` and NOT
  renormalised) over ``router_width`` = real experts + ``zero_experts``
  outputs, and ``llama.moe_experts`` with ``identity_from``: a pair whose
  index is past the real experts is an identity expert and adds the
  token itself times its weight (scope ``moe.zero``). The stacks hold the
  chip's share ``[first_expert, first_expert + num_experts)`` of the REAL
  experts (the file's ``router_num_experts`` / ``first_local_expert``,
  as kimi_linear.py's); the identity part is computed for every row of
  THIS chip, whoever holds the real experts: it costs nothing and goes
  with the token, so over the shares of a layer it is counted once
  (tests/test_longcat_flash.py adds four shares up).
- The decode window counts, a live row-step a layer, the pairs the gate
  chose, those whose expert is held here and those that chose an
  identity expert (``WINDOW_COUNTS``).

Scopes: ``attn`` > ``attn.0`` / ``attn.1`` (the sub-block) > ``attn.proj``
(query LoRA, latent and output projections) and ``attn.latent``; ``mlp``
(the dense MLPs); ``moe`` > ``moe.router`` / ``moe.dispatch`` /
``moe.experts`` / ``moe.zero``.

``mesh``: the leaves carry mla.py's names (parallel/mesh.py
``param_pspecs``: heads over "model" in the up-projections and ``w_o``,
``w_*_d`` megatron-style, ``w_*_e`` over "expert"), the pools are
replicated as mla's are, and under a mesh of more than one device every
program takes the XLA attention arm and the dense-over-experts form
(``llama.kernel_mode``, ``_moe_use_blocked``), which GSPMD shards; the
identity part is elementwise on the token. The ring long prefill
(``long_prefill_threshold``) refuses every latent model with experts
(parallel/ring_attention.py), this one included.

The rotation: the published checkpoints rotate interleaved pairs; as for
every latent model here a loader would de-interleave the rope columns
once (models/loader.py; no name mapping for this family yet: that waits
for files) and the programs rotate in the half-split form.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import llama, mla
from .config import ModelConfig, held_experts, refuser
from .granite import WINDOW_COUNTS as _PAIRS_ROUTED_HELD
from .llama import (KVCacheSpec, _at, _mlp, _moe_use_blocked, commit_window,
                    held_first, logits_at, pairs_counted, prefill_logits,
                    rms_norm, rope_freqs)
from .window import Family, make_window

Params = Dict[str, jax.Array]

# what a window's steps count (models/window.py): granite.py's two and
# the pairs that chose an identity expert
WINDOW_COUNTS = _PAIRS_ROUTED_HELD + ("moe_pairs_identity_total",)

ATTN_KEYS = ("w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv",
             "w_o", "ln_attn", "ln_mlp")
DENSE_KEYS = ("w_gate_d", "w_up_d", "w_down_d")
ROUTER_KEYS = ("w_router", "router_bias")
EXPERT_KEYS = ("w_gate_e", "w_up_e", "w_down_e")


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``longcat_flash`` config.json as published
    (``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
    ``moe_topk``, ``n_routed_experts``, ``zero_expert_num``, ...), plus
    this repo's two for a chip's share of the real experts
    (``router_num_experts``: the published count the stacks are a slice
    of; ``first_local_expert``)."""
    refuse = refuser("longcat_flash")
    if cfg.get("zero_expert_type", "identity") != "identity":
        refuse(f"zero_expert_type {cfg['zero_expert_type']!r}",
               "only identity zero-computation experts are written")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "no scaled rotation is written for the "
               "latent path")
    if cfg.get("attention_bias"):
        refuse("attention_bias true", "the latent projections carry none")
    if not cfg.get("q_lora_rank"):
        refuse("no q_lora_rank", "the family's queries go through a LoRA")
    zero = cfg.get("zero_expert_num", 0)
    held, real, first = held_experts(cfg, "n_routed_experts", "moe_topk",
                                     refuse)
    D = cfg["hidden_size"]

    def lora_scale(key: str, rank: int) -> float:
        return math.sqrt(D / rank) if cfg.get(key, False) else 1.0

    return ModelConfig(
        model_type="longcat_flash",
        vocab_size=cfg["vocab_size"], hidden_size=D,
        intermediate_size=cfg["ffn_hidden_size"],
        num_layers=cfg["num_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"],
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg.get("kv_lora_rank", 512),
        qk_nope_head_dim=cfg.get("qk_nope_head_dim", 128),
        qk_rope_head_dim=cfg.get("qk_rope_head_dim", 64),
        v_head_dim=cfg.get("v_head_dim", 128),
        rope_interleave=True,
        mla_q_scale=lora_scale("mla_scale_q_lora", cfg["q_lora_rank"]),
        mla_kv_scale=lora_scale("mla_scale_kv_lora",
                                cfg.get("kv_lora_rank", 512)),
        num_experts=held, num_experts_per_tok=cfg["moe_topk"],
        moe_router="longcat_flash",
        moe_intermediate_size=cfg["expert_ffn_hidden_size"],
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        router_experts=real + zero, first_expert=first, zero_experts=zero)


# ---------------------------------------------------------------- KV cache


def _sub_blocks(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` as mla.py's shape functions read it: a pool entry and an
    attention-side leaf a SUB-BLOCK, two a layer."""
    return dataclasses.replace(cfg, num_layers=2 * cfg.num_layers)


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    """(latent pool, rope pool) of ``2 x num_layers`` entries: sub-block
    (l, i) is entry ``2l + i``; a page's geometry is mla.py's
    (``mla.cache_shapes``, ``mla.rope_width``)."""
    return mla.init_kv_cache(_sub_blocks(cfg), spec, dtype)


# ------------------------------------------------------------------ params


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    dtype = dtype or cfg.jax_dtype
    D, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, E, Ie = cfg.num_heads, cfg.num_experts, cfg.moe_intermediate_size
    r, dr, rq = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.q_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    V = cfg.vocab_size
    ks = iter(jax.random.split(key, 16))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    p: Params = {
        "embed": w(V, D),
        # attention side, a sub-block: [2L, ...]
        "w_dq": w(2 * L, D, rq),
        "q_norm": jnp.ones((2 * L, rq), dtype),
        "w_uq": w(2 * L, rq, H * (dn + dr)),
        "w_dkv": w(2 * L, D, r + dr),
        "kv_norm": jnp.ones((2 * L, r), dtype),
        "w_uk": w(2 * L, r, H * dn),
        "w_uv": w(2 * L, r, H * dv),
        "w_o": w(2 * L, H * dv, D),
        "ln_attn": jnp.ones((2 * L, D), dtype),
        "ln_mlp": jnp.ones((2 * L, D), dtype),
        # the dense MLP of every sub-block
        "w_gate_d": w(2 * L, D, I),
        "w_up_d": w(2 * L, D, I),
        "w_down_d": w(2 * L, I, D),
        # the shortcut MoE, one a layer: the router scores the real
        # experts AND the identity ones, the stacks hold the share
        "w_router": w(L, D, cfg.router_width),
        "router_bias": jnp.zeros((L, cfg.router_width), dtype),
        "w_gate_e": w(L, E, D, Ie),
        "w_up_e": w(L, E, D, Ie),
        "w_down_e": w(L, E, Ie, D),
        "ln_final": jnp.ones((D,), dtype),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p


# ----------------------------------------------------------------- forward


def _moe(params: Params, cfg: ModelConfig, x, l, in_place: bool, live,
         valid):
    """(the shortcut MoE of layer l on the normed x [B, T, D], in x's
    type; ``WINDOW_COUNTS`` of the ``valid`` rows or None)."""
    with jax.named_scope("moe"):
        lp = _at(params, ROUTER_KEYS, l)
        with jax.named_scope("moe.router"):
            gate = llama.deepseek_gate(x.astype(jnp.float32),
                                       lp["w_router"], lp["router_bias"],
                                       cfg)
            counted = (None if valid is None
                       else pairs_counted(cfg, gate[1], valid))
        # the sorted form reads w[layer, expert] from the whole stacks,
        # the dense form one layer's (llama._moe_use_blocked: the rule)
        lp.update({k: params[k] for k in EXPERT_KEYS} if in_place
                  else _at(params, EXPERT_KEYS, l))
        out = llama.deepseek_moe_mlp(
            x, lp, cfg, live=live if in_place else None,
            layer=l if in_place else None, first=held_first(cfg), gate=gate)
    return out, counted


def _layers(params: Params, cfg: ModelConfig, h, attend, cache, mesh=None,
            live=None, valid=None):
    """All layers on h [B, T, D]: mla._layers' contract with two
    sub-blocks a layer. ``attend(j, lp, x, cache_j) -> (out_lat, cache_j)``
    is the latent attention of pool entry ``j = 2l + i``; ``cache`` is a
    pytree with a leading axis of 2L entries (the window's buffers) or
    None (a prefill chunk, which gets its own latents back stacked
    [2L, ...]). Returns (h, cache, counts): ``counts`` the
    ``WINDOW_COUNTS`` of the ``valid`` rows summed over the layers, or
    None."""
    B, T, _ = h.shape
    L = cfg.num_layers
    in_place = _moe_use_blocked(mesh, B * T, cfg.router_width,
                                cfg.num_experts_per_tok)

    def layer(h, xs):
        l, cache_l = xs
        kept = []
        for i in (0, 1):
            j = 2 * l + i
            lp = _at(params, ATTN_KEYS, j)
            with jax.named_scope("attn"), jax.named_scope(f"attn.{i}"):
                x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
                out_lat, cache_j = attend(
                    j, lp, x, jax.tree.map(lambda a: a[i], cache_l))
                with jax.named_scope("attn.proj"):
                    h = h + mla._latent_out(cfg, lp, out_lat, h.dtype)
            kept.append(cache_j)
            x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
            if i == 0:
                s, counted = _moe(params, cfg, x, l, in_place, live, valid)
            dp = _at(params, DENSE_KEYS, j)
            h = h + _mlp(x, *(dp[k] for k in DENSE_KEYS))  # scope ``mlp``
        h = h + s
        return h, (jax.tree.map(lambda *a: jnp.stack(a), *kept), counted)

    pairs = jax.tree.map(lambda a: a.reshape(L, 2, *a.shape[1:]), cache)
    h, (cache, counted) = lax.scan(
        layer, h, (jnp.arange(L, dtype=jnp.int32), pairs))
    cache = jax.tree.map(lambda a: a.reshape(2 * L, *a.shape[2:]), cache)
    return h, cache, (None if counted is None else jnp.sum(counted, axis=0))


def _qkv(cfg: ModelConfig, lp, x, safe_pos, inv_freq, dtype):
    with jax.named_scope("attn.proj"):
        return mla._latent_qkv(cfg, lp, x, safe_pos, inv_freq, dtype)


def _scale(cfg: ModelConfig) -> float:
    # the softmax scale of the published head size, no mscale
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv_lat: jax.Array, kv_rope: jax.Array,
            page_table: jax.Array, flat_slots: jax.Array,
            allow_pallas: bool = True, mesh=None, page_slots=None,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """mla.forward's signature and contract over pools of 2L entries."""
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    scale = _scale(cfg)
    kernel = llama.kernel_mode(allow_pallas, mesh=mesh)
    h = params["embed"][tokens]
    safe_pos = jnp.maximum(positions, 0)
    live = positions >= 0
    before = jnp.maximum(positions[:, 0], 0)           # [B] pool extent
    own = (live[:, None, :]
           & (positions[:, None, :] <= positions[:, :, None]))  # [B, T, T]

    def attend(j, lp, x, _):
        q_lat, q_rope, c_kv, k_rope = _qkv(cfg, lp, x, safe_pos, inv_freq,
                                           kv_lat.dtype)
        with jax.named_scope("attn.latent"):
            out = mla._merge(
                mla._attend_pool(q_lat, q_rope, kv_lat, kv_rope, j,
                                 page_table, before, scale, kernel),
                mla._attend_local(q_lat, q_rope, c_kv, k_rope, own, scale))
        return out, (c_kv, k_rope)

    h, (c_new, r_new), _ = _layers(params, cfg, h, attend, None, mesh=mesh,
                                   live=live)
    with jax.named_scope("kv_carry"):
        kv_lat = mla._commit_chunk(kv_lat, c_new, flat_slots, page_slots)
        kv_rope = mla._commit_chunk(kv_rope, r_new, flat_slots, page_slots)
    h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps)
    return h, kv_lat, kv_rope


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """Jitted (prefill_step, decode_step); same contract as llama."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None):
        h, k2, v2 = forward(params, cfg, tokens, positions, kv_k, kv_v,
                            page_table, flat_slots,
                            allow_pallas=allow_pallas, mesh=mesh,
                            page_slots=page_slots)
        return prefill_logits(params, cfg, h, last_idx), k2, v2

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots):
        h, k2, v2 = forward(params, cfg, tokens[:, None], positions[:, None],
                            kv_k, kv_v, page_table, flat_slots[:, None],
                            allow_pallas=allow_pallas, mesh=mesh)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)), k2, v2)

    return prefill_step, decode_step


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False):
    """mla.make_decode_window_fn's window over pools and buffers of 2L
    entries, with the window's counts (``WINDOW_COUNTS``)."""
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    scale = _scale(cfg)
    kernel = llama.kernel_mode(allow_pallas, pallas_interpret, mesh)
    L2 = 2 * cfg.num_layers

    def begin(w):
        B, K = w.start.shape[0], w.k_steps
        before = jnp.maximum(w.start, 0)
        wc = jnp.zeros((L2, B, K, 1, w.kv_k.shape[-1]), w.kv_k.dtype)
        wr = jnp.zeros((L2, B, K, 1, w.kv_v.shape[-1]), w.kv_v.dtype)
        return wc, wr, before, jnp.arange(K, dtype=jnp.int32)

    def step(w, bufs, tok, pos, active, i):
        # frozen (done / padding) rows flow through the matmuls; their
        # outputs are discarded and their latents never commit
        wc, wr, before, slot = bufs
        safe_pos = jnp.maximum(pos, 0)[:, None]
        seen = ((slot[None, :] <= i)
                & (w.start[:, None] >= 0))[:, None, :]      # [B, 1, K]

        def attend(j, lp, x, bufs):
            wc_j, wr_j = bufs
            q_lat, q_rope, c_kv, k_rope = _qkv(cfg, lp, x, safe_pos,
                                               inv_freq, wc.dtype)
            wc_j = wc_j.at[:, i, 0].set(c_kv[:, 0])
            wr_j = wr_j.at[:, i, 0].set(k_rope[:, 0])
            with jax.named_scope("attn.latent"):
                out = mla._merge(
                    mla._attend_pool(q_lat, q_rope, w.kv_k, w.kv_v, j,
                                     w.page_table, before, scale, kernel),
                    mla._attend_local(q_lat, q_rope, wc_j[:, :, 0],
                                      wr_j[:, :, 0], seen, scale))
            return out, (wc_j, wr_j)

        h = w.params["embed"][tok][:, None]                 # [B, 1, D]
        h, (wc, wr), counted = _layers(w.params, cfg, h, attend, (wc, wr),
                                       mesh=mesh, valid=active[:, None])
        h = rms_norm(h, w.params["ln_final"], cfg.rms_norm_eps)
        return (logits_at(w.params, cfg, h,
                          jnp.zeros(tok.shape[0], jnp.int32)),
                (wc, wr, before, slot), counted)

    def commit(w, bufs, pos):
        wc, wr = bufs[:2]
        return (commit_window(w.kv_k, wc, w.page_table, w.start, pos),
                commit_window(w.kv_v, wr, w.page_table, w.start, pos), None)

    return make_window(Family(begin, step, commit), max_top_k)
