"""DeepSeek-style MLA (multi-head latent attention) with a paged latent
KV cache — the second model family (BASELINE scale-out config: MLA
workers; the reference serves DeepSeek models through its engines).

TPU-first design points:

- the KV cache stores ONLY the rank-r latent ``c_kv`` plus the shared
  rope key ``k_rope`` per token — cache bytes/token shrink by ~an order
  of magnitude vs GQA, so the same HBM pool holds proportionally more
  context (paged pools [L, pages, 1, ps, r] and [L, pages, 1, ps, dr],
  shape-compatible with the engine's generic page machinery);
- decode uses the absorbed form: W_UK is folded into the query
  (q_lat = q_nope · W_UK) and W_UV into the output, so attention runs
  entirely in latent space — two big MXU einsums per layer instead of
  materializing per-head K/V;
- prefill/decode share one program exactly like models/llama.py (scatter
  new latents into pages, gather the page table, masked attention).

Weight layout follows the DeepSeek-V2 architecture (q LoRA optional,
kv LoRA + decoupled rope head); MoE layers reuse the Mixtral-style
dense-over-experts MLP from models/llama.py.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .llama import (DROP_SLOT, KVCacheSpec, _mlp, apply_rope, logits_at,
                    rms_norm, rope_freqs)

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------- KV cache


def cache_shapes(cfg: ModelConfig, spec: KVCacheSpec):
    """(latent pool shape, rope pool shape): KV-head axis fixed at 1 so
    the engine's page gather/scatter/transfer stay shape-agnostic."""
    latent = (cfg.num_layers, spec.num_pages, 1, spec.page_size,
              cfg.kv_lora_rank)
    rope = (cfg.num_layers, spec.num_pages, 1, spec.page_size,
            cfg.qk_rope_head_dim)
    return latent, rope


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    dtype = dtype or cfg.jax_dtype
    lat, rope = cache_shapes(cfg, spec)
    return jnp.zeros(lat, dtype), jnp.zeros(rope, dtype)


# ------------------------------------------------------------------ params


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    dtype = dtype or cfg.jax_dtype
    D, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    V = cfg.vocab_size
    ks = jax.random.split(key, 14)

    def w_init(k, *shape):
        scale = 1.0 / math.sqrt(shape[-2]) if len(shape) > 1 else 0.02
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p: Params = {
        "embed": w_init(ks[0], V, D),
        # kv path: x → [c_kv (r) | k_rope (dr)]; c_kv normed before up-proj
        "w_dkv": w_init(ks[1], L, D, r + dr),
        "kv_norm": jnp.ones((L, r), dtype),
        "w_uk": w_init(ks[2], L, r, H * dn),
        "w_uv": w_init(ks[3], L, r, H * dv),
        "w_o": w_init(ks[4], L, H * dv, D),
        "w_gate": w_init(ks[5], L, D, I),
        "w_up": w_init(ks[6], L, D, I),
        "w_down": w_init(ks[7], L, I, D),
        "ln_attn": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
    }
    if cfg.q_lora_rank > 0:
        rq = cfg.q_lora_rank
        p["w_dq"] = w_init(ks[8], L, D, rq)
        p["q_norm"] = jnp.ones((L, rq), dtype)
        p["w_uq"] = w_init(ks[9], L, rq, H * (dn + dr))
    else:
        p["w_q"] = w_init(ks[9], L, D, H * (dn + dr))
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w_init(ks[10], D, V)
    if cfg.num_experts > 0:
        # DeepSeek-MoE layout: dense first-k layers keep w_*_d; MoE
        # layers carry routed experts (+ optional shared experts/bias)
        E, kd = cfg.num_experts, cfg.first_k_dense_replace
        Lm = L - kd
        Im = cfg.moe_intermediate_size or I
        del p["w_gate"], p["w_up"], p["w_down"]
        if kd > 0:
            p["w_gate_d"] = w_init(ks[5], kd, D, I)
            p["w_up_d"] = w_init(ks[6], kd, D, I)
            p["w_down_d"] = w_init(ks[7], kd, I, D)
        p["w_router"] = w_init(ks[11], Lm, D, E)
        p["w_gate_e"] = w_init(ks[5], Lm, E, D, Im)
        p["w_up_e"] = w_init(ks[6], Lm, E, D, Im)
        p["w_down_e"] = w_init(ks[7], Lm, E, Im, D)
        if cfg.moe_router == "deepseek_v3":
            p["router_bias"] = jnp.zeros((Lm, E), dtype)
        if cfg.n_shared_experts > 0:
            Is = Im * cfg.n_shared_experts
            p["w_gate_s"] = w_init(ks[12], Lm, D, Is)
            p["w_up_s"] = w_init(ks[13], Lm, D, Is)
            p["w_down_s"] = w_init(ks[12], Lm, Is, D)
    return p


# ----------------------------------------------------------------- forward


def _mla_attn_keys(cfg: ModelConfig) -> list:
    """Attention-side per-layer param names (stacked over ALL layers,
    sliced per dense/MoE segment)."""
    keys = ["w_dkv", "kv_norm", "w_uk", "w_uv", "w_o", "ln_attn",
            "ln_mlp"]
    keys += (["w_dq", "q_norm", "w_uq"] if cfg.q_lora_rank > 0
             else ["w_q"])
    return keys


def _mla_layer_keys(cfg: ModelConfig) -> list:
    """Per-layer param names scanned over the stacked-layer axis — shared
    by forward, reference_forward, and the MLA ring long-prefill
    (parallel/ring_attention.make_mla_long_prefill_fn). DENSE configs
    only; DeepSeek-MoE configs segment their params (see forward)."""
    return _mla_attn_keys(cfg) + ["w_gate", "w_up", "w_down"]


def _moe_layer_params(cfg: ModelConfig, params: Params) -> dict:
    """The MoE segment's per-layer params (stacked over layers
    [first_k_dense_replace, L))."""
    lp = {"w_router": params["w_router"], "w_gate_e": params["w_gate_e"],
          "w_up_e": params["w_up_e"], "w_down_e": params["w_down_e"]}
    if cfg.moe_router == "deepseek_v3":
        lp["router_bias"] = params["router_bias"]
    if cfg.n_shared_experts > 0:
        lp.update({k: params[k] for k in ("w_gate_s", "w_up_s",
                                          "w_down_s")})
    return lp


def _deepseek_gate(x32, w_router, bias, cfg: ModelConfig):
    """DeepSeek router → (weights [B, T, k], expert indices [B, T, k]).

    v2 (HF DeepseekV2MoEGate): softmax scores; optional group limiting by
    the MAX score per group; top-k; weights scaled (NOT renormalized).
    v3 (HF DeepseekV3TopkRouter): sigmoid scores; selection by scores +
    e_score_correction_bias with groups ranked by their top-2 SUM; the
    applied weights are the ORIGINAL sigmoid scores of the selected
    experts, optionally renormalized, then scaled."""
    E = w_router.shape[-1]
    k = cfg.num_experts_per_tok
    logits = x32 @ w_router.astype(jnp.float32)
    if cfg.moe_router == "deepseek_v3":
        scores = jax.nn.sigmoid(logits)
        choice = scores + bias.astype(jnp.float32)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
    if cfg.n_group > 0 and cfg.topk_group > 0:
        G = cfg.n_group
        cg = choice.reshape(*choice.shape[:-1], G, E // G)
        if cfg.moe_router == "deepseek_v3":
            g_scores = jnp.sum(lax.top_k(cg, 2)[0], axis=-1)
        else:
            g_scores = jnp.max(cg, axis=-1)
        _, g_idx = lax.top_k(g_scores, cfg.topk_group)
        g_mask = jnp.sum(jax.nn.one_hot(g_idx, G, dtype=jnp.float32),
                         axis=-2)
        choice = jnp.where(g_mask[..., :, None] > 0, cg,
                           0.0).reshape(choice.shape)
    _, topi = lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, topi, axis=-1)
    # v3 (HF DeepseekV3TopkRouter): optional renorm, then ALWAYS scaled.
    # v2: transformers' DeepseekV2MoEGate ignores norm_topk_prob (always
    # scales); configs setting it are rejected at ModelConfig load.
    if cfg.moe_router == "deepseek_v3" and cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    return w, topi


def _dense_gate(w, topi, E):
    """(weights, indices) → dense [B, T, E] mask for the
    dense-over-experts einsum path."""
    return jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32)
                   * w[..., None], axis=-2)


def _deepseek_moe_mlp(x: jax.Array, lp, cfg: ModelConfig,
                      mesh=None) -> jax.Array:
    """Routed experts plus the always-on shared experts. Large
    dispatches on an unsharded expert axis use the sorted blocked
    dispatch (~top_k/E of the dense FLOPs — with E up to 256 on
    DeepSeek-V3 the dense-over-experts einsum is ~32x waste); decode-
    sized dispatches and expert-parallel meshes keep the dense einsum
    (see llama._moe_mlp for the strategy rationale)."""
    from .llama import _moe_use_blocked, moe_block, moe_experts_blocked

    B, T, D = x.shape
    E = lp["w_gate_e"].shape[0]
    x32 = x.astype(jnp.float32)
    w, topi = _deepseek_gate(x32, lp["w_router"],
                             lp.get("router_bias"), cfg)
    k = cfg.num_experts_per_tok
    if _moe_use_blocked(mesh, B * T, E, k):
        out = moe_experts_blocked(
            x32.reshape(B * T, D), w.reshape(B * T, k),
            topi.reshape(B * T, k), lp["w_gate_e"], lp["w_up_e"],
            lp["w_down_e"],
            moe_block(B * T, k, lp["w_gate_e"].shape)).reshape(B, T, D)
    else:
        gate = _dense_gate(w, topi, E)
        ge = jnp.einsum("btd,edi->btei", x32,
                        lp["w_gate_e"].astype(jnp.float32))
        up = jnp.einsum("btd,edi->btei", x32,
                        lp["w_up_e"].astype(jnp.float32))
        act = jax.nn.silu(ge) * up
        down = jnp.einsum("btei,eid->bted", act,
                          lp["w_down_e"].astype(jnp.float32))
        out = jnp.einsum("bted,bte->btd", down, gate)
    if cfg.n_shared_experts > 0:
        out = out + _mlp(x32, lp["w_gate_s"].astype(jnp.float32),
                         lp["w_up_s"].astype(jnp.float32),
                         lp["w_down_s"].astype(jnp.float32))
    return out.astype(x.dtype)


def _scatter_rows(cache_layer: jax.Array, new: jax.Array,
                  flat_slots: jax.Array) -> jax.Array:
    """cache_layer: [pages, 1, ps, d]; new: [B, T, d]; flat_slots [B, T]
    (page*ps + off; DROP_SLOT pads)."""
    _, _, ps, d = cache_layer.shape
    idx = flat_slots.reshape(-1)
    pages, offs = idx // ps, idx % ps
    rows = new.reshape(-1, d).astype(cache_layer.dtype)
    return cache_layer.at[pages, 0, offs].set(rows, mode="drop")


def _mla_attention(q_lat, q_rope, c_pages, r_pages, page_table,
                   q_positions, scale):
    """Latent-space paged attention.

    q_lat: [B, T, H, r] (absorbed queries); q_rope: [B, T, H, dr];
    c_pages: [pages, 1, ps, r]; r_pages: [pages, 1, ps, dr];
    page_table: [B, P]; q_positions: [B, T]. Returns [B, T, H, r]
    (latent-space context, to be up-projected by W_UV)."""
    B, T, H, r = q_lat.shape
    _, _, ps, dr = r_pages.shape
    P = page_table.shape[1]
    S = P * ps

    c = c_pages[page_table].reshape(B, S, r)  # [B, P, 1, ps, r] → [B, S, r]
    kr = r_pages[page_table].reshape(B, S, dr)
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(jnp.float32),
                         c.astype(jnp.float32))
              + jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32),
                           kr.astype(jnp.float32))) * scale
    mask = (jnp.arange(S)[None, None, :] <= q_positions[:, :, None])
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bsr->bthr", probs, c.astype(jnp.float32))
    return out


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv_lat: jax.Array, kv_rope: jax.Array,
            page_table: jax.Array, flat_slots: jax.Array,
            allow_pallas: bool = True, mesh=None,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Same signature/contract as llama.forward; (kv_k, kv_v) ≡
    (latent pool, rope pool)."""
    del allow_pallas  # latent attention is XLA-einsum throughout;
    # mesh is only consulted to pick the MoE dispatch strategy
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    H = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)
    B, T = tokens.shape

    h = params["embed"][tokens]
    safe_pos = jnp.maximum(positions, 0)

    def layer_with(mlp_apply):
        def layer(h, xs):
            lp, c_layer, r_layer = xs
            x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
            # queries
            if cfg.q_lora_rank > 0:
                q_all = rms_norm(x @ lp["w_dq"], lp["q_norm"],
                                 cfg.rms_norm_eps) @ lp["w_uq"]
            else:
                q_all = x @ lp["w_q"]
            q_all = q_all.reshape(B, T, H, dn + dr)
            q_nope, q_rope = q_all[..., :dn], q_all[..., dn:]
            q_rope = apply_rope(q_rope, safe_pos, inv_freq)
            # kv latent + shared rope key
            ckr = x @ lp["w_dkv"]  # [B, T, r + dr]
            c_kv = rms_norm(ckr[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
            k_rope = apply_rope(ckr[..., None, r:], safe_pos,
                                inv_freq)[..., 0, :]  # one shared rope head
            c_layer = _scatter_rows(c_layer, c_kv, flat_slots)
            r_layer = _scatter_rows(r_layer, k_rope, flat_slots)
            # absorbed attention: q_lat = q_nope · W_UK (per head)
            w_uk = lp["w_uk"].reshape(r, H, dn)
            q_lat = jnp.einsum("bthd,rhd->bthr",
                               q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            out_lat = _mla_attention(q_lat, q_rope, c_layer, r_layer,
                                     page_table, positions, scale)
            # up-project latent context per head: out = out_lat · W_UV
            w_uv = lp["w_uv"].reshape(r, H, dv)
            out = jnp.einsum("bthr,rhd->bthd", out_lat,
                             w_uv.astype(jnp.float32))
            h2 = h + out.reshape(B, T, H * dv).astype(h.dtype) @ lp["w_o"]
            x = rms_norm(h2, lp["ln_mlp"], cfg.rms_norm_eps)
            return h2 + mlp_apply(x, lp), (c_layer, r_layer)

        return layer

    if cfg.num_experts == 0:
        layer_params = {k: params[k] for k in _mla_layer_keys(cfg)}
        dense = layer_with(
            lambda x, lp: _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"]))
        h, (new_c, new_r) = lax.scan(dense, h,
                                     (layer_params, kv_lat, kv_rope))
    else:
        # DeepSeek-MoE: dense first-k layers, then MoE layers — two scans
        # over layer segments (per-segment param stacks; the pools are
        # sliced/concatenated, an extra copy the small latent cache
        # affords)
        kd = cfg.first_k_dense_replace
        attn = {k: params[k] for k in _mla_attn_keys(cfg)}
        seg_a = jax.tree.map(lambda a: a[:kd], attn)
        seg_b = jax.tree.map(lambda a: a[kd:], attn)
        new_c_parts, new_r_parts = [], []
        if kd > 0:
            seg_a.update({k: params[f"{k}_d"]
                          for k in ("w_gate", "w_up", "w_down")})
            dense = layer_with(lambda x, lp: _mlp(
                x, lp["w_gate"], lp["w_up"], lp["w_down"]))
            h, (c_a, r_a) = lax.scan(dense, h,
                                     (seg_a, kv_lat[:kd], kv_rope[:kd]))
            new_c_parts.append(c_a)
            new_r_parts.append(r_a)
        seg_b.update(_moe_layer_params(cfg, params))
        moe = layer_with(
            lambda x, lp: _deepseek_moe_mlp(x, lp, cfg, mesh=mesh))
        h, (c_b, r_b) = lax.scan(moe, h,
                                 (seg_b, kv_lat[kd:], kv_rope[kd:]))
        new_c_parts.append(c_b)
        new_r_parts.append(r_b)
        new_c = jnp.concatenate(new_c_parts, axis=0)
        new_r = jnp.concatenate(new_r_parts, axis=0)
    h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps)
    return h, new_c, new_r


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """Jitted (prefill_step, decode_step); same contract as llama.
    Latent attention is XLA-einsum based throughout, so the pallas
    kernel knob is accepted for interface parity and ignored (GSPMD
    shards the einsums directly); mesh only picks the MoE dispatch
    strategy (expert-sharded meshes keep the dense einsum)."""
    del allow_pallas

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None):
        # page_slots accepted for engine-contract parity with llama; the
        # MLA latent cache keeps the row-scatter commit (its pages hold
        # compressed latents, not per-head K/V blocks)
        del page_slots
        h, k2, v2 = forward(params, cfg, tokens, positions, kv_k, kv_v,
                            page_table, flat_slots, mesh=mesh)
        return logits_at(params, cfg, h, last_idx), k2, v2

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots):
        h, k2, v2 = forward(params, cfg, tokens[:, None], positions[:, None],
                            kv_k, kv_v, page_table, flat_slots[:, None],
                            mesh=mesh)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)), k2, v2)

    return prefill_step, decode_step


# -------------------------------------------------- full-attention reference


def reference_forward(params: Params, cfg: ModelConfig,
                      tokens: jax.Array) -> jax.Array:
    """Non-paged, non-absorbed MLA forward (materializes per-head K/V) —
    the independent oracle for the paged/absorbed path."""
    B, T = tokens.shape
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    H = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    h = params["embed"][tokens]

    def layer(h, lp, mlp_apply):
        x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
        if cfg.q_lora_rank > 0:
            q_all = rms_norm(x @ lp["w_dq"], lp["q_norm"],
                             cfg.rms_norm_eps) @ lp["w_uq"]
        else:
            q_all = x @ lp["w_q"]
        q_all = q_all.reshape(B, T, H, dn + dr)
        q_nope, q_rope = q_all[..., :dn], q_all[..., dn:]
        q_rope = apply_rope(q_rope, pos, inv_freq)
        ckr = x @ lp["w_dkv"]
        c_kv = rms_norm(ckr[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
        k_rope = apply_rope(ckr[..., None, r:], pos, inv_freq)[..., 0, :]
        # materialized per-head keys/values (the non-absorbed form)
        k_nope = jnp.einsum("btr,rhd->bthd", c_kv.astype(jnp.float32),
                            lp["w_uk"].reshape(r, H, dn).astype(jnp.float32))
        v = jnp.einsum("btr,rhd->bthd", c_kv.astype(jnp.float32),
                       lp["w_uv"].reshape(r, H, dv).astype(jnp.float32))
        scores = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(jnp.float32),
                             k_nope)
                  + jnp.einsum("bthd,bsd->bhts",
                               q_rope.astype(jnp.float32),
                               k_rope.astype(jnp.float32))) * scale
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", probs, v)
        h = h + out.reshape(B, T, H * dv).astype(h.dtype) @ lp["w_o"]
        x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
        return h + mlp_apply(x, lp)

    # oracle path: plain per-layer Python loop (unrolled trace; test-sized)
    dense_mlp = lambda x, lp: _mlp(x, lp["w_gate"], lp["w_up"],
                                   lp["w_down"])
    for li in range(cfg.num_layers):
        if cfg.num_experts == 0:
            lp = {k: params[k][li] for k in _mla_layer_keys(cfg)}
            h = layer(h, lp, dense_mlp)
        elif li < cfg.first_k_dense_replace:
            lp = {k: params[k][li] for k in _mla_attn_keys(cfg)}
            lp.update({k: params[f"{k}_d"][li]
                       for k in ("w_gate", "w_up", "w_down")})
            h = layer(h, lp, dense_mlp)
        else:
            mi = li - cfg.first_k_dense_replace
            lp = {k: params[k][li] for k in _mla_attn_keys(cfg)}
            lp.update({k: v[mi]
                       for k, v in _moe_layer_params(cfg, params).items()})
            h = layer(h, lp, lambda x, lp: _deepseek_moe_mlp(x, lp, cfg))
    h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return (h @ head).astype(jnp.float32)
