"""DeepSeek-style MLA (multi-head latent attention) with a paged latent
KV cache — the second model family (BASELINE scale-out config: MLA
workers; the reference serves DeepSeek models through its engines).

TPU-first design points:

- the KV cache stores ONLY the rank-r latent ``c_kv`` plus the shared
  rope key ``k_rope`` per token — cache bytes/token shrink by ~an order
  of magnitude vs GQA, so the same HBM pool holds proportionally more
  context (paged pools [L, pages, 1, ps, r] and [L, pages, 1, ps,
  rope_width], shape-compatible with the engine's generic page
  machinery);
- every program uses the absorbed form: W_UK is folded into the query
  (q_lat = q_nope · W_UK) and W_UV into the output, so attention runs
  entirely in latent space against ONE latent head — each cached token
  is read once for all heads, and no per-head K/V is materialized;
- the pools are read-only inside every program (prefill chunk, single
  step, fused decode window): the program's own tokens wait in a small
  buffer, attention merges the pool's part and the buffer's part by
  online-softmax statistics, and one commit per pool writes whole pages
  (or token rows) along the pool's major axis, in place;
- on the chip decode and multi-row prefill chunks read the pool through
  the Pallas latent kernels (ops/paged_attention.py: a decode step a
  grid step a row, the row's own pages copied by the kernel,
  latent_attention_decode_layered; a prefill chunk 1,024 (token, head)
  rows a block, latent_attention_layered); a one-row chunk, and
  everything off the chip, through a blockwise XLA arm (_attend_pool
  has the measurements). Neither forms [B, H, T, S].

Weight layout follows the DeepSeek-V2 architecture (q LoRA optional,
kv LoRA + decoupled rope head); an expert layer runs the DeepSeek gate
and ``llama.moe_experts`` (two forms, the dense einsum over every expert
and the sorted dispatch, and a chip's held share of the experts), the
execution every gate of models/llama.py shares.

The latent functions here (``_latent_qkv``, ``_latent_out``,
``_attend_pool``, ``_attend_local``, ``_merge``, ``_commit_chunk``) are
read by models/kimi_linear.py (its attending layers) and by
models/longcat_flash.py (two latent attentions a layer over pools of
2 x num_layers entries, the query LoRA with its two scales).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.paged_attention import (NEG_INF, latent_attention_decode_layered,
                                   latent_attention_prefill_layered)
from .config import ModelConfig, hf_base
from . import llama
from .llama import (KVCacheSpec, _at, _mlp, _moe_use_blocked, apply_rope,
                    commit_window, logits_at, prefill_logits, rms_norm,
                    rope_freqs)
from .window import Family, make_window

Params = Dict[str, jax.Array]


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``deepseek_v2`` / ``deepseek_v3`` config.json."""
    mt = cfg["model_type"]
    c = hf_base(cfg)
    c.model_type = mt
    c.q_lora_rank = cfg.get("q_lora_rank") or 0
    c.kv_lora_rank = cfg.get("kv_lora_rank", 512)
    c.qk_nope_head_dim = cfg.get("qk_nope_head_dim", 128)
    c.qk_rope_head_dim = cfg.get("qk_rope_head_dim", 64)
    c.v_head_dim = cfg.get("v_head_dim", 128)
    c.num_experts = cfg.get("n_routed_experts") or 0
    c.num_experts_per_tok = cfg.get("num_experts_per_tok", 2)
    c.rope_interleave = cfg.get("rope_interleave", True)
    if c.num_experts > 0:
        c.moe_router = mt
        c.n_shared_experts = cfg.get("n_shared_experts") or 0
        c.first_k_dense_replace = cfg.get("first_k_dense_replace", 0)
        c.moe_intermediate_size = cfg.get("moe_intermediate_size")
        c.routed_scaling_factor = cfg.get("routed_scaling_factor", 1.0)
        c.norm_topk_prob = cfg.get("norm_topk_prob", False)
        if mt == "deepseek_v2" and c.norm_topk_prob:
            # The installed transformers DeepseekV2MoEGate ignores this
            # flag (always scales, never renormalizes) while DeepSeek's
            # remote-code gate renormalizes instead of scaling: two
            # conflicting oracles, and no published V2 checkpoint sets
            # it. Reject loudly rather than silently diverging from
            # either.
            raise NotImplementedError(
                "deepseek_v2 with norm_topk_prob=true is not supported "
                "(conflicting reference semantics)")
        if mt == "deepseek_v3" or cfg.get("topk_method",
                                          "greedy") != "greedy":
            # v2 "greedy" routes without group limiting; v3 is always
            # group-limited (noaux_tc)
            c.n_group = cfg.get("n_group") or 0
            c.topk_group = cfg.get("topk_group") or 0
    return c


# ---------------------------------------------------------------- KV cache


_LANES = 128


def rope_width(cfg: ModelConfig) -> int:
    """Columns a token's rope key takes in its pool: qk_rope_head_dim,
    and where the programs run on a TPU (llama._use_pallas) rounded up
    to whole lanes of 128, the rest zeros. The TPU runtime does not
    store a pool whose minor axis is 64 wide page by page: its compact
    layout for bf16[L, pages, 1, ps, 64] makes PAGES the minor axis
    (major_to_minor (0, 2, 3, 4, 1); my chip run, PR 31), and every
    program that reads or writes a page would relayout the whole pool
    first. At 128 columns the pool is row-major and a page is one
    contiguous block, for the price of 128 B a token and layer. Other
    backends keep the pool as narrow as the key."""
    dr = cfg.qk_rope_head_dim
    return -(-dr // _LANES) * _LANES if llama._use_pallas() else dr


def cache_shapes(cfg: ModelConfig, spec: KVCacheSpec):
    """(latent pool shape, rope pool shape): KV-head axis fixed at 1 so
    the engine's page gather/scatter/transfer stay shape-agnostic."""
    latent = (cfg.num_layers, spec.num_pages, 1, spec.page_size,
              cfg.kv_lora_rank)
    rope = (cfg.num_layers, spec.num_pages, 1, spec.page_size,
            rope_width(cfg))
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


# the one sigmoid / softmax DeepSeek gate and the held-experts second half
# live in llama.py (its by-kind path runs them too, and this module
# imports that one); the names here are what lfm2.py, kimi_linear.py and
# the tests call
_deepseek_gate = llama.deepseek_gate
_deepseek_moe_mlp = llama.deepseek_moe_mlp


# --------------------------------------------------------- latent attention
#
# Every program reads the pools and never writes them before its end:
# the tokens a program adds (a prefill chunk, a decode window) keep their
# (c_kv, k_rope) in a small buffer of their own, attention is the pool's
# part (positions before the program's first) merged with the buffer's
# part by their online-softmax statistics, and ONE commit per pool writes
# the buffer in, along the pool's major axis, in place. No op has an
# output of a pool's size (tests/test_tpu_compile.py).
#
# A part is (acc, m, l): acc [B, T, H, r] float32 = sum_j exp(s_j - m) c_j
# (NOT divided by l), m [B, T, H] the running maximum, l [B, T, H] the
# sum of exp(s_j - m). An empty part is (0, NEG_INF, 0).

_POOL_BLOCK_TOKENS = 512  # cached tokens a step of the XLA arm gathers per row


def _scores(q_lat, q_rope, c, kr, scale):
    """[B, T, H, S] float32: q_lat . c + q_rope . k_r, operands in their
    own type, float32 accumulation."""
    return (jnp.einsum("bthr,bsr->bths", q_lat, c,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bthd,bsd->bths", q_rope, kr,
                         preferred_element_type=jnp.float32)) * scale


def _attend_local(q_lat, q_rope, c_loc, r_loc, mask, scale):
    """The part over a buffer: q_*: [B, T, H, *]; c_loc: [B, K, r];
    r_loc: [B, K, dr]; mask: [B, T, K] (True: visible)."""
    s = jnp.where(mask[:, :, None, :],
                  _scores(q_lat, q_rope, c_loc, r_loc, scale), NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask[:, :, None, :], jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bths,bsr->bthr", p.astype(c_loc.dtype), c_loc,
                     preferred_element_type=jnp.float32)
    return acc, m, jnp.sum(p, axis=-1)


def _attend_pool_xla(q_lat, q_rope, c_pool, r_pool, l_idx, page_table,
                     lengths, scale):
    """The part over the pool's positions < lengths[b], in plain XLA:
    blocks of _POOL_BLOCK_TOKENS tokens' pages are gathered along the
    pools' major axis and folded in by online softmax, as many blocks as the
    longest row needs (a traced bound: a 96-token prompt in a
    9,216-token bucket runs one block, not eighteen). Nothing is [B, H, T, P * ps]:
    a block's scores are [B, T, H, 512] float32. The arm of a one-row
    chunk everywhere, and of every program where the kernels are off
    (the CPU, DYN_DISABLE_PALLAS, a mesh of more than one device)."""
    B, T, H, r = q_lat.shape
    L, NP, _, ps, _ = c_pool.shape
    P = page_table.shape[1]
    nb = min(max(_POOL_BLOCK_TOKENS // ps, 1), P)
    S = nb * ps
    pt = jnp.pad(page_table, ((0, 0), (0, -P % nb)))
    cf = c_pool.reshape(L * NP, ps, r)
    rf = r_pool.reshape(L * NP, ps, r_pool.shape[-1])
    n_blocks = (jnp.max(lengths) + S - 1) // S

    def block(j, part):
        acc, m, l = part
        idx = l_idx * NP + lax.dynamic_slice_in_dim(pt, j * nb, nb, axis=1)
        c = cf[idx].reshape(B, S, r)
        kr = rf[idx].reshape(B, S, -1)
        valid = ((j * S + jnp.arange(S, dtype=jnp.int32))[None, :]
                 < lengths[:, None])[:, None, None, :]       # [B,1,1,S]
        s = jnp.where(valid, _scores(q_lat, q_rope, c, kr, scale), NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bths,bsr->bthr", p.astype(c.dtype), c,
            preferred_element_type=jnp.float32)
        return acc, m_new, alpha * l + jnp.sum(p, axis=-1)

    return lax.fori_loop(0, n_blocks, block, (
        jnp.zeros((B, T, H, r), jnp.float32),
        jnp.full((B, T, H), NEG_INF, jnp.float32),
        jnp.zeros((B, T, H), jnp.float32)))


def _attend_pool(q_lat, q_rope, c_pool, r_pool, l_idx, page_table, lengths,
                 scale, kernel: Optional[bool]):
    """Dispatch of the pool's part, by shape, where ``kernel`` is not
    None (True: interpret mode, the CPU's test hook; None: the XLA arm
    for everything).

    One token a row (a decode step): the Pallas decode kernel, whose
    time follows the pages each row owns (a padding row costs one empty
    grid step). A chunk of ONE row: the XLA arm, whose big
    batched matmuls run that shape faster than the kernel's page-sized
    ones. A chunk of several rows: the kernel over blocks of (token,
    head) rows, which skips the padding rows of the bucket and the
    blocks past each row's length, where the XLA arm computes every row
    to the longest. One layer, T 512 over 8k of cached prefix, device ms
    (tools/latent_attn_timing.py; my chip run, PR 31): PB 1 XLA 2.13 /
    kernel 3.70; PB 4 with one row live 23.1 / 5.1, all four live 23.1 /
    15.3."""
    B, T = q_lat.shape[:2]
    if kernel is None or (T > 1 and B == 1):
        return _attend_pool_xla(q_lat, q_rope, c_pool, r_pool, l_idx,
                                page_table, lengths, scale)
    if T == 1:
        acc, m, l = latent_attention_decode_layered(
            q_lat[:, 0], q_rope[:, 0], c_pool, r_pool, l_idx, page_table,
            lengths, scale=scale, interpret=kernel)
        return acc[:, None], m[:, None], l[:, None]
    return latent_attention_prefill_layered(
        q_lat, q_rope, c_pool, r_pool, l_idx, page_table, lengths,
        scale=scale, interpret=kernel)


def _merge(a, b):
    """Two parts over disjoint keys -> the attention output in latent
    space [B, T, H, r] float32 (normalised once, here)."""
    (acc_a, m_a, l_a), (acc_b, m_b, l_b) = a, b
    m = jnp.maximum(m_a, m_b)
    w_a, w_b = jnp.exp(m_a - m), jnp.exp(m_b - m)
    l = jnp.maximum(w_a * l_a + w_b * l_b, 1e-9)   # a padding row: zeros
    return (acc_a * w_a[..., None] + acc_b * w_b[..., None]) / l[..., None]


def _latent_qkv(cfg: ModelConfig, lp, x, safe_pos, inv_freq, dtype):
    """x [B, T, D] (normed) -> absorbed queries q_lat [B, T, H, r] =
    q_nope . W_UK and q_rope [B, T, H, dr], and what the cache keeps of
    the tokens: c_kv [B, T, r] (normed, times ``cfg.mla_kv_scale``; the
    query LoRA's output times ``cfg.mla_q_scale``: both 1.0 but in
    models/longcat_flash.py) and k_rope [B, T, dr], all in
    the pools' ``dtype``; both rope parts padded with zeros to the rope
    pool's width (rope_width; no padding off the TPU), which leaves every
    score what it was. With ``cfg.mla_nope`` neither side is rotated
    (``safe_pos`` and ``inv_freq`` are not read): the dr columns are a
    second, un-compressed key part shared by the heads."""
    B, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank > 0:
        q_all = rms_norm(x @ lp["w_dq"], lp["q_norm"],
                         cfg.rms_norm_eps) @ lp["w_uq"]
        if cfg.mla_q_scale != 1.0:      # longcat_flash: sqrt(D / q rank)
            q_all = q_all * jnp.asarray(cfg.mla_q_scale, q_all.dtype)
    else:
        q_all = x @ lp["w_q"]
    q_all = q_all.reshape(B, T, H, dn + dr)
    # with mla_nope the dr columns of both sides stay as they are made
    q_rope = q_all[..., dn:] if cfg.mla_nope else apply_rope(
        q_all[..., dn:], safe_pos, inv_freq)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_all[..., :dn],
                       lp["w_uk"].reshape(r, H, dn),
                       preferred_element_type=jnp.float32)
    ckr = x @ lp["w_dkv"]                                  # [B, T, r + dr]
    c_kv = rms_norm(ckr[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
    if cfg.mla_kv_scale != 1.0:         # longcat_flash: sqrt(D / kv rank)
        c_kv = c_kv * jnp.asarray(cfg.mla_kv_scale, c_kv.dtype)
    k_rope = ckr[..., r:] if cfg.mla_nope else apply_rope(
        ckr[..., None, r:], safe_pos, inv_freq)[..., 0, :]  # one shared head
    pad = [(0, rope_width(cfg) - dr)]
    return (q_lat.astype(dtype),
            jnp.pad(q_rope.astype(dtype), [(0, 0)] * 3 + pad),
            c_kv.astype(dtype),
            jnp.pad(k_rope.astype(dtype), [(0, 0)] * 2 + pad))


def _latent_out(cfg: ModelConfig, lp, out_lat, dtype):
    """The latent context [B, T, H, r] up-projected a head (out_lat .
    W_UV) and through W_O: what an attending layer adds, [B, T, D]."""
    B, T = out_lat.shape[:2]
    H, r, dv = cfg.num_heads, cfg.kv_lora_rank, cfg.v_head_dim
    out = jnp.einsum("bthr,rhd->bthd", out_lat.astype(dtype),
                     lp["w_uv"].reshape(r, H, dv),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * dv).astype(dtype) @ lp["w_o"]


def _layers(params: Params, cfg: ModelConfig, h, attend, cache, mesh=None,
            live=None):
    """All layers on h [B, T, D]. ``attend(l, lp, x, cache_l) ->
    (out_lat [B, T, H, r] float32, cache_l)`` is the latent attention of
    layer ``l`` on the normed input: the caller owns where the tokens'
    latents go (``cache``: a pytree with a leading layer axis, scanned
    in and out: the window buffers in the decode window; None in forward,
    which gets the chunk's own latents back stacked by layer). The leading ``first_k_dense_replace`` layers carry a dense
    MLP, the rest routed experts; both index the attention stacks by
    the absolute layer, so no stack and no pool is sliced in two."""
    B, T, _ = h.shape
    L = cfg.num_layers
    attn_keys = _mla_attn_keys(cfg)

    def block(h, l, cache_l, mlp):
        lp = _at(params, attn_keys, l)
        with jax.named_scope("attn"):
            x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
            out_lat, cache_l = attend(l, lp, x, cache_l)
            h = h + _latent_out(cfg, lp, out_lat, h.dtype)
        x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
        return h + mlp(x, l), cache_l

    def run(h, mlp, l0, l1):
        def layer(h, xs):
            l, cache_l = xs
            return block(h, l, cache_l, mlp)

        return lax.scan(layer, h, (
            jnp.arange(l0, l1, dtype=jnp.int32),
            jax.tree.map(lambda a: a[l0:l1], cache)))

    def dense_mlp(suffix):
        keys = tuple(k + suffix for k in ("w_gate", "w_up", "w_down"))

        def mlp(x, l):
            lp = _at(params, keys, l)
            return _mlp(x, *(lp[k] for k in keys))

        return mlp

    if cfg.num_experts == 0:
        return run(h, dense_mlp(""), 0, L)
    kd = cfg.first_k_dense_replace
    moe_keys = list(_moe_layer_params(cfg, params))
    in_place = _moe_use_blocked(mesh, B * T, cfg.num_experts,
                                cfg.num_experts_per_tok)
    experts = ("w_gate_e", "w_up_e", "w_down_e")

    def moe_mlp(x, l):
        li = l - kd
        with jax.named_scope("moe"):
            if not in_place:
                return _deepseek_moe_mlp(x, _at(params, moe_keys, li), cfg,
                                         mesh=mesh)
            # the sorted dispatch reads w[layer, expert] from the whole
            # stacks: sliced out first, each layer's 128 experts would
            # be copied before the block loop may index them
            lp = _at(params, [k for k in moe_keys if k not in experts], li)
            lp.update({k: params[k] for k in experts})
            return _deepseek_moe_mlp(x, lp, cfg, mesh=mesh, live=live,
                                     layer=li)

    parts = []
    if kd > 0:
        h, part = run(h, dense_mlp("_d"), 0, kd)
        parts.append(part)
    h, part = run(h, moe_mlp, kd, L)
    parts.append(part)
    return h, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)


def _commit_chunk(pool: jax.Array, new: jax.Array, flat_slots: jax.Array,
                  page_slots: Optional[jax.Array]) -> jax.Array:
    """Write a chunk's latents (or rope keys) of ALL layers into the pool,
    once, along its major axis, in place.

    pool: [L, pages, 1, ps, d] (donated by the caller); new: [L, B, T, d].
    With ``page_slots`` [B, T // ps] (the engine passes them when every
    chunk starts on a page and T is whole pages) whole pages are written
    to the pool seen as [L * pages, 1, ps, d]; a tail page carries junk
    past the chunk's end, which no query reads before the token that
    belongs there is written (llama._scatter_pages_paged has the
    argument). Otherwise ``flat_slots`` [B, T] (page * ps + offset) write
    token rows into [L * pages * ps, d]: with one latent head a token's
    row lies directly under (page, offset), so that too is a scatter on
    the major axis. Ids outside the pool (DROP_SLOT, padding) are
    dropped."""
    L, NP, _, ps, d = pool.shape
    B, T = new.shape[1:3]
    layers = jnp.arange(L, dtype=jnp.int32)[:, None]
    new = new.astype(pool.dtype)
    if page_slots is not None:
        idx = page_slots.reshape(-1)                       # [B * T/ps]
        dst = jnp.where((idx >= 0) & (idx < NP), layers * NP + idx, L * NP)
        flat = pool.reshape(L * NP, 1, ps, d).at[dst.reshape(-1)].set(
            new.reshape(-1, 1, ps, d), mode="drop")
    else:
        idx = flat_slots.reshape(-1)                       # [B * T]
        dst = jnp.where((idx >= 0) & (idx < NP * ps),
                        layers * (NP * ps) + idx, L * NP * ps)
        flat = pool.reshape(L * NP * ps, d).at[dst.reshape(-1)].set(
            new.reshape(-1, d), mode="drop")
    return flat.reshape(pool.shape)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv_lat: jax.Array, kv_rope: jax.Array,
            page_table: jax.Array, flat_slots: jax.Array,
            allow_pallas: bool = True, mesh=None,
            page_slots: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Same signature/contract as llama.forward; (kv_k, kv_v) ≡
    (latent pool, rope pool). A row's positions are consecutive from
    positions[b, 0] (-1: padding, at the row's end), as the engine
    builds every chunk: the pool holds what lies before positions[b, 0],
    the chunk attends to that and, causally, to itself."""
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    B, T = tokens.shape
    # no shard_map wrapper for the latent kernels yet: the XLA arm
    # under a mesh (llama.kernel_mode)
    kernel = llama.kernel_mode(allow_pallas, mesh=mesh)
    h = params["embed"][tokens]
    safe_pos = jnp.maximum(positions, 0)
    live = positions >= 0
    before = jnp.maximum(positions[:, 0], 0)           # [B] pool extent
    own = (live[:, None, :]
           & (positions[:, None, :] <= positions[:, :, None]))  # [B, T, T]

    def attend(l, lp, x, _):
        q_lat, q_rope, c_kv, k_rope = _latent_qkv(cfg, lp, x, safe_pos,
                                                  inv_freq, kv_lat.dtype)
        with jax.named_scope("attn.latent"):
            out = _merge(
                _attend_pool(q_lat, q_rope, kv_lat, kv_rope, l, page_table,
                             before, scale, kernel),
                _attend_local(q_lat, q_rope, c_kv, k_rope, own, scale))
        return out, (c_kv, k_rope)

    h, (c_new, r_new) = _layers(params, cfg, h, attend, None, mesh=mesh,
                                live=live)
    with jax.named_scope("kv_carry"):
        kv_lat = _commit_chunk(kv_lat, c_new, flat_slots, page_slots)
        kv_rope = _commit_chunk(kv_rope, r_new, flat_slots, page_slots)
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
    """The fused K-step window of llama.make_decode_window_fn, same
    signature and contract (models/window.py's program), over the latent
    cache: the latent and rope pools are read-only inside the window (the
    Pallas latent decode kernel on the chip, the XLA arm elsewhere), the
    window's own (c_kv, k_rope) live in buffers [L, B, K, 1, *] merged in
    by online-softmax statistics, and one llama.commit_window per pool
    writes them in by whole pages, in place."""
    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    kernel = llama.kernel_mode(allow_pallas, pallas_interpret, mesh)
    L = cfg.num_layers

    def begin(w):
        B, K = w.start.shape[0], w.k_steps
        before = jnp.maximum(w.start, 0)
        wc = jnp.zeros((L, B, K, 1, w.kv_k.shape[-1]), w.kv_k.dtype)
        wr = jnp.zeros((L, B, K, 1, w.kv_v.shape[-1]), w.kv_v.dtype)
        return wc, wr, before, jnp.arange(K, dtype=jnp.int32)

    def step(w, bufs, tok, pos, active, i):
        # frozen (done / padding) rows flow through the matmuls; their
        # outputs are discarded and their latents never commit
        wc, wr, before, slot = bufs
        safe_pos = jnp.maximum(pos, 0)[:, None]
        seen = ((slot[None, :] <= i)
                & (w.start[:, None] >= 0))[:, None, :]      # [B, 1, K]

        def attend(l, lp, x, bufs):
            wc_l, wr_l = bufs
            q_lat, q_rope, c_kv, k_rope = _latent_qkv(
                cfg, lp, x, safe_pos, inv_freq, wc.dtype)
            wc_l = wc_l.at[:, i, 0].set(c_kv[:, 0])
            wr_l = wr_l.at[:, i, 0].set(k_rope[:, 0])
            with jax.named_scope("attn.latent"):
                out = _merge(
                    _attend_pool(q_lat, q_rope, w.kv_k, w.kv_v, l,
                                 w.page_table, before, scale, kernel),
                    _attend_local(q_lat, q_rope, wc_l[:, :, 0],
                                  wr_l[:, :, 0], seen, scale))
            return out, (wc_l, wr_l)

        h = w.params["embed"][tok][:, None]                 # [B, 1, D]
        h, (wc, wr) = _layers(w.params, cfg, h, attend, (wc, wr), mesh=mesh)
        h = rms_norm(h, w.params["ln_final"], cfg.rms_norm_eps)
        return (logits_at(w.params, cfg, h,
                          jnp.zeros(tok.shape[0], jnp.int32)),
                (wc, wr, before, slot), None)

    def commit(w, bufs, pos):
        wc, wr = bufs[:2]
        return (commit_window(w.kv_k, wc, w.page_table, w.start, pos),
                commit_window(w.kv_v, wr, w.page_table, w.start, pos), None)

    return make_window(Family(begin, step, commit), max_top_k)


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
