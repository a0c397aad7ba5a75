"""Ring attention: sequence/context-parallel attention over an ICI ring.

The reference has NO long-context story beyond KV reuse and disaggregating
long prefills (SURVEY §5: "long-context / sequence parallelism: absent in
the reference"); this module adds it as a first-class sharding strategy of
the JAX prefill program, per the SURVEY's TPU plan.

Design (blockwise/ring attention, Liu et al. style, TPU-idiomatic):

- the sequence axis of Q/K/V activations is sharded over the mesh axis
  ``seq``; each device holds a contiguous chunk;
- K/V chunks rotate around the ring with ``lax.ppermute`` while each device
  accumulates its queries' attention over every chunk using an online
  (streaming) softmax — numerically identical to full softmax attention;
- causality is enforced with absolute positions, so the same kernel serves
  packed/padded and chunk-offset layouts (padding rows carry position -1);
- the loop is a ``lax.scan`` of ``seq`` steps: one K/V block dot per step
  on the MXU while the next block is in flight on ICI (XLA overlaps the
  ppermute with compute since the carry has no data dependence on it until
  the next step).

``make_long_prefill_fn`` builds the full sequence-parallel prefill program:
the Llama/Mixtral stack with activations sharded over ("data", "seq") and
self-attention replaced by the ring kernel — producing per-layer K/V for
the whole prompt (to be scattered into the paged pool / shipped to decode)
plus last-position logits.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..models.config import ModelConfig

NEG_INF = -1e30


# ------------------------------------------------------------- ring kernel


def _ring_attention_inner(q, k, v, q_pos, kv_pos, is_sliding, *,
                          axis_name: str, scale: float,
                          softcap=None, window=None):
    """Per-device body (runs under shard_map over ``axis_name``).

    q: [B, Tq, KV, G, hd] local query chunk (grouped GQA heads);
    k: [B, Tk, KV, hd]; v: [B, Tk, KV, dv] local key/value chunks —
    dv may differ from hd (MLA rides this kernel with keys
    [c_kv | k_rope] of width r+dr and values c_kv of width r);
    q_pos/kv_pos: [B, T] absolute positions (-1 = padding);
    is_sliding: traced scalar bool (Gemma-2 layer parity under scan).
    ``softcap``/``window`` are the static Gemma-2 knobs: tanh softcap
    applied BEFORE masking (models/llama._softcap_mask), and the
    sliding window as a pure POSITION predicate (j > t - window) — it
    needs no block locality, so any window size composes with any ring
    chunking; blocks wholly outside a query's window just contribute
    zero mass to its online softmax.
    Returns [B, Tq, KV, G, dv].
    """
    from ..models.llama import _softcap_mask, _visible

    n = lax.psum(1, axis_name)
    B, Tq, KV, G, hd = q.shape
    dv = v.shape[-1]
    qf = q.astype(jnp.float32)

    m0 = jnp.full((B, KV, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Tq), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Tq, dv), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        k_blk, v_blk, pos_blk, m, l, acc = carry
        scores = jnp.einsum("btkgh,bskh->bkgts", qf,
                            k_blk.astype(jnp.float32)) * scale
        kvp = pos_blk[:, None, None, None, :]
        qp = q_pos[:, None, None, :, None]
        # same helpers as the paged path — ONE copy of the Gemma-2
        # softcap-before-mask ordering and window-visibility invariants
        valid = (kvp >= 0) & _visible(kvp, qp, window, is_sliding)
        scores = _softcap_mask(scores, valid, softcap)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # exp only where valid: when a row has no valid keys yet, m_new is
        # still NEG_INF and exp(scores - m_new) would be exp(0)=1 — mask it
        p = jnp.where(valid, jnp.exp(scores - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p, v_blk.astype(jnp.float32))
        k_blk, v_blk, pos_blk = (
            lax.ppermute(k_blk, axis_name, perm),
            lax.ppermute(v_blk, axis_name, perm),
            lax.ppermute(pos_blk, axis_name, perm))
        return (k_blk, v_blk, pos_blk, m_new, l, acc), None

    (_, _, _, _, l, acc), _ = lax.scan(
        step, (k, v, kv_pos, m0, l0, acc0), None, length=n)
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, KV, G, Tq, hd]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   positions: jax.Array, mesh: Mesh, *,
                   scale: float, seq_axis: str = "seq",
                   softcap=None, window=None,
                   is_sliding=False) -> jax.Array:
    """Causal GQA attention with the sequence sharded over ``seq_axis``.

    q: [B, T, H, hd]; k/v: [B, T, KV, hd]; positions: [B, T] absolute
    (-1 for padding). All sequence-sharded over ``seq_axis``; heads may be
    additionally sharded over "model" (the kernel is per-head, so TP
    composes freely). ``softcap``/``window``/``is_sliding`` are the
    Gemma-2 semantics (see _ring_attention_inner). Returns [B, T, H, hd]
    with q's sharding.
    """
    B, T, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, T, KV, H // KV, hd)

    # TP shards KV heads over "model" (consistent with mesh.kv_cache_pspec);
    # each TP rank runs the ring over its own head slice
    qspec = P("data", seq_axis, "model", None, None)
    kvspec = P("data", seq_axis, "model", None)
    pspec = P("data", seq_axis)

    inner = partial(_ring_attention_inner, axis_name=seq_axis, scale=scale,
                    softcap=softcap, window=window)
    out = shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, pspec, pspec, P()),
        out_specs=qspec, check_vma=False,
    )(qg, k, v, positions, positions, jnp.asarray(is_sliding))
    return out.reshape(B, T, H, hd)


def ring_attention_mqa(q: jax.Array, k: jax.Array, v: jax.Array,
                       positions: jax.Array, mesh: Mesh, *,
                       scale: float, seq_axis: str = "seq") -> jax.Array:
    """Ring attention with ONE shared key/value stream (MQA form) — the
    MLA latent exchange: every query head attends to the same compressed
    stream, so only [B, T, dk] keys + [B, T, dv] values rotate on ICI
    (~an order of magnitude less ring traffic than per-head GQA K/V).

    q: [B, T, H, dk]; k: [B, T, dk]; v: [B, T, dv]; positions [B, T]
    absolute (-1 padding). Query heads shard over "model" (scores are
    per-head); the shared stream replicates across TP shards — it has no
    head axis to split. Returns [B, T, H, dv].
    """
    B, T, H, dk = q.shape
    qg = q.reshape(B, T, 1, H, dk)  # KV=1, G=H

    qspec = P("data", seq_axis, None, "model", None)
    kvspec = P("data", seq_axis, None, None)
    pspec = P("data", seq_axis)

    inner = partial(_ring_attention_inner, axis_name=seq_axis, scale=scale)
    out = shard_map(
        inner, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, pspec, pspec, P()),
        out_specs=qspec, check_vma=False,
    )(qg, k[:, :, None], v[:, :, None], positions, positions,
      jnp.asarray(False))
    return out.reshape(B, T, H, -1)


# -------------------------------------------- sequence-parallel prefill fn


def make_long_prefill_fn(cfg: ModelConfig, mesh: Mesh, *,
                         seq_axis: str = "seq"):
    """Jitted long-context prefill: the model stack with activations
    sharded over ("data", seq) and ring attention.

    Returns ``fn(params, tokens, positions) -> (logits [B, V], k_all, v_all)``
    where k_all/v_all are [L, B, T, KV, hd] (per-layer KV for the whole
    prompt — scatter into the paged pool with
    :func:`scatter_prefill_kv`, or ship to the decode mesh via the disagg
    transfer plane). ``positions`` are absolute; -1 marks padding.
    """
    from ..models.llama import (_act, _layer_keys, _mlp, _moe_mlp,
                                _qk_headnorm, _residual_add, _window_flag,
                                apply_rope, embed_tokens, project_logits,
                                rms_norm, rope_freqs)

    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_

    act_spec = NamedSharding(mesh, P("data", seq_axis, None))

    @jax.jit
    def long_prefill(params, tokens, positions):
        B, T = tokens.shape
        h = embed_tokens(params, cfg, tokens)
        h = lax.with_sharding_constraint(h, act_spec)
        safe_pos = jnp.maximum(positions, 0)

        layer_params = {kk: params[kk] for kk in _layer_keys(cfg)}

        def layer(h, xs):
            lp, l_idx = xs
            x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps, cfg.norm_unit_offset)
            xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
            if cfg.attn_bias:  # Qwen2-style qkv bias (matches llama.forward)
                xq, xk, xv = xq + lp["bq"], xk + lp["bk"], xv + lp["bv"]
            q, k = _qk_headnorm(xq.reshape(B, T, H, hd),
                                xk.reshape(B, T, KV, hd), lp, cfg)
            q = apply_rope(q, safe_pos, inv_freq)
            k = apply_rope(k, safe_pos, inv_freq)
            v = xv.reshape(B, T, KV, hd)
            attn = ring_attention(q, k, v, positions, mesh, scale=scale,
                                  seq_axis=seq_axis,
                                  softcap=cfg.attn_logit_softcap,
                                  window=cfg.sliding_window,
                                  is_sliding=_window_flag(cfg, l_idx))
            h = _residual_add(h, attn.reshape(B, T, H * hd) @ lp["wo"],
                              lp, "ln_attn_post", cfg)
            x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps, cfg.norm_unit_offset)
            if cfg.num_experts > 0:
                mlp_out = _moe_mlp(x, lp["w_router"], lp["w_gate"],
                                   lp["w_up"], lp["w_down"],
                                   cfg.num_experts_per_tok, mesh=mesh)
            else:
                mlp_out = _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                               _act(cfg))
            h = _residual_add(h, mlp_out, lp, "ln_mlp_post", cfg)
            h = lax.with_sharding_constraint(h, act_spec)
            return h, (k, v)

        h, (k_all, v_all) = lax.scan(
            layer, h, (layer_params, jnp.arange(cfg.num_layers)))
        h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps, cfg.norm_unit_offset)
        # logits at the true last token of each row (max position)
        last_idx = jnp.argmax(positions, axis=1)
        h_last = h[jnp.arange(B), last_idx]
        return project_logits(params, cfg, h_last), k_all, v_all

    return long_prefill


def make_mla_long_prefill_fn(cfg: ModelConfig, mesh: Mesh, *,
                             seq_axis: str = "seq"):
    """Sequence-parallel long prefill for the MLA family
    (models/mla.py): the latent-only ring exchange. Only the shared
    compressed stream (c_kv [B, T, r] + k_rope [B, T, dr]) rotates on
    the ring — per-head K/V are never materialized, matching the
    absorbed decode form.

    Same contract as :func:`make_long_prefill_fn`: ``fn(params, tokens,
    positions) -> (logits [B, V], c_all, r_all)`` with c_all/r_all
    [L, B, T, 1, r|dr] — KV-head axis fixed at 1 exactly like the MLA
    paged pools (mla.cache_shapes), so the engine's generic
    :func:`scatter_prefill_kv` commits them unchanged.
    """
    import math

    from ..models.llama import apply_rope, rms_norm, rope_freqs
    from ..models.mla import _mla_layer_keys, rope_width

    if cfg.num_experts > 0:
        raise ValueError(
            "MLA ring long-prefill covers dense MLA only; the DeepSeek-"
            "MoE segmented stack is not wired through the ring — unset "
            "long_prefill_threshold")
    from ..models.llama import _mlp, _moe_mlp, project_logits

    inv_freq = rope_freqs(cfg, dim=cfg.qk_rope_head_dim)
    H = cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    scale = 1.0 / math.sqrt(dn + dr)

    act_spec = NamedSharding(mesh, P("data", seq_axis, None))

    @jax.jit
    def long_prefill(params, tokens, positions):
        B, T = tokens.shape
        h = params["embed"][tokens]
        h = lax.with_sharding_constraint(h, act_spec)
        safe_pos = jnp.maximum(positions, 0)
        layer_params = {k: params[k] for k in _mla_layer_keys(cfg)}

        def layer(h, lp):
            x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
            if cfg.q_lora_rank > 0:
                q_all = rms_norm(x @ lp["w_dq"], lp["q_norm"],
                                 cfg.rms_norm_eps) @ lp["w_uq"]
            else:
                q_all = x @ lp["w_q"]
            q_all = q_all.reshape(B, T, H, dn + dr)
            q_nope, q_rope = q_all[..., :dn], q_all[..., dn:]
            q_rope = apply_rope(q_rope, safe_pos, inv_freq)
            ckr = x @ lp["w_dkv"]
            c_kv = rms_norm(ckr[..., :r], lp["kv_norm"], cfg.rms_norm_eps)
            k_rope = apply_rope(ckr[..., None, r:], safe_pos,
                                inv_freq)[..., 0, :]
            # absorbed queries + concatenated shared stream: scores =
            # q_lat·c + q_rope·k_rope in ONE MQA ring pass
            w_uk = lp["w_uk"].reshape(r, H, dn)
            q_lat = jnp.einsum("bthd,rhd->bthr",
                               q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            q_cat = jnp.concatenate(
                [q_lat, q_rope.astype(jnp.float32)], axis=-1)
            k_cat = jnp.concatenate(
                [c_kv.astype(jnp.float32),
                 k_rope.astype(jnp.float32)], axis=-1)
            out_lat = ring_attention_mqa(
                q_cat, k_cat, c_kv.astype(jnp.float32), positions, mesh,
                scale=scale, seq_axis=seq_axis)  # [B, T, H, r]
            w_uv = lp["w_uv"].reshape(r, H, dv)
            out = jnp.einsum("bthr,rhd->bthd", out_lat,
                             w_uv.astype(jnp.float32))
            h = h + out.reshape(B, T, H * dv).astype(h.dtype) @ lp["w_o"]
            x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
            if cfg.num_experts > 0:
                h = h + _moe_mlp(x, lp["w_router"], lp["w_gate"],
                                 lp["w_up"], lp["w_down"],
                                 cfg.num_experts_per_tok, mesh=mesh)
            else:
                h = h + _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            h = lax.with_sharding_constraint(h, act_spec)
            return h, (c_kv.astype(h.dtype), k_rope.astype(h.dtype))

        h, (c_all, r_all) = lax.scan(layer, h, layer_params)
        h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps)
        last_idx = jnp.argmax(positions, axis=1)
        h_last = h[jnp.arange(B), last_idx]
        # KV-head axis = 1 and the rope key padded to whole lanes,
        # matching the MLA paged pools (mla.cache_shapes)
        r_all = jnp.pad(r_all, [(0, 0)] * 3 + [(0, rope_width(cfg) - dr)])
        return (project_logits(params, cfg, h_last),
                c_all[:, :, :, None], r_all[:, :, :, None])

    return long_prefill


@partial(jax.jit, donate_argnums=(0, 1))
def scatter_prefill_kv(kv_k: jax.Array, kv_v: jax.Array, k_all: jax.Array,
                       v_all: jax.Array, flat_slots: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Write long-prefill K/V ([L, B, T, KV, hd]) into the paged pools
    ([L, pages, KV, ps, hd]) at ``flat_slots`` [B, T] (page*ps + offset;
    out-of-range = drop). The pools are DONATED — like every other pool
    update in the engine, XLA scatters in place instead of materializing
    a second full-pool copy (which would double peak KV memory on pools
    sized to fill HBM)."""
    from ..models.llama import _scatter_pages

    def per_layer(cache_layer, new):
        return _scatter_pages(cache_layer, new, flat_slots)

    return (jax.vmap(per_layer)(kv_k, k_all),
            jax.vmap(per_layer)(kv_v, v_all))
