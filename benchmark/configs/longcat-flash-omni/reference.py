"""The plain reference for LongCat-Flash's language model (``longcat_flash``:
the shortcut-connected MoE with zero-computation experts of the
LongCat-Flash technical report, Meituan 2025, over DeepSeek-style latent
attention), written from the published description in straightforward
``jax.numpy`` and float32. No cache, no kernel, no batching, none of the
program's model code (``dynamo_tpu/models/`` is not imported).

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``; ``F(x; g, u, d) =
d(silu(g x) * u x)``. ``h = embed[token]``; layer l, sub-blocks i in
(0, 1), j = 2l + i:

    a = rms(h; ln_attn[j]);   h = h + MLA_j(a)
    x = rms(h; ln_mlp[j])
    if i == 0:  s = MoE_l(x)                    the shortcut: read here ...
    h = h + F(x; w_gate_d[j], w_up_d[j], w_down_d[j])
    after i == 1:  h = h + s                    ... added here

exit ``logits = rms(h; ln_final) @ lm_head``.

MLA_j on a [T, D] (H heads, nope dn, rope dr, v dv, latent rank r, query
rank rq), the NON-absorbed form: every head's K and V exist.

    q        = W_uq rms(W_dq a; q_norm) * sqrt(D / rq)     [T, H, dn + dr]
    [c', kr] = split(W_dkv a)                              (r, dr)
    c        = rms(c'; kv_norm) * sqrt(D / r)   what a cache would keep
    k_h      = [W_uk_h c, rope(kr)]    ONE rope key a token, all heads
    v_h      = W_uv_h c
    o_h      = softmax_causal(rope'(q_h) . k_h / sqrt(dn + dr)) v_h
    out      = W_o [o_1 .. o_H]

MoE_l on x [T, D], the router over W = real + zero outputs:

    sc     = softmax(x W_r)                 over all W outputs, float32
    chosen = top-k of (sc + b_sel)          the bias selects, never weighs
    w_e    = routed_scaling_factor * sc_e   (chosen; else 0), NOT renormalised
    s      = sum_{e held} w_e F_e(x)  +  x * sum_{e >= real} w_e

``F_e`` for the real experts HELD here, ``[cfg.first_expert,
cfg.first_expert + cfg.num_experts)``, each evaluated for every token
and weighted by w_e (zero when not chosen: exact, and no dispatch to get
wrong); a real expert that is not held adds nothing (the configuration
is one chip's share of a layer's experts: the program leaves the same
pairs out, and nothing stands in for them); an identity expert's output
is the token itself.

Departures from the published description, each of naming, storage or
size, none of arithmetic:
- rope layout: the published checkpoints rotate interleaved pairs; a
  loader would de-interleave the rope columns once, as this repo's does
  for every latent model (dynamo_tpu/models/loader.py), so the program
  rotates in the half-split (``rotate_half``) form and so does this
  file. With weights drawn at random on the leaves as they are served
  the two forms are the same function up to that fixed permutation of
  the columns of W_uq and W_dkv;
- the leaves carry this repo's names (``w_dq`` / ``q_norm`` / ``w_uq`` =
  q_a_proj / q_a_layernorm / q_b_proj, ``w_dkv`` = kv_a_proj_with_mqa,
  ``kv_norm`` = kv_a_layernorm, ``w_uk`` / ``w_uv`` = the halves of
  kv_b_proj, ``ln_attn`` / ``ln_mlp`` = input_layernorm /
  post_attention_layernorm, ``w_*_d`` = mlps, ``w_router`` /
  ``router_bias`` = the router's classifier and
  e_score_correction_bias, ``w_*_e`` the routed experts) and matrices
  are stored input-major (``x @ W``);
- attention-side and dense-MLP leaves are stacked over the 2L
  sub-blocks, router and expert leaves over the L layers;
- memory: parameters are upcast from the type they are served in to
  float32 one sub-block, and one expert, at a time; queries attend in
  blocks of ``Q_BLOCK`` rows and the dense MLP runs ``ROW_BLOCK`` rows at
  a time; ``last=n`` projects only the last n positions.

``fault`` (tests and tools only) computes ONE thing wrong, to show that
the comparison sees it: ``FAULTS`` names them.

Callers wrap the call in ``jax.default_matmul_precision("highest")``; it
is set here as well, for a caller that does not.
"""

from __future__ import annotations

import math
from functools import partial

FAULTS = (
    "shortcut_early",    # s added after sub-block 0's MLP, not after 1's
    "lora_scales_off",   # both LoRA scales left at 1
    "bias_unselected",   # top-k of the scores alone, the bias left out
    "renormalised",      # the chosen scores divided by their sum
    "identity_dropped",  # an identity pair adds nothing
)
Q_BLOCK = 256
ROW_BLOCK = 2048


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, inv_freq):
    """x: [T, heads, dr]; half-split rotation by position 0..T-1."""
    import jax.numpy as jnp

    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(params, name, i):
    import jax.numpy as jnp
    from jax import lax

    return lax.dynamic_index_in_dim(params[name], i, 0,
                                    False).astype(jnp.float32)


def _mlp(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _attention(cfg, fault, params, a, j):
    """Causal attention of sub-block j on the normed a [T, D], per-head K
    and V materialised from the latent, queries in blocks."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T, D = a.shape
    H, r, rq = cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    at = partial(_at, params, i=j)
    off = fault == "lora_scales_off"
    q_scale = 1.0 if off else math.sqrt(D / rq)
    kv_scale = 1.0 if off else math.sqrt(D / r)

    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=f32) / dr))
    q = _rms(a @ at("w_dq"), at("q_norm"), cfg.rms_norm_eps) @ at("w_uq")
    q = (q * q_scale).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], axis=-1)
    ckr = a @ at("w_dkv")                                   # [T, r + dr]
    c = _rms(ckr[:, :r], at("kv_norm"), cfg.rms_norm_eps) * kv_scale
    k_r = _rope(ckr[:, None, r:], inv)                      # [T, 1, dr]
    k = jnp.concatenate([(c @ at("w_uk")).reshape(T, H, dn),
                         jnp.broadcast_to(k_r, (T, H, dr))], axis=-1)
    v = (c @ at("w_uv")).reshape(T, H, dv)

    qb = min(Q_BLOCK, T)
    nb = -(-T // qb)
    q = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))

    def block(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        s = jnp.einsum("thd,shd->hts", qi, k) * ((dn + dr) ** -0.5)
        seen = (jnp.arange(T)[None, :]
                <= (i * qb + jnp.arange(qb))[:, None])
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    o = lax.map(block, jnp.arange(nb)).reshape(nb * qb, H * dv)[:T]
    return o @ at("w_o")


def route(cfg, scores, bias, fault=None):
    """scores [T, W] (softmax over all W outputs), bias [W] -> routing
    weights [T, W]: routed_scaling_factor * score where the output is
    among the top-k of scores + bias, 0 elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    T, W = scores.shape
    choice = scores if fault == "bias_unselected" else scores + bias
    _, idx = lax.top_k(choice, cfg.num_experts_per_tok)
    chosen = jnp.zeros((T, W), bool).at[jnp.arange(T)[:, None], idx].set(
        True)
    w = jnp.where(chosen, scores, 0.0)
    if fault == "renormalised":
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.routed_scaling_factor


def _moe(cfg, fault, params, x, l):
    """The shortcut MoE of layer l on the normed x [T, D]: the held real
    experts (each computed for every token) + the identity experts."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    real = cfg.router_width - cfg.zero_experts
    scores = jax.nn.softmax(x @ _at(params, "w_router", l), axis=-1)
    w = route(cfg, scores, _at(params, "router_bias", l), fault)

    def expert(acc, e):
        def w_(name):
            return lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(params[name], l, 0, False),
                e, 0, False).astype(f32)

        y = _mlp(x, w_("w_gate_e"), w_("w_up_e"), w_("w_down_e"))
        gate = lax.dynamic_index_in_dim(w, cfg.first_expert + e, 1, True)
        return acc + gate * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      jnp.arange(cfg.num_experts))
    if fault != "identity_dropped":
        # E_j(x) = x for the zero-computation experts
        out = out + x * jnp.sum(w[:, real:], axis=-1, keepdims=True)
    return out


def _dense(cfg, params, x, j):
    """The dense MLP of sub-block j, ROW_BLOCK rows at a time."""
    import jax.numpy as jnp
    from jax import lax

    T, D = x.shape
    ws = [_at(params, n, j) for n in ("w_gate_d", "w_up_d", "w_down_d")]
    rb = min(ROW_BLOCK, T)
    nb = -(-T // rb)
    xs = jnp.pad(x, ((0, nb * rb - T), (0, 0))).reshape(nb, rb, D)
    return lax.map(lambda xb: _mlp(xb, *ws), xs).reshape(nb * rb, D)[:T]


def layer(cfg, params, h, l, fault=None):
    """One layer (both sub-blocks and the shortcut MoE) on h [T, D]
    float32; ``l`` may be traced (one compiled program serves every
    layer; rehearse.py compiles it)."""
    eps = cfg.rms_norm_eps
    for i in (0, 1):
        j = 2 * l + i
        h = h + _attention(cfg, fault, params,
                           _rms(h, _at(params, "ln_attn", j), eps), j)
        x = _rms(h, _at(params, "ln_mlp", j), eps)
        if i == 0:
            s = _moe(cfg, fault, params, x, l)
        h = h + _dense(cfg, params, x, j)
        if i == 0 and fault == "shortcut_early":
            h = h + s
    return h if fault == "shortcut_early" else h + s


def reference_logits(params, cfg, tokens, last=None, fault=None):
    """Logits float32 for one sequence of token ids: [T, V], or with
    ``last`` = n the last n positions only, [n, V]."""
    import jax
    import jax.numpy as jnp

    if not (cfg.moe_router == "longcat_flash" and cfg.q_lora_rank > 0
            and cfg.kv_lora_rank > 0):
        raise NotImplementedError(
            "this reference covers the longcat_flash family: latent "
            "attention behind a query LoRA and the shortcut MoE")
    if cfg.rope_scaling or cfg.tie_word_embeddings:
        raise NotImplementedError(
            "this reference does not cover rope scaling or tied embeddings")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    one_layer = jax.jit(partial(layer, cfg, fault=fault))

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = embed(params, jnp.asarray(tokens, jnp.int32))
        for l in range(cfg.num_layers):
            h = one_layer(params, h, jnp.int32(l))
        return head(params, h if last is None else h[-last:])
