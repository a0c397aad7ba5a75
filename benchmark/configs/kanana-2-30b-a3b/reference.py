"""The plain reference for the DeepSeek-V3 family as Kanana-2-30B-A3B
configures it (latent attention without a query LoRA, sigmoid-scored
routed experts with a selection bias, shared experts, leading dense
layers): written from the published description in straightforward
``jax.numpy`` and float32. No cache, no kernel, no batching, none of
the program's model code (``dynamo_tpu/models/mla.py`` is not
imported).

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on h [T, D], x = rms(h):

    q          = W_q x                     [T, H, dn + dr], no query LoRA
    [c, k_r]   = split(W_dkv x)            sizes (r, dr)
    c          = rms(c) * kv_norm          the latent a cache would keep
    k_r        = rope(k_r)                 ONE rope key a token, all heads
    k_h        = [W_uk_h c, k_r]           per-head key, MATERIALISED
    v_h        = W_uv_h c                  per-head value, MATERIALISED
    a_h        = softmax_causal(rope'(q_h) . k_h / sqrt(dn + dr)) v_h
    h         += W_o [a_1 .. a_H]

(rope' rotates the last dr columns of q_h). This is the NON-absorbed
form: the program folds W_uk into the query and W_uv into the output and
attends in latent space; here every head's K and V exist. Then, with
x = rms(h): layers l < first_k_dense_replace add the dense MLP
W_down(silu(W_gate x) * W_up x); the others add

    s      = sigmoid(W_router x)                    [T, E]
    chosen = top-k of (s + router_bias)             noaux_tc: the bias
                                                    selects, never weighs
    w_e    = s_e / sum_chosen(s) * routed_scaling_factor   (chosen; else 0)
    out    = sum_e w_e * MLP_e(x)  +  MLP_shared(x)

with EVERY expert evaluated for every token and weighted by w_e (zero
when not chosen): exact, and no dispatch to get wrong. ``n_group`` 1 /
``topk_group`` 1 (one group, always kept) limits nothing, so no group
step is written; a configuration with more groups is refused. Final
RMSNorm; logits through ``lm_head``.

Departures from the published description, each of naming, storage or
size, none of arithmetic:
- rope layout: the published checkpoints store the rope columns
  interleaved (``rope_interleave``); this repo's loader de-interleaves
  them once at load (dynamo_tpu/models/loader.py, ``_rope_perm``), so
  the program rotates in the half-split (``rotate_half``) form, and so
  does this file. With weights drawn at random on the leaves as they are
  served, the two forms are the same function of the same leaves up to
  that fixed column permutation of W_q and W_dkv;
- the leaves carry this repo's names (``w_dkv`` = kv_a_proj_with_mqa,
  ``kv_norm`` = kv_a_layernorm, ``w_uk`` / ``w_uv`` = the two halves of
  kv_b_proj, ``router_bias`` = e_score_correction_bias, ``*_d`` the
  dense layers' MLP, ``*_e`` the routed experts, ``*_s`` the shared
  experts fused into one MLP of width n_shared x moe_intermediate) and
  matrices are stored input-major (``x @ W``);
- attention leaves are stacked over all layers, ``*_d`` over the leading
  dense layers, router / expert / shared leaves over the expert layers;
- memory: parameters are upcast from the type they are served in (bf16
  on the chip) to float32 one layer, and one expert, at a time; the
  queries attend in blocks of ``Q_BLOCK`` rows, so a 9k-token context
  needs [H, 256, T] float32 scores (0.3 GB) and not [H, T, T] (10 GB);
  ``last=n`` projects only the last n positions onto the vocabulary
  ([9k, V] float32 would be 4.5 GB). All of it fits beside the engine.

Callers wrap the call in ``jax.default_matmul_precision("highest")``; it
is set here as well, for a caller that does not.
"""

from __future__ import annotations

from functools import partial

Q_BLOCK = 256


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


def _attention(cfg, params, x, l):
    """Causal attention of layer l on the normed x [T, D], per-head K
    and V materialised from the latent, queries in blocks."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = x.shape[0]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False).astype(
            f32)

    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dr, 2, dtype=f32) / dr))
    q = (x @ at("w_q")).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], axis=-1)
    ckr = x @ at("w_dkv")                                   # [T, r + dr]
    c = _rms(ckr[:, :r], at("kv_norm"), cfg.rms_norm_eps)
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

    a = lax.map(block, jnp.arange(nb)).reshape(nb * qb, H * dv)[:T]
    return a @ at("w_o")


def _mlp(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _dense_mlp(cfg, params, x, i):
    import jax.numpy as jnp
    from jax import lax

    def at(name):
        return lax.dynamic_index_in_dim(params[name], i, 0, False).astype(
            jnp.float32)

    return _mlp(x, at("w_gate_d"), at("w_up_d"), at("w_down_d"))


def route(cfg, scores, bias):
    """scores [T, E] (sigmoid), bias [E] -> routing weights [T, E]:
    s_e / sum over the chosen * routed_scaling_factor where e is among
    the top-k of scores + bias, 0 elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    T, E = scores.shape
    _, idx = lax.top_k(scores + bias, cfg.num_experts_per_tok)
    chosen = jnp.zeros((T, E), bool).at[jnp.arange(T)[:, None], idx].set(
        True)
    w = jnp.where(chosen, scores, 0.0)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor


def _moe_mlp(cfg, params, x, i):
    """Routed experts (each computed for every token) + shared experts
    of expert layer i on the normed x [T, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32

    def at(name):
        return lax.dynamic_index_in_dim(params[name], i, 0, False)

    scores = jax.nn.sigmoid(x @ at("w_router").astype(f32))     # [T, E]
    w = route(cfg, scores, at("router_bias").astype(f32))

    def expert(acc, e):
        def w_(name):
            return lax.dynamic_index_in_dim(
                at(name), e, 0, False).astype(f32)

        y = _mlp(x, w_("w_gate_e"), w_("w_up_e"), w_("w_down_e"))
        return acc + lax.dynamic_index_in_dim(w, e, 1, True) * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      jnp.arange(cfg.num_experts))
    if cfg.n_shared_experts > 0:
        out = out + _mlp(x, at("w_gate_s").astype(f32),
                         at("w_up_s").astype(f32), at("w_down_s").astype(f32))
    return out


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32; ``l`` may be traced (one compiled
    program serves every layer; rehearse.py compiles it)."""
    import jax.numpy as jnp
    from jax import lax

    eps = cfg.rms_norm_eps

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    h = h + _attention(cfg, params, _rms(h, at("ln_attn"), eps), l)
    x = _rms(h, at("ln_mlp"), eps)
    kd = cfg.first_k_dense_replace
    moe = partial(_moe_mlp, cfg, params, x)
    if kd <= 0:
        return h + moe(l)
    n_moe = cfg.num_layers - kd
    return h + lax.cond(
        l < kd,
        lambda: _dense_mlp(cfg, params, x, jnp.clip(l, 0, kd - 1)),
        lambda: moe(jnp.clip(l - kd, 0, n_moe - 1)))


def reference_logits(params, cfg, tokens, last=None):
    """Logits float32 for one sequence of token ids: [T, V], or with
    ``last`` = n the last n positions only, [n, V]."""
    import jax
    import jax.numpy as jnp

    if not (cfg.is_mla and cfg.moe_router == "deepseek_v3"
            and cfg.q_lora_rank == 0 and cfg.num_experts > 0):
        raise NotImplementedError(
            "this reference covers latent attention without a query LoRA "
            "and the deepseek_v3 router")
    if cfg.n_group > 1 or cfg.rope_scaling or cfg.tie_word_embeddings:
        raise NotImplementedError(
            "this reference does not cover group-limited routing, rope "
            "scaling or tied embeddings")
    one_layer = jax.jit(partial(layer, cfg))

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
