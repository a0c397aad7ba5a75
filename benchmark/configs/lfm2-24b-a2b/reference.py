"""The plain reference for the LFM2-MoE family as LFM2-24B-A2B configures
it (gated short convolutions, GQA attention every few layers, two dense
MLPs and then sigmoid-scored routed experts with a selection bias):
written from the equations of ISSUE 33 in straightforward ``jax.numpy``
and float32. No cache, no state, no kernel, no batching, none of the
program's model code (nothing of ``dynamo_tpu/models/`` is imported).

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on h [T, D], with u = rms(h; ln_op_l) and v = rms(h; ln_ffn_l):
``h += Op_l(u)`` then ``h += FF_l(v)``.

``layer_types[l] == "conv"`` (K = conv_L_cache taps, no bias, NO
activation):

    [B, C, x] = split3(W_in u)            three blocks of D, in that order
    z_t  = B_t * x_t
    c_t  = sum_{j<K} conv_w[j] * z_{t-(K-1)+j}        z = 0 before token 0
    Op   = W_out (C_t * c_t)

written as a sum of K shifted products over the whole sequence: there is
no state to carry, so none to get wrong.

``"full_attention"``: q, k, v linear without bias (H / KV / KV heads of
hd = D / H); RMS norm over each head's hd values of q and of k (one
learned hd-vector each, shared by the heads); RoPE (theta from the
configuration, half-split rotation) at the token's position; causal
softmax attention at scale 1 / sqrt(hd), every query head against its KV
head (h // (H / KV)); output projection without bias.

``FF_l`` for l < num_dense_layers: ``W_down(silu(W_gate v) * W_up v)``.
For the other layers, with e = l - num_dense_layers:

    s      = sigmoid(W_router v)                       [T, E], float32
    chosen = top-k of (s + router_bias)                the bias selects,
                                                       never weighs
    w_e    = s_e / (sum_chosen(s) + 1e-6) * routed_scaling_factor
    FF     = sum_e w_e * MLP_e(v)

with EVERY expert evaluated for every token and weighted by w_e (zero
when not chosen): exact, and no dispatch to get wrong. Final RMS norm;
logits through ``lm_head``, or through the embedding's transpose where
the configuration ties them (the published one does; the cell's does
not: about.json, ``reduced``).

Departures from the published description, each of naming, storage or
size, none of arithmetic:
- the leaves carry this repo's names (``w_in`` = conv.in_proj, ``conv_w``
  = conv.conv.weight as [K, D], ``w_out`` = conv.out_proj, ``q_norm`` /
  ``k_norm`` = q_layernorm / k_layernorm, ``ln_op`` = operator_norm,
  ``ln_ffn`` = ffn_norm, ``ln_final`` = embedding_norm, ``router_bias`` =
  expert_bias, ``*_d`` the dense layers' MLP, ``*_e`` the routed experts)
  and matrices are stored input-major (``x @ W``);
- conv leaves are stacked over the conv layers, attention leaves over
  the attending ones, ``*_d`` over the leading dense layers, router and
  expert leaves over the expert layers, norms over all layers;
- memory: parameters are upcast from the type they are served in (bf16
  on the chip) to float32 one layer, and one expert, at a time; the
  queries attend in blocks of ``Q_BLOCK`` rows; ``last=n`` projects only
  the last n positions onto the vocabulary.

Callers wrap the call in ``jax.default_matmul_precision("highest")``; it
is set here as well, for a caller that does not.
"""

from __future__ import annotations

from functools import partial

Q_BLOCK = 256
RENORM_EPS = 1e-6


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, inv_freq):
    """x: [T, heads, hd]; half-split rotation by position 0..T-1."""
    import jax.numpy as jnp

    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(cfg, params, u, m):
    """The gated short convolution of conv layer m on the normed u
    [T, D]: K shifted products over the whole sequence."""
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T, D = u.shape
    K = cfg.conv_l_cache

    def at(name):
        return lax.dynamic_index_in_dim(params[name], m, 0, False).astype(f32)

    b, c, x = jnp.split(u @ at("w_in"), 3, axis=-1)
    z = jnp.concatenate([jnp.zeros((K - 1, D), f32), b * x], axis=0)
    w = at("conv_w")                                            # [K, D]
    conv = sum(w[j] * z[j:j + T] for j in range(K))
    return (c * conv) @ at("w_out")


def attention(cfg, params, u, a):
    """Causal GQA attention of attending layer a on the normed u [T, D],
    queries in blocks."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T, D = u.shape
    H, KV = cfg.num_heads, cfg.num_kv_heads
    hd = D // H
    eps = cfg.rms_norm_eps

    def at(name):
        return lax.dynamic_index_in_dim(params[name], a, 0, False).astype(f32)

    inv_freq = 1.0 / (cfg.rope_theta
                      ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    q = _rope(_rms((u @ at("wq")).reshape(T, H, hd), at("q_norm"), eps),
              inv_freq)
    k = _rope(_rms((u @ at("wk")).reshape(T, KV, hd), at("k_norm"), eps),
              inv_freq)
    v = (u @ at("wv")).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)          # head h reads KV head h//G
    v = jnp.repeat(v, H // KV, axis=1)
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, H, hd)
    kpos = jnp.arange(T)

    def block(_, xs):
        qs, first = xs
        s = jnp.einsum("qhd,khd->hqk", qs, k) / jnp.sqrt(f32(hd))
        qpos = first + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -1e30)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    _, out = lax.scan(block, None,
                      (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H * hd)[:T] @ at("wo")


def _mlp(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(cfg, scores, bias):
    """Dense gate [T, E]: the chosen experts' UNBIASED scores divided by
    (their sum + 1e-6), times the scaling factor; zero elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    T, E = scores.shape
    _, idx = lax.top_k(scores + bias, cfg.num_experts_per_tok)
    chosen = jnp.zeros((T, E), bool).at[jnp.arange(T)[:, None], idx].set(
        True)
    w = jnp.where(chosen, scores, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + RENORM_EPS)
    return w * cfg.routed_scaling_factor


def feed_forward(cfg, params, v, l):
    """FF_l on the normed v [T, D]; l is static."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    if l < cfg.num_dense_layers:
        return _mlp(v, *(params[n][l].astype(f32)
                         for n in ("w_gate_d", "w_up_d", "w_down_d")))
    e = l - cfg.num_dense_layers
    scores = jax.nn.sigmoid(v @ params["w_router"][e].astype(f32))
    w = route(cfg, scores, params["router_bias"][e].astype(f32))

    def expert(acc, i):
        def w_(name):
            return lax.dynamic_index_in_dim(
                params[name][e], i, 0, False).astype(f32)

        y = _mlp(v, w_("w_gate_e"), w_("w_up_e"), w_("w_down_e"))
        return acc + lax.dynamic_index_in_dim(w, i, 1, True) * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(v),
                      jnp.arange(cfg.num_experts))
    return out


def layer(cfg, l, params, h):
    """Layer l (static) on h [T, D] float32."""
    eps = cfg.rms_norm_eps
    kinds = cfg.layer_types
    u = _rms(h, params["ln_op"][l], eps)
    if kinds[l] == "conv":
        h = h + short_conv(cfg, params, u, sum(
            1 for k in kinds[:l] if k == "conv"))
    else:
        h = h + attention(cfg, params, u, sum(
            1 for k in kinds[:l] if k != "conv"))
    return h + feed_forward(cfg, params, _rms(h, params["ln_ffn"][l], eps), l)


def reference_logits(params, cfg, tokens, last=None):
    """Logits float32 for one sequence of token ids: [T, V], or with
    ``last`` = n the last n positions only, [n, V]."""
    import jax
    import jax.numpy as jnp

    kinds = tuple(getattr(cfg, "layer_types", ()))
    if not kinds or set(kinds) - {"conv", "full_attention"}:
        raise NotImplementedError(
            "this reference covers layers named conv or full_attention "
            f"(layer_types {kinds!r})")
    if cfg.rope_scaling:
        raise NotImplementedError(
            "this reference does not cover rope scaling")

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        if cfg.tie_word_embeddings:     # the family's own: head = embed^T
            return x @ params["embed"].astype(jnp.float32).T
        return x @ params["lm_head"].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = embed(params, jnp.asarray(tokens, jnp.int32))
        for l in range(cfg.num_layers):
            h = jax.jit(partial(layer, cfg, l))(params, h)
        return head(params, h if last is None else h[-last:])
