"""The plain reference for the Kimi Linear family (``kimi_linear``: Kimi
Delta Attention mixers, latent attention without positions where the
configuration's lists say so, a dense MLP in the first layers and
sigmoid-routed experts beside a shared expert after them): written from
the published description in straightforward ``jax.numpy`` and float32.
No cache, no stored state, no kernel, no chunked form, no batching, none
of the program's model code (``dynamo_tpu/models/kimi_linear.py`` and
``mla.py`` are not imported); the delta rule is a literal loop over the
tokens of the one sequence.

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on h [T, D], x = rms(h) * ln_mixer. A KDA layer (H heads of d_k
= d_v channels, a head a matrix S [d_k, d_v], S_{-1} = 0):

    [q, k, v]_t = silu(sum_j conv_w[j] * (W_qkv x)_{t - (d_conv-1) + j})
    q = q / |q| / sqrt(d_k),  k = k / |k|        a head (|.|^2 + 1e-6)
    g_t    = -exp(A_log[head]) * softplus(W_f2 (W_f1 x_t) + b_dt)  [H, d_k]
    beta_t = sigmoid(W_beta x_t)                                   [H]
    S'   = Diag(exp(g_t)) S_{t-1}          a decay A KEY CHANNEL
    S_t  = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t  = S_t^T q_t
    h   += W_out(rms_head(o_t) * kda_norm * sigmoid(W_g2 (W_g1 x_t) + b_g))

An attending layer is latent attention WITHOUT positions, un-absorbed:

    q        = W_q x                      [T, H, dn + dr], no query LoRA
    [c, r]   = split(W_dkv x)             sizes (rank, dr)
    c        = rms(c) * kv_norm           the latent a cache would keep
    k_h      = [W_uk_h c, r]              per-head key, MATERIALISED; the
                                          dr columns r are shared by the
                                          heads and NOT rotated, nor are
                                          the query's (mla_use_nope)
    v_h      = W_uv_h c                   per-head value, MATERIALISED
    a_h      = softmax_causal(q_h . k_h / sqrt(dn + dr)) v_h
    h       += W_o [a_1 .. a_H]

Then, with y = rms(h) * ln_mlp: layers l < first_k_dense_replace add the
dense MLP W_down(silu(W_gate y) * W_up y); the others add

    s      = sigmoid(W_router y)                    over the router's
                                                    published width
    chosen = top-k of (s + router_bias)             the bias selects,
                                                    never weighs
    w_e    = s_e / sum_chosen(s) * routed_scaling_factor   (chosen; else 0)
    out    = sum_{e held} w_e * MLP_e(y)  +  MLP_shared(y)

with every expert HELD (``cfg.num_experts`` of them, the router's
experts ``first_expert`` and up) evaluated for every token and weighted
by w_e (zero when not chosen). An expert the router chose that is not
held adds nothing: the configuration is one chip's share of a layer's
experts, and this reference is given the same share (the guide's section
4). ``num_expert_group`` 1 / ``topk_group`` 1 limits nothing, so no
group step is written. Final RMSNorm; logits through ``lm_head``.

Departures from the published description, each of naming, storage or
size, none of arithmetic:
- the leaves carry this repo's names (``w_qkv`` / ``conv_w`` = q_proj,
  k_proj, v_proj and their three short convolutions side by side,
  ``conv_w`` [d_conv, channels] = conv1d.weight transposed, ``w_f1`` /
  ``w_f2`` = f_a_proj / f_b_proj, ``b_dt`` = dt_bias, ``w_beta`` =
  b_proj, ``w_g1`` / ``w_g2`` / ``b_g`` = g_a_proj / g_b_proj and its
  bias, ``kda_norm`` = o_norm.weight, ``w_out`` = o_proj; ``w_dkv`` =
  kv_a_proj_with_mqa, ``kv_norm`` = kv_a_layernorm, ``w_uk`` / ``w_uv``
  = the two halves of kv_b_proj; ``router_bias`` =
  e_score_correction_bias, ``*_d`` the dense layer's MLP, ``*_e`` the
  routed experts, ``*_s`` the shared expert; ``ln_mixer`` =
  input_layernorm, ``ln_mlp`` = post_attention_layernorm) and matrices
  are stored input-major (``x @ W``);
- KDA leaves are stacked over the KDA layers, latent-attention leaves
  over the attending layers, ``*_d`` over the leading dense layers,
  router / expert / shared leaves over the expert layers, norms over all
  layers;
- memory: parameters are upcast from the type they are served in (bf16
  on the chip) to float32 one layer, and one expert, at a time; the
  queries attend in blocks of ``Q_BLOCK`` rows, so an 8.7k-token context
  needs [H, 256, T] float32 scores (0.3 GB) and not [H, T, T] (10 GB);
  ``last=n`` projects only the last n positions onto the vocabulary
  ([8.7k, 163840] float32 would be 5.7 GB). All of it fits beside the
  engine.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from functools import partial

Q_BLOCK = 256


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _pattern(cfg):
    """[(kind, index into that kind's stack)] per layer."""
    out, m, a = [], 0, 0
    for kind in cfg.layer_types[:cfg.num_layers]:
        if kind == "attention":
            out.append(("attn", a))
            a += 1
        else:
            out.append(("kda", m))
            m += 1
    return out


def _at(params, name, i):
    import jax.numpy as jnp
    from jax import lax

    return lax.dynamic_index_in_dim(params[name], i, 0, False).astype(
        jnp.float32)


def _kda(cfg, params, x, m):
    """The KDA mixer of KDA layer m on x [T, D] (normed)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, dk, dc = cfg.kda_n_heads, cfg.kda_head_dim, cfg.mamba_d_conv
    T = x.shape[0]
    at = partial(_at, params, i=m)

    qkv = x @ at("w_qkv")
    xp = jnp.concatenate([jnp.zeros((dc - 1, qkv.shape[1]), f32), qkv])
    w = at("conv_w")                                        # [dc, channels]
    qkv = jax.nn.silu(sum(xp[j:j + T] * w[j] for j in range(dc)))
    q, k, v = (y.reshape(T, H, dk) for y in jnp.split(qkv, 3, axis=-1))

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * dk ** -0.5, unit(k)
    g = ((x @ at("w_f1")) @ at("w_f2") + at("b_dt")).reshape(T, H, dk)
    g = -jnp.exp(at("A_log"))[:, None] * jax.nn.softplus(g)
    beta = jax.nn.sigmoid(x @ at("w_beta"))                 # [T, H]

    def token(S, xs):                                       # S [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = lax.scan(token, jnp.zeros((H, dk, dk), f32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(((x @ at("w_g1")) @ at("w_g2")
                           + at("b_g")).reshape(T, H, dk))
    o = _rms(o, at("kda_norm"), cfg.rms_norm_eps) * gate
    return o.reshape(T, H * dk) @ at("w_out")


def _attention(cfg, params, x, a):
    """Latent attention of attending layer a on x [T, D] (normed),
    un-absorbed, no rotation on either side."""
    import jax
    import jax.numpy as jnp

    H, r, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    T = x.shape[0]
    at = partial(_at, params, i=a)

    q = (x @ at("w_q")).reshape(T, H, dn + dr)
    ckr = x @ at("w_dkv")
    c = _rms(ckr[:, :r], at("kv_norm"), cfg.rms_norm_eps)
    k = jnp.concatenate([
        (c @ at("w_uk")).reshape(T, H, dn),
        jnp.broadcast_to(ckr[:, None, r:], (T, H, dr))], axis=-1)
    v = (c @ at("w_uv")).reshape(T, H, dv)
    out = []
    for t0 in range(0, T, Q_BLOCK):     # exact: a row's softmax is whole
        qb = q[t0:t0 + Q_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) * (dn + dr) ** -0.5
        causal = (jnp.arange(T)[None, :]
                  <= (t0 + jnp.arange(qb.shape[0]))[:, None])
        s = jnp.where(causal[None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out).reshape(T, H * dv) @ at("w_o")


def _second_half(cfg, params, h, l, dense):
    """h + the dense MLP (``dense``: l < first_k_dense_replace) or the
    held routed experts + the shared expert, of rms(h)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = h.shape[0]
    kd = cfg.first_k_dense_replace

    def swiglu(y, gate, up, down):
        return (jax.nn.silu(y @ gate.astype(f32)) * (y @ up.astype(f32))) \
            @ down.astype(f32)

    def one(name, i):       # a layer's leaf, in the type it is served in
        return lax.dynamic_index_in_dim(params[name], i, 0, False)

    y = _rms(h, _at(params, "ln_mlp", l), cfg.rms_norm_eps)
    if dense:
        return h + swiglu(y, *(one(n, l) for n in
                               ("w_gate_d", "w_up_d", "w_down_d")))
    li = l - kd
    s = jax.nn.sigmoid(y @ _at(params, "w_router", li))
    _, idx = lax.top_k(s + _at(params, "router_bias", li),
                       cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                           + cfg.moe_renorm_eps)
    route = jnp.zeros((T, cfg.router_width), f32).at[
        jnp.arange(T)[:, None], idx].set(chosen * cfg.routed_scaling_factor)

    def expert(acc, e):         # e counts the experts HELD
        # one expert of one layer, sliced out of the stack in one step
        out = swiglu(y, *(lax.dynamic_slice(
            params[n], (li, e, 0, 0), (1, 1, *params[n].shape[2:]))[0, 0]
            for n in ("w_gate_e", "w_up_e", "w_down_e")))
        gate = lax.dynamic_index_in_dim(route, cfg.first_expert + e, 1, True)
        return acc + gate * out, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(cfg.num_experts))
    if cfg.n_shared_experts > 0:
        out = out + swiglu(y, *(one(n, li) for n in
                                ("w_gate_s", "w_up_s", "w_down_s")))
    return h + out


def _layer(cfg, kind, dense, params, h, l, i):
    """Layer l (the i-th of its kind) on h [T, D]; l and i traced, so the
    layers of one kind and one second half share a program."""
    x = _rms(h, _at(params, "ln_mixer", l), cfg.rms_norm_eps)
    mixer = _kda if kind == "kda" else _attention
    return _second_half(cfg, params, h + mixer(cfg, params, x, i), l, dense)


def reference_logits(params, cfg, tokens, last=None):
    """Logits [T, V] float32 for one sequence of token ids; with ``last``
    only the last ``last`` positions are projected ([last, V])."""
    import jax
    import jax.numpy as jnp

    if not getattr(cfg, "kda_n_heads", 0):
        raise NotImplementedError(
            "this reference is the Kimi Linear family's; the configuration "
            "has no kda_n_heads")
    if getattr(cfg, "n_group", 0) > 1:
        raise NotImplementedError(
            "this reference writes no group-limited routing")

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)

    programs = {}
    with jax.default_matmul_precision("highest"):
        h = embed(params, jnp.asarray(tokens, jnp.int32))
        for l, (kind, i) in enumerate(_pattern(cfg)):
            key = kind, l < cfg.first_k_dense_replace
            if key not in programs:
                programs[key] = jax.jit(partial(_layer, cfg, *key))
            h = programs[key](params, h, jnp.int32(l), jnp.int32(i))
        return head(params, h if last is None else h[-last:])
