"""The plain reference for the Solar Open 2 family (``solar_open2``: Kimi
Delta Attention mixers whose delta rule may have negative eigenvalues,
gated GQA without positions where ``gqa_layers`` says so, and in every
layer sigmoid-routed experts beside a shared expert): written from the
published description in straightforward ``jax.numpy`` and float32. No
cache, no stored state, no kernel, no chunked form, no batching, none of
the program's model code (nothing of ``dynamo_tpu/models`` or
``dynamo_tpu/ops`` is imported); the delta rule is a literal loop over
the tokens of the one sequence and attention a softmax over the whole
prefix.

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on h [T, D], x = rms(h) * ln_mixer. A KDA layer (H heads of d_k
= d_v channels, a head a matrix S [d_k, d_v], S_{-1} = 0):

    [q, k, v]_t = silu(sum_j conv_w[j] * (W_qkv x)_{t - (d_conv-1) + j})
    q = q / |q| / sqrt(d_k),  k = k / |k|        a head (|.|^2 + 1e-6)
    g_t    = -exp(A_log[head]) * softplus(W_f2 (W_f1 x_t) + b_dt)  [H, d_k]
    beta_t = 2 * sigmoid(W_beta x_t)      in (0, 2): kda_allow_neg_eigval
                                          (1 * sigmoid without the key)
    S'   = Diag(exp(g_t)) S_{t-1}          a decay A KEY CHANNEL
    S_t  = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t  = S_t^T q_t
    h   += W_out(rms_head(o_t) * kda_norm * sigmoid(W_g2 (W_g1 x_t) + b_g))

An attending layer is GQA (H query heads over KV key/value heads of
``head_dim``) WITHOUT positions and without a q/k norm, behind a gate:

    q = W_q x [T, H, hd];  k = W_k x, v = W_v x [T, KV, hd]   no rotation
    a_h = softmax_causal(q_h . k_{h // (H / KV)} / sqrt(hd)) v_{h // (H / KV)}
    G   = sigmoid(W_gate x)               [T, H * hd], elementwise
    h  += W_o ([a_1 .. a_H] * G)

Then, in every layer, with y = rms(h) * ln_mlp:

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
4). Final RMSNorm; logits through ``lm_head``.

Departures from the published description, each of naming, storage or
size, none of arithmetic:
- the leaves carry this repo's names (``w_qkv`` / ``conv_w`` = q_proj,
  k_proj, v_proj and their three short convolutions side by side,
  ``conv_w`` [d_conv, channels] = conv1d.weight transposed, ``w_f1`` /
  ``w_f2`` = f_a_proj / f_b_proj, ``b_dt`` = dt_bias, ``w_beta`` =
  b_proj, ``w_g1`` / ``w_g2`` / ``b_g`` = g_a_proj / g_b_proj and its
  bias, ``kda_norm`` = o_norm.weight, ``w_out`` = the KDA o_proj;
  ``wq`` / ``wk`` / ``wv`` / ``wo`` the GQA projections and ``wg`` its
  gate's; ``router_bias`` = e_score_correction_bias, ``*_e`` the routed
  experts, ``*_s`` the shared expert; ``ln_mixer`` = input_layernorm,
  ``ln_mlp`` = post_attention_layernorm) and matrices are stored
  input-major (``x @ W``);
- KDA leaves are stacked over the KDA layers, attention leaves over the
  attending layers, router / expert / shared leaves and norms over all
  layers;
- memory: parameters are upcast from the type they are served in (bf16
  on the chip) to float32 one layer, and one expert, at a time; the
  queries attend in blocks of ``Q_BLOCK`` rows, so a 16.6k-token context
  needs [H, 128, T] float32 scores (0.55 GB) and not [H, T, T] (70 GB);
  a KDA layer's heads, which do not meet before the output projection,
  are computed ``HEAD_BLOCK`` at a time, each block over the whole
  sequence from S = 0; ``last=n`` projects only the last n positions
  onto the vocabulary. All of it fits beside the engine.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from functools import partial

Q_BLOCK = 128
HEAD_BLOCK = 8


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
    """The KDA mixer of KDA layer m on x [T, D] (normed). The heads do
    not meet before the output projection, so they are computed
    ``HEAD_BLOCK`` at a time, every block over the whole sequence token
    by token from S = 0 (memory only: [T, 3 x 64 x 128] float32 at 16.6k
    tokens would be 1.6 GB, three times over)."""
    import math

    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, dk, dc = cfg.kda_n_heads, cfg.kda_head_dim, cfg.mamba_d_conv
    T = x.shape[0]
    at = partial(_at, params, i=m)
    hb = math.gcd(H, HEAD_BLOCK)
    w_qkv, conv_w = at("w_qkv"), at("conv_w")       # [D, 3 H dk], [dc, 3 H dk]
    f = x @ at("w_f1")                              # [T, dk]: the decay's
    gt = x @ at("w_g1")                             # and the gate's bottleneck
    beta_all = cfg.kda_beta_scale * jax.nn.sigmoid(x @ at("w_beta"))  # [T, H]

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    def cols(w, part, b):       # the block's columns of q (0), k (1), v (2)
        return lax.dynamic_slice_in_dim(w, part * H * dk + b * hb * dk,
                                        hb * dk, axis=1)

    def one(w, b):              # the block's columns of a [*, H dk] leaf
        return lax.dynamic_slice_in_dim(w, b * hb * dk, hb * dk, axis=-1)

    def heads(out, b):          # heads [b hb, (b + 1) hb) on the whole x
        def conv(part):
            y = x @ cols(w_qkv, part, b)
            yp = jnp.concatenate([jnp.zeros((dc - 1, hb * dk), f32), y])
            w = cols(conv_w, part, b)
            return jax.nn.silu(sum(yp[j:j + T] * w[j] for j in range(dc))
                               ).reshape(T, hb, dk)

        q, k, v = unit(conv(0)) * dk ** -0.5, unit(conv(1)), conv(2)
        g = (f @ one(at("w_f2"), b) + one(at("b_dt"), b)).reshape(T, hb, dk)
        a_log = lax.dynamic_slice_in_dim(at("A_log"), b * hb, hb)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(g)
        # kda_allow_neg_eigval: beta in (0, 2)
        beta = lax.dynamic_slice_in_dim(beta_all, b * hb, hb, axis=1)

        def token(S, xs):                                   # S [hb, dk, dv]
            q_t, k_t, v_t, g_t, b_t = xs
            S = jnp.exp(g_t)[:, :, None] * S
            delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = S + k_t[:, :, None] * delta[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        _, o = lax.scan(token, jnp.zeros((hb, dk, dk), f32),
                        (q, k, v, g, beta))
        gate = jax.nn.sigmoid((gt @ one(at("w_g2"), b)
                               + one(at("b_g"), b)).reshape(T, hb, dk))
        o = _rms(o, at("kda_norm"), cfg.rms_norm_eps) * gate
        w_out = lax.dynamic_slice_in_dim(at("w_out"), b * hb * dk, hb * dk)
        return out + o.reshape(T, hb * dk) @ w_out, None

    out, _ = lax.scan(heads, jnp.zeros_like(x), jnp.arange(H // hb))
    return out


def _attention(cfg, params, x, a):
    """Gated GQA of attending layer a on x [T, D] (normed): no rotation,
    no q/k norm, a softmax over every earlier position; the queries in
    blocks of ``Q_BLOCK`` rows (exact: a row's softmax is whole)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    T = x.shape[0]
    at = partial(_at, params, i=a)
    nb = -(-T // Q_BLOCK)

    q = (x @ at("wq")).reshape(T, KV, H // KV, hd)
    q = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0), (0, 0)))
    k = (x @ at("wk")).reshape(T, KV, hd)
    v = (x @ at("wv")).reshape(T, KV, hd)

    def block(t0):
        qb = lax.dynamic_slice_in_dim(q, t0, Q_BLOCK)
        s = jnp.einsum("tkgd,skd->kgts", qb, k) * hd ** -0.5
        causal = jnp.arange(T)[None, :] <= (t0 + jnp.arange(Q_BLOCK))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(block, jnp.arange(nb) * Q_BLOCK)
    out = out.reshape(nb * Q_BLOCK, H * hd)[:T]
    if "wg" in params:      # use_gqa_gate
        out = out * jax.nn.sigmoid(x @ at("wg"))
    return out @ at("wo")


def _second_half(cfg, params, h, l):
    """h + the held routed experts + the shared expert, of rms(h)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = h.shape[0]

    def swiglu(y, gate, up, down):
        return (jax.nn.silu(y @ gate.astype(f32)) * (y @ up.astype(f32))) \
            @ down.astype(f32)

    def one(name):          # the layer's leaf, in the type it is served in
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    y = _rms(h, _at(params, "ln_mlp", l), cfg.rms_norm_eps)
    s = jax.nn.sigmoid(y @ _at(params, "w_router", l))
    _, idx = lax.top_k(s + _at(params, "router_bias", l),
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
            params[n], (l, e, 0, 0), (1, 1, *params[n].shape[2:]))[0, 0]
            for n in ("w_gate_e", "w_up_e", "w_down_e")))
        gate = lax.dynamic_index_in_dim(route, cfg.first_expert + e, 1, True)
        return acc + gate * out, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(cfg.num_experts))
    if cfg.n_shared_experts > 0:
        out = out + swiglu(y, *(one(n) for n in
                                ("w_gate_s", "w_up_s", "w_down_s")))
    return h + out


def _layer(cfg, kind, params, h, l, i):
    """Layer l (the i-th of its kind) on h [T, D]; l and i traced, so the
    layers of one kind share a program."""
    x = _rms(h, _at(params, "ln_mixer", l), cfg.rms_norm_eps)
    mixer = _kda if kind == "kda" else _attention
    return _second_half(cfg, params, h + mixer(cfg, params, x, i), l)


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32 with a traced layer index, for a
    memory count (benchmark/rehearse.py, tools/cell_programs.py): the
    KDA layer, the kind three layers of four are, whatever ``l`` is."""
    import jax.numpy as jnp

    return _layer(cfg, "kda", params, h, l, jnp.int32(0))


def reference_logits(params, cfg, tokens, last=None):
    """Logits [T, V] float32 for one sequence of token ids; with ``last``
    only the last ``last`` positions are projected ([last, V])."""
    import jax
    import jax.numpy as jnp

    if not getattr(cfg, "kda_n_heads", 0) or getattr(cfg, "kv_lora_rank", 0):
        raise NotImplementedError(
            "this reference is the Solar Open 2 family's (KDA layers "
            "beside GQA layers); the configuration has no kda_n_heads, or "
            "its attending layers are latent")
    if getattr(cfg, "first_k_dense_replace", 0):
        raise NotImplementedError("this reference writes no dense layer")

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
            if kind not in programs:
                programs[kind] = jax.jit(partial(_layer, cfg, kind))
            h = programs[kind](params, h, jnp.int32(l), jnp.int32(i))
        return head(params, h if last is None else h[-last:])
