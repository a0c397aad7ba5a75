"""The plain reference for the Granite 4.0-H family (``granitemoehybrid``:
Mamba-2 mixers, attention where ``layer_types`` says so, routed experts
beside one shared expert in every layer, four multipliers): written from
the published description in straightforward ``jax.numpy`` and float32.
No cache, no stored state, no kernel, no chunking, no batching, none of
the program's model code (``dynamo_tpu/models/granite.py``); the
recurrence is a literal loop over the tokens of the one sequence.

    logits = reference_logits(params, cfg, tokens)      # [T, V] float32
    logits = reference_logits(params, cfg, tokens, last=n)   # the last n rows

Entry  h = embedding_multiplier * embed[token].
Layer  h += r * Mixer_l(rms(h));  h += r * (Routed(x) + Shared(x)),
       x = rms(h), r = residual_multiplier.
Exit   logits = (rms(h) @ head) / logits_scaling.

Mixer_l attends where ``layer_types[l] == "attention"``: causal
softmax(attention_multiplier * q k^T) v over the T tokens (the
multiplier in place of 1/sqrt(hd)), KV heads shared by groups of H/KV
query heads, no bias, no positional embedding; computed in blocks of
queries so that 2,048 tokens fit beside the engine. Elsewhere it is the
Mamba-2 mixer, H heads of P channels, state N, one group:

    [z, xBC, dt] = split(W_in u)           sizes (H P, H P + 2 N, H)
    xBC_t = silu(b_conv + sum_k conv_w[k] * xBC_{t - (d_conv-1) + k})
    [x, B, C] = split(xBC_t)               sizes (H P, N, N)
    dt_t  = softplus(dt_t + b_dt)          a head
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
                                           A = -exp(A_log) a head, S_{-1} = 0
    y_t[h] = S_t[h] C_t + d_skip[h] x_t[h]
    out_t = W_out(rms(y_t * silu(z_t)) * ssm_norm)   gate, then norm over H P

Routed: logits = W_r x over the router's published width, the top
``num_experts_per_tok`` of them, softmax over those; every expert HELD
(``cfg.num_experts`` of them, the router's experts ``first_expert`` and
up) is computed for every token and weighted by its gate, zero where it
was not chosen. An expert the router chose that is not held adds
nothing: the configuration is one chip's share of a layer's experts, and
this reference is given the same share (the guide's section 4). Shared:
the same SwiGLU at ``shared_intermediate_size``, every token, weight 1.

Departures from the published description, each of naming or storage,
none of arithmetic:
- the leaves carry this repo's names (``w_in`` = in_proj, ``conv_w``
  [d_conv, channels] = conv1d.weight transposed, ``b_dt`` = dt_bias,
  ``d_skip`` = D, ``ssm_norm`` = the gated norm's weight, ``w_router`` =
  router.layer, ``w_gate`` / ``w_up`` = the two halves of an expert's
  fused input_linear, ``w_gate_s`` / ``w_up_s`` / ``w_down_s`` =
  shared_mlp, ``ln_mixer`` = input_layernorm, ``ln_mlp`` =
  post_attention_layernorm) and matrices are stored input-major (``x @
  W``);
- Mamba leaves are stacked over the Mamba layers, attention leaves over
  the attending layers, everything else over all layers;
- parameters are upcast from the type they are served in (bf16 on the
  chip) to float32 one layer, and one expert, at a time.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from functools import partial

QUERY_BLOCK = 512


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
            out.append(("mamba", m))
            m += 1
    return out


def _attention(cfg, params, x, a):
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    T = x.shape[0]

    def at(name):
        return lax.dynamic_index_in_dim(params[name], a, 0, False).astype(f32)

    q = (x @ at("wq")).reshape(T, H, hd)
    k = jnp.repeat((x @ at("wk")).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat((x @ at("wv")).reshape(T, KV, hd), H // KV, axis=1)
    out = []
    for t0 in range(0, T, QUERY_BLOCK):     # exact: a row's softmax is whole
        qb = q[t0:t0 + QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, k) * cfg.attention_multiplier
        causal = (jnp.arange(T)[None, :]
                  <= (t0 + jnp.arange(qb.shape[0]))[:, None])
        s = jnp.where(causal[None], s, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out).reshape(T, H * hd) @ at("wo")


def _mamba(cfg, params, u, m):
    """The Mamba-2 mixer of Mamba layer m on u [T, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, P, N, dc = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                   cfg.mamba_d_conv)
    T = u.shape[0]

    def at(name):
        return lax.dynamic_index_in_dim(params[name], m, 0, False).astype(f32)

    z, xbc, dt = jnp.split(u @ at("w_in"), [H * P, 2 * H * P + 2 * N],
                           axis=-1)
    xp = jnp.concatenate([jnp.zeros((dc - 1, xbc.shape[1]), f32), xbc])
    w = at("conv_w")                                        # [dc, channels]
    xbc = jax.nn.silu(at("b_conv")
                      + sum(xp[k:k + T] * w[k] for k in range(dc)))
    x, b, c = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    x = x.reshape(T, H, P)
    dt = jax.nn.softplus(dt + at("b_dt"))                   # [T, H]
    A = -jnp.exp(at("A_log"))                               # [H]

    def token(s, xs):                                       # s [H, P, N]
        dt_t, x_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, s @ c_t                                   # [H, P]

    _, y = lax.scan(token, jnp.zeros((H, P, N), f32), (dt, x, b, c))
    y = (y + at("d_skip")[:, None] * x).reshape(T, H * P)
    return _rms(y * jax.nn.silu(z), at("ssm_norm"), cfg.rms_norm_eps) \
        @ at("w_out")


def _experts(cfg, params, h, l):
    """h + r * (the held routed experts + the shared expert) of rms(h)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = h.shape[0]

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) \
            @ down.astype(f32)

    x = _rms(h, at("ln_mlp"), cfg.rms_norm_eps)
    top, idx = lax.top_k(x @ at("w_router").astype(f32),
                         cfg.num_experts_per_tok)
    route = jnp.zeros((T, cfg.router_width), f32).at[
        jnp.arange(T)[:, None], idx].set(jax.nn.softmax(top, axis=-1))

    def expert(acc, e):         # e counts the experts HELD
        y = swiglu(x, *(lax.dynamic_index_in_dim(at(n), e, 0, False)
                        for n in ("w_gate", "w_up", "w_down")))
        gate = lax.dynamic_index_in_dim(route, cfg.first_expert + e, 1, True)
        return acc + gate * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(h),
                      jnp.arange(cfg.num_experts))
    out = out + swiglu(x, at("w_gate_s"), at("w_up_s"), at("w_down_s"))
    return h + cfg.residual_multiplier * out


def _mamba_layer(cfg, params, h, l, m):
    from jax import lax

    x = _rms(h, lax.dynamic_index_in_dim(params["ln_mixer"], l, 0, False),
             cfg.rms_norm_eps)
    return _experts(cfg, params,
                    h + cfg.residual_multiplier * _mamba(cfg, params, x, m),
                    l)


def _attn_layer(cfg, params, h, l, a):
    from jax import lax

    x = _rms(h, lax.dynamic_index_in_dim(params["ln_mixer"], l, 0, False),
             cfg.rms_norm_eps)
    return _experts(
        cfg, params,
        h + cfg.residual_multiplier * _attention(cfg, params, x, a), l)


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32 with a traced layer index: the
    one-layer program a memory count compiles. Layer l is taken as the
    l-th MAMBA layer (l clipped to their count): an attending layer is
    smaller."""
    import jax.numpy as jnp

    n_mamba = sum(1 for kind, _ in _pattern(cfg) if kind == "mamba")
    return _mamba_layer(cfg, params, h, l, jnp.minimum(l, n_mamba - 1))


def reference_logits(params, cfg, tokens, last=None):
    """Logits [T, V] float32 for one sequence of token ids; with ``last``
    only the last ``last`` positions are projected ([last, V]: at 2,048
    tokens beside a serving engine the full [T, 100352] does not fit)."""
    import jax
    import jax.numpy as jnp

    if not getattr(cfg, "mamba_n_heads", 0):
        raise NotImplementedError(
            "this reference is the Granite 4.0-H family's; the "
            "configuration has no mamba_n_heads")
    mamba_layer = jax.jit(partial(_mamba_layer, cfg))
    attn_layer = jax.jit(partial(_attn_layer, cfg))

    @jax.jit
    def embed(params, toks):
        return cfg.embedding_multiplier * params["embed"][toks].astype(
            jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        wh = params.get("lm_head")
        wh = params["embed"].T if wh is None else wh
        return (x @ wh.astype(jnp.float32)) / cfg.logits_scaling

    h = embed(params, jnp.asarray(tokens, jnp.int32))
    for l, (kind, i) in enumerate(_pattern(cfg)):
        step = mamba_layer if kind == "mamba" else attn_layer
        h = step(params, h, jnp.int32(l), jnp.int32(i))
    return head(params, h if last is None else h[-last:])
