"""The plain reference for the Jamba family (Mamba-1 mixers, attention
every ``attn_layer_period`` layers): written from the published
description in straightforward ``jax.numpy`` and float32. No cache, no
stored state, no batching, none of the program's model code
(``dynamo_tpu/models/jamba.py``); the recurrence is a literal loop over
the tokens of the one sequence.

    logits = reference_logits(params, cfg, tokens)      # [T, V] float32

Layer l on h [T, D]:  h += Mixer_l(rms(h));  h += W_down(silu(W_gate x)
* W_up x) with x = rms(h). Mixer_l attends where (l - attn_layer_offset)
% attn_layer_period == 0: causal softmax(q k^T / sqrt(hd)) v over the T
tokens, KV heads shared by groups of H/KV query heads, no bias, no
positional embedding. Elsewhere it is the Mamba-1 mixer:

    [x, z] = split(W_in u)                       u = rms(h), no bias
    x_t    = silu(b_conv + sum_k conv_w[k] * x_{t - (d_conv-1) + k})
    [dt_r, B, C] = split(W_x x_t)   sizes (dt_rank, N, N)
    dt_r, B, C   each through an RMSNorm with a learned weight (Jamba's
                 own step; plain Mamba has none)
    dt_t   = softplus(W_dt dt_r + b_dt)
    s_t    = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t,   s_{-1} = 0
    y_t    = s_t . C_t + d_skip * x_t            A = -exp(A_log) [d_inner, N]
    out_t  = W_out(y_t * silu(z_t))              no bias

Final RMSNorm; logits through ``lm_head``, or the embedding's transpose
where the tree has none (tied).

Departures from the published description, each of naming or storage,
none of arithmetic:
- the leaves carry this repo's names (``w_in`` = in_proj, ``conv_w``
  [d_conv, d_inner] = conv1d.weight transposed, ``w_x`` = x_proj,
  ``w_dt``/``b_dt`` = dt_proj, ``d_skip`` = D, ``ssm_b_norm`` /
  ``ssm_c_norm`` / ``dt_norm`` = b/c/dt_layernorm, ``ln_mixer`` =
  input_layernorm, ``ln_mlp`` = pre_ff_layernorm) and matrices are
  stored input-major (``x @ W``);
- Mamba leaves are stacked over the Mamba layers, attention leaves over
  the attending layers, MLP leaves and the two pre-norms over all layers;
- parameters are upcast from the type they are served in (bf16 on the
  chip) to float32 one layer at a time, so no float32 copy of the tree
  exists beside the engine's.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

from functools import partial


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _pattern(cfg):
    """[(kind, index into that kind's stack)] per layer."""
    out, m, a = [], 0, 0
    for l in range(cfg.num_layers):
        if (l - cfg.attn_layer_offset) % cfg.attn_layer_period == 0:
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
    s = jnp.einsum("thd,shd->hts", q, k) * (hd ** -0.5)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, H * hd) @ at("wo")


def _mamba(cfg, params, u, m):
    """The Mamba-1 mixer of Mamba layer m on u [T, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    N, R, dc = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    T = u.shape[0]
    eps = cfg.rms_norm_eps

    def at(name):
        return lax.dynamic_index_in_dim(params[name], m, 0, False).astype(f32)

    x, z = jnp.split(u @ at("w_in"), 2, axis=-1)                # [T, di]
    xp = jnp.concatenate([jnp.zeros((dc - 1, x.shape[1]), f32), x])
    w = at("conv_w")                                            # [dc, di]
    conv = at("b_conv") + sum(xp[k:k + T] * w[k] for k in range(dc))
    x = jax.nn.silu(conv)
    dt_r, b, c = jnp.split(x @ at("w_x"), [R, R + N], axis=-1)
    dt_r = _rms(dt_r, at("dt_norm"), eps)
    b = _rms(b, at("ssm_b_norm"), eps)                          # [T, N]
    c = _rms(c, at("ssm_c_norm"), eps)
    dt = jax.nn.softplus(dt_r @ at("w_dt") + at("b_dt"))        # [T, di]
    A = -jnp.exp(at("A_log"))                                   # [di, N]

    def token(s, xs):
        dt_t, x_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = lax.scan(token, jnp.zeros((x.shape[1], N), f32),
                    (dt, x, b, c))
    y = y + at("d_skip") * x
    return (y * jax.nn.silu(z)) @ at("w_out")


def _mlp(cfg, params, h, l):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0,
                                        False).astype(jnp.float32)

    x = _rms(h, at("ln_mlp"), cfg.rms_norm_eps)
    return h + (jax.nn.silu(x @ at("w_gate")) * (x @ at("w_up"))) \
        @ at("w_down")


def _mamba_layer(cfg, params, h, l, m):
    from jax import lax

    x = _rms(h, lax.dynamic_index_in_dim(params["ln_mixer"], l, 0, False),
             cfg.rms_norm_eps)
    return _mlp(cfg, params, h + _mamba(cfg, params, x, m), l)


def _attn_layer(cfg, params, h, l, a):
    from jax import lax

    x = _rms(h, lax.dynamic_index_in_dim(params["ln_mixer"], l, 0, False),
             cfg.rms_norm_eps)
    return _mlp(cfg, params, h + _attention(cfg, params, x, a), l)


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32 with a traced layer index: the
    one-layer program rehearse.py compiles for its memory count. Layer l
    is taken as the l-th MAMBA layer (26 of the 28 are; l is clipped to
    their count): an attending layer is smaller."""
    import jax.numpy as jnp

    n_mamba = sum(1 for kind, _ in _pattern(cfg) if kind == "mamba")
    return _mamba_layer(cfg, params, h, l, jnp.minimum(l, n_mamba - 1))


def reference_logits(params, cfg, tokens):
    """Logits [T, V] float32 for one sequence of token ids."""
    import jax
    import jax.numpy as jnp

    if not getattr(cfg, "mamba_d_state", 0):
        raise NotImplementedError(
            "this reference is the Jamba family's; the configuration has "
            "no mamba_d_state")
    mamba_layer = jax.jit(partial(_mamba_layer, cfg))
    attn_layer = jax.jit(partial(_attn_layer, cfg))

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        wh = params.get("lm_head")
        wh = params["embed"].T if wh is None else wh
        return x @ wh.astype(jnp.float32)

    h = embed(params, jnp.asarray(tokens, jnp.int32))
    for l, (kind, i) in enumerate(_pattern(cfg)):
        step = mamba_layer if kind == "mamba" else attn_layer
        h = step(params, h, jnp.int32(l), jnp.int32(i))
    return head(params, h)
