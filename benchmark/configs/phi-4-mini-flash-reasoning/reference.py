"""The plain reference for the Phi-4-mini-flash family (``phi4flash``: the
decoder-hybrid-decoder of arXiv:2507.06607 with the differential
attention of arXiv:2410.05258): written from the published description in
straightforward ``jax.numpy`` and float32. No cache, no stored state, no
pages, no kernel, no chunking, no batching, none of the program's model
code (``dynamo_tpu/models/``); the recurrence is a literal loop over the
tokens of the one sequence, and EVERY layer runs at EVERY position.

    logits = reference_logits(params, cfg, tokens)      # [T, V] float32
    logits = reference_logits(params, cfg, tokens, last=n)   # the last n rows

``ln(x) = (x - mean) / sqrt(var + eps) * w + b`` (LayerNorm with a bias).
Entry  h = embed[token].
Layer  h += Mixer_l(ln(h));  h += W_down(silu(W_gate x) * W_up x), x = ln(h).
Exit   logits = ln(h) @ head (``lm_head``, or the embedding's transpose
       where the tree has none).
No positional embedding anywhere. With half = L / 2, Mixer_l is:

l even, l <= half: Mamba-1 (no inner norms):

    [x, z] = split(W_in u)
    x_t    = silu(b_conv + sum_k conv_w[k] * x_{t - (d_conv-1) + k})
    [dt_r, B, C] = split(W_x x_t)   sizes (dt_rank, N, N)
    dt_t   = softplus(W_dt dt_r + b_dt)
    s_t    = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t,   s_{-1} = 0
    y_t    = s_t . C_t + d_skip * x_t            A = -exp(A_log) [d_inner, N]
    out_t  = W_out(y_t * silu(z_t))
    layer ``half`` also hands down m_t = y_t, every position's.

l odd, l <= half + 1: differential attention, causal; layer half + 1
sees every position j <= t, the others t - window < j <= t:

    [q, k, v] = W u + b;  H query heads, KV key/value heads of hd
    pair i = query heads (2i, 2i+1); it reads KV heads (2j, 2j+1), j = i // 2
    a1 = softmax(q_{2i} k_{2j}^T / sqrt(hd)) [v_{2j}, v_{2j+1}]
    a2 = softmax(q_{2i+1} k_{2j+1}^T / sqrt(hd)) [v_{2j}, v_{2j+1}]
    lam0 = 0.8 - 0.6 exp(-0.3 l);  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
    o_i = rms(a1 - lam a2; 2 hd wide, eps 1e-5, diff_norm) * (1 - lam0)
    out = W_o concat_i(o_i) + b_o

l even, l > half: gated memory unit: out_t = W_2(silu(W_1 u_t) * m_t).

l odd, l > half + 1: cross attention: q = W_q u + b_q, then the
differential form above with its own lambdas, norm and lam0(l), over
layer half + 1's k and v of every position j <= t.

Attention runs in blocks of queries (two explicit softmaxes a pair, the
window a mask) so that thousands of tokens fit beside the engine.

Departures from the published description, each of naming or storage,
none of arithmetic:
- the leaves carry this repo's names (``w_in`` = in_proj, ``conv_w``
  [d_conv, d_inner] = conv1d.weight transposed, ``w_x`` = x_proj,
  ``w_dt`` / ``b_dt`` = dt_proj, ``d_skip`` = D, ``wq`` / ``wk`` / ``wv``
  = the columns of Wqkv, ``w_gate`` / ``w_up`` = the halves of
  gate_up_proj, ``lq1`` .. ``lk2`` = lambda_q1 .. lambda_k2,
  ``diff_norm`` = subln, ``w_gmu_in`` / ``w_gmu_out`` = the memory unit's
  in_proj / out_proj, ``ln_*`` / ``b_ln_*`` a LayerNorm's weight / bias)
  and matrices are stored input-major (``x @ W``);
- leaves are stacked a kind: norms and MLPs over all layers, Mamba leaves
  over the Mamba layers, ``wq`` / ``wo`` / lambdas / ``diff_norm`` over
  all attending layers (self-attending first, then cross), ``wk`` /
  ``wv`` over the self-attending ones;
- parameters are upcast from the type they are served in to float32 one
  layer at a time.

``fault`` (tests and tools only) computes ONE thing wrong, to show that
the comparison sees it: ``FAULTS`` names them.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from functools import partial

FAULTS = (
    "window_ignored",   # the window layers see every earlier position
    "lam_fixed",        # lam = lam0, the learned part left out
    "no_pair_norm",     # a1 - lam a2 without the RMS norm over the pair
    "m_gated",          # the memory is y_t * silu(z_t), taken after the gate
    "m_previous",       # the memory units read m_{t-1}
    "cross_windowed",   # the cross layers see a window of layer half + 1
    "inner_norms",      # Jamba's RMS norms on dt_r, B and C
    "a2_from_k1",       # the second softmax over the first key of the pair
)
Q_BLOCK = 512


def kinds(L):
    half = L // 2
    return [("mamba" if l <= half else "gmu") if l % 2 == 0 else
            ("window" if l < half + 1 else "full" if l == half + 1
             else "cross") for l in range(L)]


def _ln(x, w, b, eps):
    import jax.numpy as jnp
    from jax import lax

    c = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return c * lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rms(x, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _at(params, name, i):
    import jax.numpy as jnp
    from jax import lax

    return lax.dynamic_index_in_dim(params[name], i, 0,
                                    False).astype(jnp.float32)


def _mixer_in(cfg, params, h, l):
    return _ln(h, _at(params, "ln_mixer", l), _at(params, "b_ln_mixer", l),
               cfg.rms_norm_eps)


def _mlp(cfg, params, h, l):
    import jax

    x = _ln(h, _at(params, "ln_mlp", l), _at(params, "b_ln_mlp", l),
            cfg.rms_norm_eps)
    return h + (jax.nn.silu(x @ _at(params, "w_gate", l))
                * (x @ _at(params, "w_up", l))) @ _at(params, "w_down", l)


def _mamba(cfg, params, u, m, fault):
    """(out [T, D], the memory y [T, d_inner]) of Mamba layer m on u
    [T, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    N, R, dc = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    T = u.shape[0]
    at = partial(_at, params, i=m)
    x, z = jnp.split(u @ at("w_in"), 2, axis=-1)                # [T, di]
    xp = jnp.concatenate([jnp.zeros((dc - 1, x.shape[1]), f32), x])
    w = at("conv_w")                                            # [dc, di]
    x = jax.nn.silu(at("b_conv") + sum(xp[k:k + T] * w[k]
                                       for k in range(dc)))
    dt_r, b, c = jnp.split(x @ at("w_x"), [R, R + N], axis=-1)
    if fault == "inner_norms":
        dt_r, b, c = (_rms(v, cfg.rms_norm_eps) for v in (dt_r, b, c))
    dt = jax.nn.softplus(dt_r @ at("w_dt") + at("b_dt"))        # [T, di]
    A = -jnp.exp(at("A_log"))                                   # [di, N]

    def token(s, xs):
        dt_t, x_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = lax.scan(token, jnp.zeros((x.shape[1], N), f32), (dt, x, b, c))
    y = y + at("d_skip") * x
    gated = y * jax.nn.silu(z)
    return gated @ at("w_out"), (gated if fault == "m_gated" else y)


def _diff_attention(cfg, params, q, k, v, a, l, window, fault):
    """The differential form of attending layer a (layer l) for queries q
    [T, H, hd] over k, v [T, KV, hd] of the same T positions; ``window``
    None: every j <= t."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T, H, hd = q.shape
    KV = k.shape[1]
    at = partial(_at, params, i=a)
    fixed = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, f32))
    lam = fixed if fault == "lam_fixed" else (
        jnp.exp(jnp.sum(at("lq1") * at("lk1")))
        - jnp.exp(jnp.sum(at("lq2") * at("lk2"))) + fixed)
    q = q.reshape(T, H // 2, 2, hd)
    k = k.reshape(T, KV // 2, 2, hd)
    rep = (H // 2) // (KV // 2)
    # pair i reads KV pair i // rep
    k1 = jnp.repeat(k[:, :, 0], rep, axis=1)                    # [T, H/2, hd]
    k2 = k1 if fault == "a2_from_k1" else jnp.repeat(k[:, :, 1], rep, axis=1)
    vv = jnp.repeat(v.reshape(T, KV // 2, 2 * hd), rep, axis=1)
    out = []
    for t0 in range(0, T, Q_BLOCK):
        qb = q[t0:t0 + Q_BLOCK]
        tq = jnp.arange(t0, t0 + qb.shape[0])[:, None]
        tk = jnp.arange(T)[None, :]
        see = tk <= tq
        if window is not None:
            see = see & (tk > tq - window)

        def soft(qh, kh):
            s = jnp.einsum("tpd,spd->pts", qh, kh) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)

        a1 = jnp.einsum("pts,spd->tpd", soft(qb[:, :, 0], k1), vv)
        a2 = jnp.einsum("pts,spd->tpd", soft(qb[:, :, 1], k2), vv)
        o = a1 - lam * a2                                   # [tq, H/2, 2hd]
        if fault != "no_pair_norm":
            o = _rms(o, 1e-5) * at("diff_norm")
        out.append((o * (1.0 - fixed)).reshape(qb.shape[0], H * hd))
    return jnp.concatenate(out) @ at("wo") + at("bo")


def _queries(cfg, params, x, a):
    return (x @ _at(params, "wq", a) + _at(params, "bq", a)).reshape(
        x.shape[0], cfg.num_heads, cfg.head_dim_)


def _keys_values(cfg, params, x, a):
    shape = (x.shape[0], cfg.num_kv_heads, cfg.head_dim_)
    return ((x @ _at(params, "wk", a) + _at(params, "bk", a)).reshape(shape),
            (x @ _at(params, "wv", a) + _at(params, "bv", a)).reshape(shape))


def _mamba_layer(cfg, fault, params, h, l, m):
    out, y = _mamba(cfg, params, _mixer_in(cfg, params, h, l), m, fault)
    return _mlp(cfg, params, h + out, l), y


def _attn_layer(cfg, fault, window, params, h, l, a):
    """A self-attending layer; returns its k and v too."""
    x = _mixer_in(cfg, params, h, l)
    k, v = _keys_values(cfg, params, x, a)
    out = _diff_attention(cfg, params, _queries(cfg, params, x, a), k, v,
                          a, l, window, fault)
    return _mlp(cfg, params, h + out, l), k, v


def _gmu_layer(cfg, fault, params, h, l, g, mem):
    import jax
    import jax.numpy as jnp

    if fault == "m_previous":
        mem = jnp.concatenate([jnp.zeros_like(mem[:1]), mem[:-1]])
    x = _mixer_in(cfg, params, h, l)
    out = (jax.nn.silu(x @ _at(params, "w_gmu_in", g)) * mem) \
        @ _at(params, "w_gmu_out", g)
    return _mlp(cfg, params, h + out, l)


def _cross_layer(cfg, fault, params, h, l, a, k, v):
    x = _mixer_in(cfg, params, h, l)
    window = cfg.sliding_window if fault == "cross_windowed" else None
    out = _diff_attention(cfg, params, _queries(cfg, params, x, a), k, v,
                          a, l, window, fault)
    return _mlp(cfg, params, h + out, l)


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32 with a traced layer index: the
    one-layer program rehearse.py compiles for its memory count. Layer l
    is taken as the l-th Mamba layer (clipped to their count), the
    largest kind."""
    import jax.numpy as jnp

    m = jnp.minimum(l, cfg.num_layers // 4)
    return _mamba_layer(cfg, None, params, h, l, m)[0]


def reference_logits(params, cfg, tokens, last=None, fault=None):
    """Logits [T, V] float32 for one sequence of token ids; with ``last``
    only the last ``last`` positions are projected."""
    import jax
    import jax.numpy as jnp

    if not (getattr(cfg, "mamba_d_state", 0)
            and getattr(cfg, "kv_pool_by_kind", False)):
        raise NotImplementedError(
            "this reference is the Phi-4-mini-flash family's; the "
            "configuration is not of it")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    window = None if fault == "window_ignored" else cfg.sliding_window
    steps = {
        "mamba": jax.jit(partial(_mamba_layer, cfg, fault)),
        "window": jax.jit(partial(_attn_layer, cfg, fault, window)),
        "full": jax.jit(partial(_attn_layer, cfg, fault, None)),
        "gmu": jax.jit(partial(_gmu_layer, cfg, fault)),
        "cross": jax.jit(partial(_cross_layer, cfg, fault)),
    }

    @jax.jit
    def head(params, h):
        """The exit norm and the head, the head's columns upcast a block
        at a time (200,064 x 2,560 in float32 at once is 2 GB beside the
        engine)."""
        x = _ln(h, params["ln_final"], params["b_ln_final"],
                cfg.rms_norm_eps)
        wh = params.get("lm_head")
        wh = params["embed"].T if wh is None else wh
        D, V = wh.shape
        nb = next(n for n in (16, 8, 4, 2, 1) if V % n == 0)
        blocks = jax.lax.map(lambda w: x @ w.astype(jnp.float32),
                             jnp.moveaxis(wh.reshape(D, nb, V // nb), 1, 0))
        return jnp.moveaxis(blocks, 0, 1).reshape(x.shape[0], V)

    h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    m = a = g = 0
    mem = k = v = None
    for l, kind in enumerate(kinds(cfg.num_layers)):
        l_ = jnp.int32(l)
        if kind == "mamba":
            h, mem = steps[kind](params, h, l_, jnp.int32(m))
            m += 1
        elif kind in ("window", "full"):
            h, k, v = steps[kind](params, h, l_, jnp.int32(a))
            a += 1
        elif kind == "gmu":
            h = steps[kind](params, h, l_, jnp.int32(g), mem)
            g += 1
        else:
            h = steps[kind](params, h, l_, jnp.int32(a), k, v)
            a += 1
    return head(params, h if last is None else h[-last:])
