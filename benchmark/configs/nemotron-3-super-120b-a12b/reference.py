"""The plain reference for the Nemotron-H family as Nemotron 3 Super
states it (``nemotron_h``: a stack whose layer is ONE sub-block, a
Mamba-2 mixer with groups of B and C, attention without positions, or
routed experts in a latent beside a full-width shared expert): written
from the published description in straightforward ``jax.numpy`` and
float32. No cache, no stored state, no kernel, no chunking, no batching,
none of the program's model code (``dynamo_tpu/models/``); the
recurrence is a literal loop over the tokens of the one sequence.

    logits = reference_logits(params, cfg, tokens)      # [T, V] float32
    logits = reference_logits(params, cfg, tokens, last=n)   # the last n rows

Entry  h = embed[token].
Layer  h += Block_l(rms(h; ln_l)), the block that
       ``hybrid_override_pattern[l]`` names (``cfg.layer_types[l]``).
Exit   logits = rms(h; ln_final) @ lm_head.

``mamba`` (M), H heads of P channels, state N, G groups; head h belongs
to group g = h // (H / G):

    [z, xBC, dt] = split(W_in u)           sizes (H P, H P + 2 G N, H)
    xBC_t = silu(b_conv + sum_k conv_w[k] * xBC_{t - (d_conv-1) + k})
    [x, B, C] = split(xBC_t)               sizes (H P, G N, G N)
    dt_t  = softplus(dt_t + b_dt)          a head
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
                                           A = -exp(A_log) a head, S_{-1} = 0
    y_t[h] = S_t[h] C_t[g] + d_skip[h] x_t[h]
    out_t = W_out(group_rms(y_t * silu(z_t)) * ssm_norm)
                            the gate, THEN the RMS over each group's H P / G

``attention`` (*): causal softmax(q k^T / sqrt(hd)) v over the T tokens,
KV heads shared by groups of H/KV query heads, no bias, NO positional
embedding; computed in blocks of queries so that 2,048 tokens fit beside
the engine.

``moe`` (E), on the normed input x:

    s = sigmoid(W_r x) float32 over the router's published width;
    chosen = the top ``num_experts_per_tok`` of s + router_bias;
    w = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20);
    u = x W_lat_in                        hidden -> latent, once a token
    r = sum over the experts HELD of w_e relu(u W_up[e])^2 W_down[e]
    out = r W_lat_out + relu(x W_up_s)^2 W_down_s

Every expert HELD (``cfg.num_experts`` of them, the router's experts
``first_expert`` and up) is computed for every token and weighted by its
gate, zero where it was not chosen. An expert the router chose that is
not held adds nothing: the configuration is one chip's share of a layer's
experts, and this reference is given the same share (the guide's section
4); its partial sum goes through W_lat_out as the program's does.

Departures from the published description, each of naming or storage,
none of arithmetic:
- the leaves carry this repo's names (``w_in`` = in_proj, ``conv_w``
  [d_conv, channels] = conv1d.weight transposed, ``b_dt`` = dt_bias,
  ``d_skip`` = D, ``ssm_norm`` = the gated norm's weight, ``w_router`` =
  gate.weight transposed, ``router_bias`` = e_score_correction_bias,
  ``w_lat_in`` / ``w_lat_out`` = the latent pair, ``w_up`` / ``w_down``
  = an expert's up_proj / down_proj, ``w_up_s`` / ``w_down_s`` = the
  shared expert's, ``ln_mixer`` / ``ln_mlp`` = a layer's one norm) and
  matrices are stored input-major (``x @ W``);
- a layer's norm is kept in ``ln_mixer`` where the layer is a mixer (M
  and * in order) and in ``ln_mlp`` where it is experts; Mamba-2 leaves
  are stacked over the M layers, attention leaves over the * layers,
  every leaf of the experts' part over the E layers;
- the residual stream is float32 (the published ``residual_in_fp32`` is
  false);
- no rotary embedding in the * layers: the family's attention applies
  none (``rope_theta`` / ``partial_rotary_factor`` are unused by it);
- the vocabulary is the slice the configuration holds: token ids and
  logits are over ``cfg.vocab_size`` rows;
- the self-drafting head is absent: the served logits are the main
  model's and do not depend on it;
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
    """[(kind, index into that kind's stacks, index of the layer's
    norm in ln_mixer or ln_mlp)] per layer."""
    out, at = [], {"mamba": 0, "attention": 0, "moe": 0}
    for kind in cfg.layer_types[:cfg.num_layers]:
        norm = at["moe"] if kind == "moe" else at["mamba"] + at["attention"]
        out.append((kind, at[kind], norm))
        at[kind] += 1
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
        s = jnp.einsum("thd,shd->hts", qb, k) / (hd ** 0.5)
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
    H, P, N, G, dc = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                      cfg.mamba_n_groups, cfg.mamba_d_conv)
    T = u.shape[0]

    def at(name):
        return lax.dynamic_index_in_dim(params[name], m, 0, False).astype(f32)

    z, xbc, dt = jnp.split(u @ at("w_in"), [H * P, 2 * H * P + 2 * G * N],
                           axis=-1)
    xp = jnp.concatenate([jnp.zeros((dc - 1, xbc.shape[1]), f32), xbc])
    w = at("conv_w")                                        # [dc, channels]
    xbc = jax.nn.silu(at("b_conv")
                      + sum(xp[k:k + T] * w[k] for k in range(dc)))
    x, b, c = jnp.split(xbc, [H * P, H * P + G * N], axis=-1)
    x = x.reshape(T, H, P)
    # a head's B and C are its group's
    b = jnp.repeat(b.reshape(T, G, N), H // G, axis=1)      # [T, H, N]
    c = jnp.repeat(c.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + at("b_dt"))                   # [T, H]
    A = -jnp.exp(at("A_log"))                               # [H]

    def token(s, xs):                                       # s [H, P, N]
        dt_t, x_t, b_t, c_t = xs
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = lax.scan(token, jnp.zeros((H, P, N), f32), (dt, x, b, c))
    y = (y + at("d_skip")[:, None] * x).reshape(T, H * P)
    g = (y * jax.nn.silu(z)).reshape(T, G, H * P // G)
    g = _rms(g, at("ssm_norm").reshape(G, H * P // G), cfg.rms_norm_eps)
    return g.reshape(T, H * P) @ at("w_out")


def _relu2(x):
    import jax

    return jax.nn.relu(x) ** 2


def _experts(cfg, params, x, e):
    """What ``moe`` layer e adds, of the normed x [T, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    T = x.shape[0]

    def at(name):
        return lax.dynamic_index_in_dim(params[name], e, 0, False)

    s = jax.nn.sigmoid(x @ at("w_router").astype(f32))
    _, idx = lax.top_k(s + at("router_bias").astype(f32),
                       cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    route = jnp.zeros((T, cfg.router_width), f32).at[
        jnp.arange(T)[:, None], idx].set(w)
    u = x @ at("w_lat_in").astype(f32)                      # [T, latent]

    def expert(acc, i):         # i counts the experts HELD
        up, down = (lax.dynamic_index_in_dim(at(n), i, 0, False).astype(f32)
                    for n in ("w_up", "w_down"))
        gate = lax.dynamic_index_in_dim(route, cfg.first_expert + i, 1, True)
        return acc + gate * (_relu2(u @ up) @ down), None

    r, _ = lax.scan(expert, jnp.zeros_like(u), jnp.arange(cfg.num_experts))
    return (r @ at("w_lat_out").astype(f32)
            + _relu2(x @ at("w_up_s").astype(f32))
            @ at("w_down_s").astype(f32))


def _layer(cfg, kind, params, h, i, n):
    """h + the block of ``kind`` (its index i in that kind's stacks) of
    rms(h) under the layer's norm (row n of ln_mlp for experts, of
    ln_mixer otherwise)."""
    from jax import lax

    ln = params["ln_mlp" if kind == "moe" else "ln_mixer"]
    x = _rms(h, lax.dynamic_index_in_dim(ln, n, 0, False), cfg.rms_norm_eps)
    block = {"mamba": _mamba, "attention": _attention, "moe": _experts}[kind]
    return h + block(cfg, params, x, i)


def layer(cfg, params, h, l):
    """One layer on h [T, D] float32 with a traced layer index: the
    one-layer program a memory count compiles. Layer l is taken as the
    l-th ``moe`` layer (l clipped to their count), the largest kind: one
    expert's float32 matrices beside the shared expert's."""
    import jax.numpy as jnp

    n = sum(kind == "moe" for kind, _, _ in _pattern(cfg))
    e = jnp.minimum(l, n - 1)
    return _layer(cfg, "moe", params, h, e, e)


def reference_logits(params, cfg, tokens, last=None):
    """Logits [T, V] float32 for one sequence of token ids; with ``last``
    only the last ``last`` positions are projected."""
    import jax
    import jax.numpy as jnp

    if not getattr(cfg, "moe_latent_size", 0):
        raise NotImplementedError(
            "this reference is the Nemotron-H family's; the configuration "
            "has no moe_latent_size")
    steps = {kind: jax.jit(partial(_layer, cfg, kind))
             for kind in ("mamba", "attention", "moe")}

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)

    h = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for kind, i, n in _pattern(cfg):
        h = steps[kind](params, h, jnp.int32(i), jnp.int32(n))
    return head(params, h if last is None else h[-last:])
