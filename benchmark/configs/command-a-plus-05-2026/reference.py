"""The plain reference for Command A+ (``command-a-plus-05-2026``, the
language model): what its layers compute, written from the published
description (config.json's keys and the family's public implementation,
Cohere2 in ``transformers``) in straightforward ``jax.numpy`` and
float32: no cache, no pool, no page, no kernel, no batching, none of the
program's model code.

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on the residual stream h [T, D] (``use_parallel_block``):

    x   = LN(h) = w * (h - mean(h)) / sqrt(var(h) + layer_norm_eps)
                                        ONE norm a layer, no bias
    q, k, v = x W_q, x W_k, x W_v       128 / 8 / 8 heads of 128, no bias,
                                        no q/k norm, scale 128 ** -0.5
    layer_types[l] = sliding_attention: q and k rotated over INTERLEAVED
        pairs (x[2i], x[2i+1]) by pos * theta ** (-2i / 128)
        (``rope_gptj``, all 128 columns), position j visible from t iff
        t - sliding_window < j <= t;
    = full_attention: no positional embedding at all, j visible iff j <= t
    s   = sigmoid(x W_r)                128 scores; the 8 largest chosen,
                                        weights s_e / sum(chosen s)
    routed = sum over the chosen e of weight_e
             W_down,e (silu(W_gate,e x) * (W_up,e x))
    shared = (1 / 4) sum over the 4 shared experts j of
             W_down,j (silu(W_gate,j x) * (W_up,j x))
    h'  = h + attn W_o + routed + shared       both branches read x; ONE add

then the final LN and ``logits = logit_scale * (h E^T)`` with the tied
embedding E, or the head ``lm_head`` where the configuration is run
untied.

Departures from the published description, each because the catalog's
``config`` does not carry the point (about.json ``assumed``), at the
line that makes it:
  - "average": the mean over the shared experts' outputs, ADDED to the
    routed sum (``_ff``); the other reading, (routed + sum shared) / 2,
    is the control ``shared_halved``;
  - no selection bias, no expert groups, no scaling factor on the routed
    sum: the config has no key for any (``_ff``);
  - the width of one expert is ``intermediate_size`` (the catalog's
    note); ``prefix_dense_intermediate_size`` is read by no layer under
    ``first_k_dense_replace`` 0;
  - LayerNorm in float32 with the mean subtracted and no bias, RoPE on
    the window layers only and over interleaved pairs: the family's
    public implementation (``_ln``, ``_rope``, ``_layer``);
  - the chip's share: the router keeps its published width
    (``cfg.router_width``), the stacks hold experts ``[cfg.first_expert,
    cfg.first_expert + cfg.num_experts)`` and a pair routed to another
    expert adds nothing here, in the program and here alike (``_ff``);
    the shared experts are counted once.
The layouts are read from ``cfg.layer_window`` / ``cfg.layer_rope``,
which are config.json's ``layer_types`` as data (a window or None, a
bool).

``control`` names ONE deliberate fault, for the tests and the builder's
tool (tools/command_a_long_context_check.py), never the model:
``window_ignored`` (the window layers see every earlier position),
``full_rotated`` (the full layers rotate too), ``half_split`` (the
rotation over the pairs (i, i + 64)), ``sequential`` (the second half
reads LN(h + attention) and not x), ``rms_norm`` (the mean kept),
``shared_summed`` (the shared experts' sum, not their mean),
``shared_halved`` ((routed + sum shared) / 2), ``softmax_gate`` (scores
by a softmax over the router's outputs).

Memory, for a sequence of 33k tokens beside the engine's weights on a 16
GB chip: layers are indexed one at a time; a layer is computed
QUERY_BLOCK queries at a time under ``lax.map``: the scores of ONE KV
head's 16 query heads ([16, 128, T] float32 = 0.27 GB at 33k) and one
expert's float32 matrices (3 x 64 MiB) are what is alive beside h, x, k
and v ([T, D] and [T, 8, 128] float32: 1.35 GB at 33k); ``last=n``
projects only the last n rows. Every held expert is evaluated for every
token and weighted by its routing weight (zero when not chosen): exact,
and no dispatch to get wrong.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
The rule of agreement is the harness's (benchmark/reference.py judge).
"""

from __future__ import annotations

from functools import partial

QUERY_BLOCK = 128
CONTROLS = ("window_ignored", "full_rotated", "half_split", "sequential",
            "rms_norm", "shared_summed", "shared_halved", "softmax_gate")


def _ln(x, w, eps, keep_mean=False):
    """LayerNorm without a bias; ``keep_mean``: the control ``rms_norm``."""
    import jax.numpy as jnp
    from jax import lax

    if not keep_mean:
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta, half_split=False):
    """x: [T, heads, hd] at positions ``pos`` [T]: pair i = columns
    (2i, 2i + 1) rotated by pos * theta ** (-2i / hd); ``half_split``:
    the control (pair i = columns (i, i + hd / 2))."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if half_split:
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(y, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _ff(cfg, control, at, y):
    """The routed experts held here + the shared experts, on y [t, D]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    t = y.shape[0]
    E, K, I = cfg.num_experts, cfg.num_experts_per_tok, cfg.intermediate_size
    r = y @ at("w_router").astype(f32)              # [t, router width]
    # no selection bias, no groups: the K largest scores
    s = (jax.nn.softmax(r, axis=-1) if control == "softmax_gate"
         else jax.nn.sigmoid(r))
    top, idx = lax.top_k(s, K)
    g = top / jnp.sum(top, axis=-1, keepdims=True)  # norm_topk_prob
    route = jnp.zeros_like(s).at[jnp.arange(t)[:, None], idx].set(g)

    def expert(acc, e):
        def w_(name):
            return lax.dynamic_index_in_dim(at(name), e, 0, False).astype(f32)

        # the stacks hold experts first_expert ...: a pair routed to any
        # other expert is another chip's and adds nothing here
        g_e = lax.dynamic_index_in_dim(route, cfg.first_expert + e, 1, True)
        return acc + g_e * _swiglu(y, w_("w_gate"), w_("w_up"),
                                   w_("w_down")), None

    routed, _ = lax.scan(expert, jnp.zeros_like(y), jnp.arange(E))
    S = cfg.n_shared_experts
    if not S:
        return routed

    def shared_expert(acc, j):
        # shared expert j: columns [j I, (j + 1) I) of the stacks
        w_g = lax.dynamic_slice_in_dim(at("w_gate_s"), j * I, I, 1)
        w_u = lax.dynamic_slice_in_dim(at("w_up_s"), j * I, I, 1)
        w_d = lax.dynamic_slice_in_dim(at("w_down_s"), j * I, I, 0)
        return acc + _swiglu(y, w_g.astype(f32), w_u.astype(f32),
                             w_d.astype(f32)), None

    total, _ = lax.scan(shared_expert, jnp.zeros_like(y), jnp.arange(S))
    if control == "shared_summed":
        return routed + total
    if control == "shared_halved":
        return (routed + total) / 2
    # "average": the mean over the shared experts, added to the routed sum
    return routed + total / S


def _layer(cfg, window, rotate, control, params, h, l):
    """One layer on h [T, D] float32; ``l`` is a traced layer index,
    ``window`` / ``rotate`` its static kind."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    G = H // KV
    T, D = h.shape
    eps = cfg.rms_norm_eps
    keep_mean = control == "rms_norm"
    if control == "window_ignored":
        window = None
    if control == "full_rotated":
        rotate = True

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    x = _ln(h, at("ln_attn"), eps, keep_mean)
    pos = jnp.arange(T)
    k = (x @ at("wk").astype(f32)).reshape(T, KV, hd)
    v = (x @ at("wv").astype(f32)).reshape(T, KV, hd)
    if rotate:
        k = _rope(k, pos, cfg.rope_theta, control == "half_split")
    blk = min(QUERY_BLOCK, T)
    n = -(-T // blk)
    pad = n * blk - T
    xp, hp = jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(h, ((0, pad), (0, 0)))
    j = pos[None, :]

    def block(i):
        xb = lax.dynamic_slice_in_dim(xp, i * blk, blk, 0)
        hb = lax.dynamic_slice_in_dim(hp, i * blk, blk, 0)
        t = i * blk + jnp.arange(blk)
        seen = j <= t[:, None]
        if window is not None:
            seen = seen & (j > t[:, None] - window)

        def kv_head(acc, g):
            # the G query heads of KV head g: columns [g G hd, (g+1) G hd)
            wq = lax.dynamic_slice_in_dim(at("wq"), g * G * hd, G * hd, 1)
            wo = lax.dynamic_slice_in_dim(at("wo"), g * G * hd, G * hd, 0)
            q = (xb @ wq.astype(f32)).reshape(blk, G, hd)
            if rotate:
                q = _rope(q, t, cfg.rope_theta, control == "half_split")
            kg = lax.dynamic_index_in_dim(k, g, 1, False)       # [T, hd]
            vg = lax.dynamic_index_in_dim(v, g, 1, False)
            s = jnp.einsum("thd,sd->hts", q, kg) * hd ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            a = jnp.einsum("hts,sd->thd", jax.nn.softmax(s, axis=-1), vg)
            return acc + a.reshape(blk, G * hd) @ wo.astype(f32), None

        attn, _ = lax.scan(kv_head, jnp.zeros((blk, D), f32),
                           jnp.arange(KV))
        # the parallel block: the second half reads x, as attention did
        y = (_ln(hb + attn, at("ln_attn"), eps, keep_mean)
             if control == "sequential" else xb)
        return hb + attn + _ff(cfg, control, at, y)

    return lax.map(block, jnp.arange(n)).reshape(n * blk, D)[:T]


def layer(cfg, params, h, l):
    """One layer by a traced index, for a memory count: the window layer
    (the larger program of the two kinds)."""
    return _layer(cfg, cfg.sliding_window, True, None, params, h, l)


def reference_logits(params, cfg, tokens, last=None, *, control=None):
    """Logits [T, V] float32 for one sequence of token ids, or with
    ``last=n`` the last n rows [n, V]."""
    import jax
    import jax.numpy as jnp

    if control is not None and control not in CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    kinds = {}

    def layer_fn(l):
        kind = (cfg.layer_window[l], bool(cfg.layer_rope[l]))
        if kind not in kinds:
            kinds[kind] = jax.jit(partial(_layer, cfg, *kind, control))
        return kinds[kind]

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _ln(h, params["ln_final"], cfg.rms_norm_eps,
                control == "rms_norm")
        # tied: the embedding, as published; untied where the
        # configuration is run so (about.json reduced)
        w = (params["embed"].T if cfg.tie_word_embeddings
             else params["lm_head"])
        # logit_scale, which the program keeps as the divisor 1 / scale
        return (x @ w.astype(jnp.float32)) / cfg.logits_scaling

    h = embed(params, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg.num_layers):
        h = layer_fn(l)(params, h, jnp.int32(l))
    return head(params, h if last is None else h[-last:])
