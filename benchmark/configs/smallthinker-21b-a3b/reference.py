"""The plain reference for SmallThinker-21BA3B-Instruct: what its layers
compute, written from the published description (config.json's keys, the
family's paper and public implementation) in straightforward
``jax.numpy`` and float32: no cache, no pool, no page, no kernel, no
batching, none of the program's model code.

    logits = reference_logits(params, cfg, tokens)            # [T, V]
    logits = reference_logits(params, cfg, tokens, last=n)    # [n, V]

Layer l on the residual stream h [T, D]:

    r   = h W_r                         the router reads the UN-NORMED
                                        layer input, before attention
    x   = rms_norm(h)
    q, k, v = x W_q, x W_k, x W_v       28 / 4 / 4 heads of 128, no bias,
                                        no q/k norm, scale 1 / sqrt(128)
    sliding_window_layout[l] = 0 (and rope_layout[l] = 0): no positional
        embedding, position j visible from t iff j <= t;
    = 1: q and k rotated (half-split ``rotate_half`` layout, theta
        ``rope_theta``), j visible from t iff t - window < j <= t
    h'  = h + attn W_o
    y   = rms_norm(h')
    h'' = h' + sum over the top-k experts e of r, g = softmax over the k
          chosen logits: g_e W_down,e (relu(W_gate,e y) * (W_up,e y))

then the final rms_norm and the untied head.

Departures from the published description, each because the catalog's
``config`` does not carry the point (about.json ``assumed``):
  - the router's input is taken to be the un-normed stream (the public
    implementation's; ``described_as`` says only "before attention");
  - the experts' activation is relu (``described_as``: "sparse ReGLU";
    the ``config`` has no activation key);
  - primary experts only (the ``config`` names no secondary ones);
  - ``moe_primary_router_apply_softmax`` + ``norm_topk_prob``: a softmax
    over all experts renormalised over the chosen is the softmax over
    the chosen logits, which is what is computed.
The layouts are read from ``cfg.layer_window`` / ``cfg.layer_rope``,
which are config.json's two lists as data (a window or None, a bool).

Memory, for a sequence of 12.5k tokens beside the engine's weights and
pools on a 16 GB chip: layers are indexed one at a time and experts
scanned one at a time, so no float32 copy of the tree exists (as
benchmark/reference.py); the scores of a layer are made for QUERY_BLOCK
queries at a time ([28, 512, T] float32 = 0.7 GB at 12.5k) and never
for all T; ``last=n`` projects only the last n rows (the whole [T, V]
is 7.6 GB at 12.5k tokens). Every expert is evaluated for every token
and weighted by its routing weight (zero when not chosen): exact, and no
dispatch to get wrong.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.
The rule of agreement is the harness's (benchmark/reference.py judge).
"""

from __future__ import annotations

from functools import partial

QUERY_BLOCK = 512


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, inv_freq):
    """x: [T, heads, hd] at positions 0 .. T-1; half-split rotation."""
    import jax.numpy as jnp

    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window, scale):
    """Causal attention of q [T, H, hd] over k, v [T, KV, hd], QUERY_BLOCK
    queries at a time; ``window``: None, or j visible iff t - window < j."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)        # [T, H, hd]
    v = jnp.repeat(v, g, axis=1)
    blk = min(QUERY_BLOCK, T)
    n = -(-T // blk)
    qp = jnp.pad(q, ((0, n * blk - T), (0, 0), (0, 0)))
    j = jnp.arange(T)[None, :]

    def block(i):
        t = i * blk + jnp.arange(blk)[:, None]              # [blk, 1]
        s = jnp.einsum("thd,shd->hts",
                       lax.dynamic_slice_in_dim(qp, i * blk, blk, 0),
                       k) * scale
        seen = j <= t
        if window is not None:
            seen = seen & (j > t - window)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    return lax.map(block, jnp.arange(n)).reshape(n * blk, H, hd)[:T]


def _layer(cfg, window, rotate, router_on_normed, params, h, l):
    """One layer on h [T, D] float32; ``l`` is a traced layer index,
    ``window`` / ``rotate`` its static kind. ``router_on_normed`` is the
    tests' control (the router moved behind the norm), never the model."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    T = h.shape[0]
    eps = cfg.rms_norm_eps
    E, K = cfg.num_experts, cfg.num_experts_per_tok

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    x = _rms(h, at("ln_attn"), eps)
    # the router reads the layer's input as it arrives: no norm
    r = (x if router_on_normed else h) @ at("w_router").astype(f32)
    q = (x @ at("wq").astype(f32)).reshape(T, H, hd)
    k = (x @ at("wk").astype(f32)).reshape(T, KV, hd)
    v = (x @ at("wv").astype(f32)).reshape(T, KV, hd)
    if rotate:
        inv = 1.0 / (cfg.rope_theta
                     ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
        q, k = _rope(q, inv), _rope(k, inv)
    a = _attend(q, k, v, window, hd ** -0.5)
    h = h + a.reshape(T, H * hd) @ at("wo").astype(f32)

    y = _rms(h, at("ln_mlp"), eps)
    top, idx = lax.top_k(r, K)
    g = jax.nn.softmax(top, axis=-1)                    # over the chosen K
    route = jnp.zeros((T, E), f32).at[
        jnp.arange(T)[:, None], idx].set(g)             # 0 where not chosen

    def expert(acc, e):
        def w_(name):
            return lax.dynamic_index_in_dim(
                at(name), e, 0, False).astype(f32)

        out = (jax.nn.relu(y @ w_("w_gate")) * (y @ w_("w_up"))) \
            @ w_("w_down")
        return acc + lax.dynamic_index_in_dim(route, e, 1, True) * out, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return h + out


def layer(cfg, params, h, l):
    """One layer by a traced index, for a memory count: the window layer
    (the larger program of the two kinds)."""
    return _layer(cfg, cfg.sliding_window, True, False, params, h, l)


def reference_logits(params, cfg, tokens, last=None, *,
                     router_on_normed=False):
    """Logits [T, V] float32 for one sequence of token ids, or with
    ``last=n`` the last n rows [n, V]."""
    import jax
    import jax.numpy as jnp

    kinds = {}

    def layer_fn(l):
        kind = (cfg.layer_window[l], bool(cfg.layer_rope[l]))
        if kind not in kinds:
            kinds[kind] = jax.jit(partial(_layer, cfg, *kind,
                                          router_on_normed))
        return kinds[kind]

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        return x @ params["lm_head"].astype(jnp.float32)

    h = embed(params, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg.num_layers):
        h = layer_fn(l)(params, h, jnp.int32(l))
    return head(params, h if last is None else h[-last:])
