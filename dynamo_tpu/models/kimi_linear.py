"""Kimi Linear: Kimi Delta Attention (KDA) mixers with latent attention
(MLA, no positions) where the configuration's lists say so, a dense MLP
in the first ``first_k_dense_replace`` layers and sigmoid-routed experts
beside a shared expert after them (Moonshot Kimi-Linear family,
``model_type: kimi_linear``), on jamba.py's layout: the runs of layers
that keep state between the attending ones, the pool of per-sequence
recurrent state and the fused window are that module's
(``jamba.Blocks``); this one supplies the mixer, the layer's second
half, the pools' shapes, the step kernel's call, and an attending half
(``jamba.Attending``) built from models/mla.py's functions.

Layer l: ``h += Mixer_l(rms_norm(h))`` then ``h += FF_l(rms_norm(h))``.
A KDA mixer has H heads of d_k = d_v channels, a head a matrix of state
``S [d_k, d_v]`` float32:

    [q, k, v] = silu(causal depthwise conv1d(W_qkv u))     3 x H d_k
    q = l2norm(q) / sqrt(d_k),   k = l2norm(k)             a head
    g    = -exp(A_log[head]) * softplus(W_f2 (W_f1 u) + b_dt)   [H, d_k]
    beta = kda_beta_scale * sigmoid(W_beta u)                   [H]
    S'   = Diag(exp(g_t)) S_{t-1}            the decay, A KEY CHANNEL
    S_t  = S' + beta_t k_t (v_t - S'^T k_t)^T        the delta rule
    o_t  = S_t^T q_t
    out  = W_out(rms_norm_head(o) * kda_norm * sigmoid(W_g2 (W_g1 u) + b_g))

An attending layer is MLA with no query LoRA and NO rotation
(``cfg.mla_nope``): the cache keeps a token's normed latent ``c`` (rank
r) and its ``qk_rope_head_dim`` shared key columns as they are made;
absorbed queries, the read-only pools, the Pallas latent kernels and
the one commit a program are mla.py's (``_latent_qkv``, ``_attend_pool``,
``_attend_local``, ``_merge``, ``_latent_out``, ``_commit_chunk``), over
pools of the ATTENDING layers only (``[n_attn, pages, 1, ps, *]``).

FF_l: SwiGLU of ``intermediate_size`` in the dense layers; after them
``mla._deepseek_moe_mlp``: sigmoid scores over the router's published
width, the top-k of score + bias, the chosen scores renormalised and
scaled, the experts HELD here (``[first_expert, first_expert +
num_experts)``: the chip's share of a layer under expert parallelism,
``llama.moe_experts``' ``first``) and the shared expert on every token.
What the absent experts would add is left out.

**State.** A sequence carries, a KDA layer, S of every head (float32) and
the last ``d_conv - 1`` inputs of the three convolutions. The pool keeps
S as ``[N, H * d_v]``, N = d_k: what is H * d_v wide a token (v, beta, o)
lies along the lanes and a head's q, k and decay are columns on the
sublanes (ops/kda.py); both pools have jamba.py's ranks and axes, ``[S,
M, N, H * d_v]`` slot-major and the conv tails ``[M, S, (d_conv - 1) * 3
H d_k]`` layer-major, oldest input first. At the published widths a
row is 2 MiB a layer: the module declares no snapshots, so a prefix hit
counts as a miss, as for Jamba and Granite.

**Three forms of the scan.** A chunk of T tokens runs in matrix products:
for a chunk of Q tokens entered with S_in, ``G_t = sum_{s<=t} g_s`` (a
channel), ``A_ts = beta_t sum_i k_t[i] k_s[i] exp(G_t[i] - G_s[i])`` for
s < t, ``(I + A) [W | U] = [beta k exp(G) | beta v]`` (unit lower
triangular: forward substitution), ``V~ = U - W S_in``,

    o_t   = (q_t exp(G_t))^T S_in
            + sum_{s<=t} [sum_i q_t[i] k_s[i] exp(G_t[i] - G_s[i])] V~_s
    S_out = Diag(exp(G_Q)) S_in + sum_s (k_s exp(G_Q - G_s)) V~_s^T

with every exponent a difference ``<= 0``: nothing overflows whatever is
drawn (at Q = 1 it is the recurrence). Where the kernels run
(``llama.kernel_mode``, asked by jamba.forward because ``BLOCKS`` carries
a chunk kernel) that is ONE Pallas kernel on the gathered rows,
ops/kda.py ``kda_chunk``: a head's state and its tables stay in VMEM
over the whole chunk of T tokens, Q = 64. Elsewhere, and as the kernel's
reference, it is ``_kda_chunk`` in plain XLA at Q = ``kda_chunk_size``:
the tables and the solve do not depend on S_in and are made for all of a
program's chunks at once; only the three products with the state run
chunk after chunk. One token from a stored state: the step kernel on the
pool where the kernels run, ``_kda_step`` on gathered rows elsewhere.
Scopes: ``kda`` around the mixer with ``kda.proj``, ``kda.conv``,
``kda.gate``, ``kda.scan`` (either chunk form, and the step), ``kda.norm``
inside; ``attn`` with ``attn.latent``; ``mlp``; ``moe`` with
``moe.router``, ``moe.dispatch``, ``moe.experts``, ``moe.shared``;
``lm_head``, ``sample``, ``kv_carry`` as in jamba.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import jamba, llama, mla
from .config import ModelConfig, held_experts, hf_base, refuser
from .granite import WINDOW_COUNTS, held_first
from .jamba import _at, _causal_conv, num_mamba_layers
from .llama import (KVCacheSpec, Params, _mlp, _moe_use_blocked,
                    pairs_counted, rms_norm)
from ..ops.kda import kda_chunk, kda_step

KDA_KEYS = ("w_qkv", "conv_w", "w_f1", "w_f2", "b_dt", "A_log", "w_beta",
            "w_g1", "w_g2", "b_g", "kda_norm", "w_out")
LATENT_KEYS = ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv", "w_o")
DENSE_KEYS = ("w_gate_d", "w_up_d", "w_down_d")
EXPERT_KEYS = ("w_gate_e", "w_up_e", "w_down_e")
MOE_KEYS = ("w_router", "router_bias", "w_gate_s", "w_up_s", "w_down_s")
_HIGHEST = lax.Precision.HIGHEST


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``kimi_linear`` config.json. The two lists of
    ``linear_attn_config`` count layers from 1 and are kept whole in a
    file cut in depth: the entries up to ``num_hidden_layers`` are the
    layers that run (``num_experts``: config.held_experts)."""
    refuse = refuser("kimi_linear")
    c = hf_base(cfg)
    L = cfg["num_hidden_layers"]
    lin = cfg["linear_attn_config"]
    kda = [l for l in lin["kda_layers"] if l <= L]
    full = [l for l in lin["full_attn_layers"] if l <= L]
    if sorted(kda + full) != list(range(1, L + 1)):
        refuse(f"kda_layers {kda} and full_attn_layers {full}",
               f"up to num_hidden_layers they must partition the "
               f"layers 1..{L}: every layer is of exactly one kind")
    if not kda or not full:
        refuse("layers of one kind only",
               "the state pool holds the KDA layers and the latent "
               "pools the attending ones; a model of one kind is "
               "another module's")
    if not cfg.get("mla_use_nope", False):
        refuse("mla_use_nope false",
               "its attending layers apply no rotation to the shared "
               "key columns, and no cell would run the rotated form")
    if cfg.get("q_lora_rank"):
        refuse(f"q_lora_rank {cfg['q_lora_rank']}",
               "its attending layers project the queries at full "
               "rank")
    if (cfg.get("num_expert_group") or 1) != 1 \
            or (cfg.get("topk_group") or 1) != 1:
        refuse(f"num_expert_group {cfg.get('num_expert_group')} / "
               f"topk_group {cfg.get('topk_group')}",
               "the gate chooses among all the router's outputs: "
               "groups other than 1 are not computed")
    if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        refuse(f"moe_router_activation_func "
               f"{cfg['moe_router_activation_func']!r}",
               "the gate scores by a sigmoid")
    if cfg.get("moe_layer_freq", 1) != 1:
        refuse(f"moe_layer_freq {cfg['moe_layer_freq']}",
               "every layer after the first_k_dense_replace dense "
               "ones has routed experts")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "no layer rotates")
    c.num_experts, c.router_experts, c.first_expert = held_experts(
        cfg, "num_experts", "num_experts_per_token", refuse)
    attending = set(full)
    c.model_type = "kimi_linear"
    c.layer_types = tuple("attention" if l in attending else "kda"
                          for l in range(1, L + 1))
    c.kda_n_heads = lin["num_heads"]
    c.kda_head_dim = lin["head_dim"]
    c.mamba_d_conv = lin.get("short_conv_kernel_size", 4)
    c.mla_nope = True
    c.q_lora_rank = 0
    c.kv_lora_rank = cfg["kv_lora_rank"]
    c.qk_nope_head_dim = cfg["qk_nope_head_dim"]
    c.qk_rope_head_dim = cfg["qk_rope_head_dim"]
    c.v_head_dim = cfg["v_head_dim"]
    c.num_experts_per_tok = cfg["num_experts_per_token"]
    c.moe_router = "deepseek_v3"
    c.norm_topk_prob = bool(cfg.get("moe_renormalize", True))
    c.routed_scaling_factor = cfg.get("routed_scaling_factor", 1.0)
    c.n_shared_experts = cfg.get("num_shared_experts", 0)
    c.first_k_dense_replace = cfg.get("first_k_dense_replace", 0)
    c.moe_intermediate_size = cfg["moe_intermediate_size"]
    return c


def conv_width(cfg: ModelConfig) -> int:
    """Channels the three convolutions run over: q, k and v."""
    return 3 * cfg.kda_n_heads * cfg.kda_head_dim


# ------------------------------------------------------- params and pools


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec, dtype=None):
    """The latent and the shared-key pools of the attending layers only:
    mla.py's shapes with the attending layers on the leading axis."""
    dtype = dtype or cfg.jax_dtype
    A = len(cfg.attn_layer_ids)
    return tuple(jnp.zeros((A, *shape[1:]), dtype)
                 for shape in mla.cache_shapes(cfg, spec))


def init_state(cfg: ModelConfig, slots: int, dtype=None) -> jamba.State:
    """The recurrent-state pools for ``slots`` sequences: [S, M, d_k, H
    * d_v] float32, slot-major, and the conv tails [M, S, (d_conv - 1) *
    conv_width], layer-major as jamba.init_state's (what declares to the
    engine that this module's sequences carry state beside pages)."""
    M, H, dk = num_mamba_layers(cfg), cfg.kda_n_heads, cfg.kda_head_dim
    return (jnp.zeros((slots, M, dk, H * dk), jnp.float32),
            jnp.zeros((M, slots, (cfg.mamba_d_conv - 1) * conv_width(cfg)),
                      dtype or cfg.jax_dtype))


def _drawer(key: jax.Array, dtype):
    """(w, ks): ``w(*shape)`` draws a normal leaf of std 1 / sqrt(fan_in)
    from the next of ``ks``, the keys split from ``key``."""
    ks = iter(jax.random.split(key, 32))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    return w, ks


def kda_leaves(cfg: ModelConfig, w, ks, dtype) -> Params:
    """The KDA mixers' leaves (``KDA_KEYS``), stacked over the M KDA
    layers: what every family with this mixer draws."""
    D, M = cfg.hidden_size, num_mamba_layers(cfg)
    H, dk, dc = cfg.kda_n_heads, cfg.kda_head_dim, cfg.mamba_d_conv
    return {
        "w_qkv": w(M, D, 3 * H * dk),
        "conv_w": w(M, dc, 3 * H * dk),
        "w_f1": w(M, D, dk), "w_f2": w(M, dk, H * dk),
        "b_dt": jnp.zeros((M, H * dk), dtype),
        # a head's A between 1 and 16, as the family's Mamba-style init
        "A_log": jnp.log(jax.random.uniform(
            next(ks), (M, H), jnp.float32, 1.0, 16.0)).astype(dtype),
        "w_beta": w(M, D, H),
        "w_g1": w(M, D, dk), "w_g2": w(M, dk, H * dk),
        "b_g": jnp.zeros((M, H * dk), dtype),
        "kda_norm": jnp.ones((M, dk), dtype),
        "w_out": w(M, H * dk, D),
    }


def moe_leaves(cfg: ModelConfig, w, dtype) -> Params:
    """The leaves of ``_ff``'s expert layers (``MOE_KEYS`` and
    ``EXPERT_KEYS``), stacked over the layers after the
    ``first_k_dense_replace`` dense ones. The expert stacks hold the
    experts HELD (``cfg.num_experts``); the router and its selection
    bias are ``cfg.router_width`` wide."""
    D, Le = cfg.hidden_size, cfg.num_layers - cfg.first_k_dense_replace
    E, W, Im = cfg.num_experts, cfg.router_width, cfg.moe_intermediate_size
    Is = Im * cfg.n_shared_experts
    return {
        "w_router": w(Le, D, W),
        "router_bias": jnp.zeros((Le, W), dtype),
        "w_gate_e": w(Le, E, D, Im), "w_up_e": w(Le, E, D, Im),
        "w_down_e": w(Le, E, Im, D),
        "w_gate_s": w(Le, D, Is), "w_up_s": w(Le, D, Is),
        "w_down_s": w(Le, Is, D),
    }


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params; each kind of leaf stacked on its own axis 0
    (pre-norms over all L layers, KDA leaves over the M KDA layers,
    latent-attention leaves over the attending ones, the dense MLP over
    the first ``first_k_dense_replace`` layers, router, experts and
    shared expert over the layers after them)."""
    dtype = dtype or cfg.jax_dtype
    D, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    A = len(cfg.attn_layer_ids)
    Ha, r, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kd = cfg.first_k_dense_replace
    w, ks = _drawer(key, dtype)
    return {
        "embed": w(V, D), "lm_head": w(D, V),
        "ln_mixer": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        **kda_leaves(cfg, w, ks, dtype),
        "w_q": w(A, D, Ha * (dn + dr)),
        "w_dkv": w(A, D, r + dr),
        "kv_norm": jnp.ones((A, r), dtype),
        "w_uk": w(A, r, Ha * dn), "w_uv": w(A, r, Ha * dv),
        "w_o": w(A, Ha * dv, D),
        "w_gate_d": w(kd, D, I), "w_up_d": w(kd, D, I),
        "w_down_d": w(kd, I, D),
        **moe_leaves(cfg, w, dtype),
    }


# ----------------------------------------------------------- the mixer


def _kda_step(s, q, k, v, g, beta):
    """One token of the gated delta rule for every row. s [B, N, H * dv]
    float32 (N = d_k); q, k, g [B, H, N]; v [B, H, dv]; beta [B, H]. A
    row whose g and beta are 0 keeps its state. Returns (s, o [B, H,
    dv])."""
    B, N, C = s.shape
    H = q.shape[1]

    def col(x):             # [B, H, N] -> [B, N, H, 1]
        return jnp.swapaxes(x, 1, 2)[..., None]

    s = col(jnp.exp(g)) * s.reshape(B, N, H, C // H)        # S'
    u = jnp.sum(s * col(k), axis=1)                         # S'^T k
    s = s + col(k) * (beta[..., None] * (v - u))[:, None]
    return s.reshape(B, N, C), jnp.sum(s * col(q), axis=1)


def _kda_chunk(s0, q, k, v, g, beta, chunk: int):
    """T tokens of the gated delta rule from the carried state s0 [B, N,
    H * dv], in T / Q chunks of the matmul form (the module's
    docstring). q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]
    (g and beta 0 at a token that does not count), all float32. Returns
    (s after the last token, o [B, T, H, dv]). What does not depend on
    the carried state (the two [Q, Q] tables a head, whose exponents are
    differences G_t - G_s with s <= t, never positive, and the
    triangular solve, Q steps of forward substitution) is made for every
    chunk side by side; the chunks then run in order, three products
    with the state each."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    Q = math.gcd(T, chunk)
    nb = T // Q

    def chunks(x):          # [B, T, H, d] -> [B, nb, H, Q, d]
        return jnp.swapaxes(x.reshape(B, nb, Q, H, x.shape[-1]), 2, 3)

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta[..., None])                            # [.., Q, 1]
    G = jnp.cumsum(chunks(g), axis=3)                       # [B,nb,H,Q,dk]
    t, s = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    # [.., t, s, i] = exp(G_t[i] - G_s[i]) for s <= t, 0 after
    decay = jnp.exp(jnp.where(
        (s <= t)[:, :, None], G[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))
    ks = kc[..., None, :, :] * decay
    a = bc * jnp.where(s < t, jnp.sum(kc[..., :, None, :] * ks, -1), 0.0)
    p = jnp.sum(qc[..., :, None, :] * ks, -1)               # [.., Q, Q]
    rhs = jnp.concatenate([bc * kc * jnp.exp(G), bc * vc], -1)

    def solve(i, x):        # row i of (I + A) x = rhs, rows < i solved
        a_i = lax.dynamic_index_in_dim(a, i, 3, True)       # [.., 1, Q]
        r_i = lax.dynamic_index_in_dim(rhs, i, 3, True)
        return lax.dynamic_update_index_in_dim(
            x, r_i - dot("...ts,...sd->...td", a_i, x), i, 3)

    wu = lax.fori_loop(0, Q, solve, jnp.zeros_like(rhs))
    g_end = G[..., -1:, :]                                  # [.., 1, dk]
    xs = (wu[..., :dk], wu[..., dk:], qc * jnp.exp(G), p,
          kc * jnp.exp(g_end - G), jnp.exp(g_end[..., 0, :]))

    def one(S, xs):         # S [B, H, dk, dv]
        w, u, q_in, p, k_out, dec = xs
        vt = u - dot("bhqi,bhid->bhqd", w, S)
        o = dot("bhqi,bhid->bhqd", q_in, S) + dot("bhqs,bhsd->bhqd", p, vt)
        S = dec[..., None] * S + dot("bhsi,bhsd->bhid", k_out, vt)
        return S, o

    S, o = lax.scan(
        one, jnp.moveaxis(s0.reshape(B, dk, H, dv), 1, 2),
        jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), xs))
    # o [nb, B, H, Q, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, T, H, dv)
    return jnp.moveaxis(S, 1, 2).reshape(B, dk, H * dv), o


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(cfg: ModelConfig, mp, u, valid, s, tail, step=_kda_step,
         chunk=None, tail_step=None):
    """The KDA mixer on a chunk: jamba._mamba's call form. u [B, T, D]
    (normed); valid [B, T] (a row's valid tokens lead); s [B, d_k, H *
    d_v] float32 and tail [B, (d_conv - 1) * 3 H d_k]: the rows' state on
    entry. Returns (out [B, T, D], s, tail) with the state after each
    row's last valid token. ``step`` is the one-token recurrence (T ==
    1) with _kda_step's operands and results, ``s`` being whatever it
    carries: the rows' states, or the pool they lie in. ``chunk`` is the
    chunk kernel (T > 1) with _kda_chunk's operands and results but the
    chunk size, which is the kernel's own; None: _kda_chunk.
    ``tail_step`` is the one-token advance of the conv tails
    (jamba._causal_conv)."""
    f32 = jnp.float32
    B, T, _ = u.shape
    H, dk = cfg.kda_n_heads, cfg.kda_head_dim

    def dot(a, w):
        # operands in the weights' type, the result in float32: between
        # the mixer's matmuls nothing is rounded (jamba._mamba)
        return jnp.dot(a.astype(w.dtype), w, preferred_element_type=f32)

    def heads(x):
        return x.reshape(B, T, H, dk)

    with jax.named_scope("kda"):
        with jax.named_scope("kda.proj"):
            qkv = dot(u, mp["w_qkv"])                       # [B, T, 3 H dk]
        qkv, tail = _causal_conv(mp, qkv, valid, tail, cfg.mamba_d_conv,
                                 "kda.conv", tail_step)
        with jax.named_scope("kda.gate"):
            q, k, v = (heads(x) for x in jnp.split(qkv, 3, axis=-1))
            q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
            g = heads(dot(dot(u, mp["w_f1"]), mp["w_f2"])
                      + mp["b_dt"].astype(f32))
            g = -jnp.exp(mp["A_log"].astype(f32))[:, None] \
                * jax.nn.softplus(g)
            beta = jax.nn.sigmoid(dot(u, mp["w_beta"]))     # [B, T, H]
            if cfg.kda_beta_scale != 1.0:   # (0, 2): negative eigenvalues
                beta = cfg.kda_beta_scale * beta
            # a token that does not count moves no state
            g = jnp.where(valid[:, :, None, None], g, 0.0)
            beta = jnp.where(valid[:, :, None], beta, 0.0)
        with jax.named_scope("kda.scan"):
            if T == 1:      # one token from a stored state
                s, o = step(s, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0])
                o = o[:, None]
            elif chunk is not None:     # a chunk, in the kernel
                s, o = chunk(s, q, k, v, g, beta)
            else:           # a chunk from a carried state, by matmuls
                s, o = _kda_chunk(s, q, k, v, g, beta, cfg.kda_chunk_size)
        with jax.named_scope("kda.norm"):
            gate = jax.nn.sigmoid(heads(
                dot(dot(u, mp["w_g1"]), mp["w_g2"])
                + mp["b_g"].astype(f32)))
            o = rms_norm(o, mp["kda_norm"].astype(f32),
                         cfg.rms_norm_eps) * gate
        with jax.named_scope("kda.proj"):
            out = dot(o.reshape(B, T, H * dk), mp["w_out"])
    return out, s, tail


# ------------------------------------------------- the layer's second half


def _ff(params: Params, cfg: ModelConfig, norm, h, l, valid, l0):
    """(h + the second half of layer l on norm(h), WINDOW_COUNTS of this
    layer): jamba._dense_ff's call form. The run that starts at layer l0
    is dense (the first ``first_k_dense_replace`` layers: jamba.segments
    cuts a run there) or routed experts held here + the shared expert."""
    x = norm(h, lax.dynamic_index_in_dim(params["ln_mlp"], l, 0, False))
    kd = cfg.first_k_dense_replace
    if l0 < kd:
        lp = _at(params, DENSE_KEYS, l)
        with jax.named_scope("mlp"):
            return (h + _mlp(x, *(lp[n] for n in DENSE_KEYS)),
                    jnp.zeros(len(WINDOW_COUNTS), jnp.int32))
    B, T, _ = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    li = l - kd
    with jax.named_scope("moe"):
        lp = _at(params, MOE_KEYS, li)
        with jax.named_scope("moe.router"):
            gate = mla._deepseek_gate(x.astype(jnp.float32), lp["w_router"],
                                      lp["router_bias"], cfg)
            counted = pairs_counted(cfg, gate[1], valid)
        # the sorted form reads w[layer, expert] from the whole stacks,
        # the dense form one layer's (llama._moe_use_blocked: the rule)
        blocked = _moe_use_blocked(None, B * T, E, k)
        lp.update({n: params[n] for n in EXPERT_KEYS} if blocked
                  else _at(params, EXPERT_KEYS, li))
        out = mla._deepseek_moe_mlp(
            x, lp, cfg, live=valid if blocked else None,
            layer=li if blocked else None, first=held_first(cfg), gate=gate)
    return h + out, counted


# ------------------------------------------------------ the attending half


def _scale(cfg: ModelConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _latent_chunk(cfg: ModelConfig, params: Params, positions, kv_lat,
                  kv_rope, page_table, flat_slots, page_slots, allow_pallas,
                  mesh):
    """jamba.Attending.chunk over the latent pools, as mla.forward reads
    and writes them: the pools hold what lies before the chunk's first
    position, the chunk attends to that and, causally, to itself, and
    its own latents wait for one commit a pool at the program's end."""
    kernel = llama.kernel_mode(allow_pallas, mesh=mesh)
    live = positions >= 0
    safe_pos = jnp.maximum(positions, 0)    # read by no layer: mla_nope
    before = jnp.maximum(positions[:, 0], 0)            # [B] pool extent
    own = (live[:, None, :]
           & (positions[:, None, :] <= positions[:, :, None]))  # [B, T, T]

    def attend(a, x, cache):
        lp = {n: params[n][a] for n in LATENT_KEYS}
        q_lat, q_rope, c_kv, k_rope = mla._latent_qkv(
            cfg, lp, x, safe_pos, None, kv_lat.dtype)
        with jax.named_scope("attn.latent"):
            out = mla._merge(
                mla._attend_pool(q_lat, q_rope, kv_lat, kv_rope, a,
                                 page_table, before, _scale(cfg), kernel),
                mla._attend_local(q_lat, q_rope, c_kv, k_rope, own,
                                  _scale(cfg)))
        return mla._latent_out(cfg, lp, out, x.dtype), \
            (*cache, (c_kv, k_rope))

    def finish(cache):
        with jax.named_scope("kv_carry"):
            c_new, r_new = (jnp.stack(x) for x in zip(*cache))
            return (mla._commit_chunk(kv_lat, c_new, flat_slots, page_slots),
                    mla._commit_chunk(kv_rope, r_new, flat_slots,
                                      page_slots))

    return attend, (), finish


def _latent_window(cfg: ModelConfig, interpret, mesh):
    """jamba.Attending.window over the latent pools, as
    mla.make_decode_window_fn's: read-only pools, the window's own (c,
    shared key) in buffers [n_attn, B, K, 1, *] merged in by
    online-softmax statistics, one llama.commit_window a pool."""
    A = len(cfg.attn_layer_ids)

    def begin(w):
        B, K = w.start.shape[0], w.k_steps
        return (jnp.zeros((A, B, K, 1, w.kv_k.shape[-1]), w.kv_k.dtype),
                jnp.zeros((A, B, K, 1, w.kv_v.shape[-1]), w.kv_v.dtype))

    def attend_of(w, i, pos):
        before = jnp.maximum(w.start, 0)
        safe_pos = jnp.maximum(pos, 0)[:, None]
        seen = ((jnp.arange(w.k_steps, dtype=jnp.int32)[None, :] <= i)
                & (w.start[:, None] >= 0))[:, None, :]      # [B, 1, K]

        def attend(a, x, cache):
            wc, wr = cache
            lp = {n: w.params[n][a] for n in LATENT_KEYS}
            q_lat, q_rope, c_kv, k_rope = mla._latent_qkv(
                cfg, lp, x, safe_pos, None, wc.dtype)
            wc_l = wc[a].at[:, i, 0].set(c_kv[:, 0])
            wr_l = wr[a].at[:, i, 0].set(k_rope[:, 0])
            with jax.named_scope("attn.latent"):
                out = mla._merge(
                    mla._attend_pool(q_lat, q_rope, w.kv_k, w.kv_v, a,
                                     w.page_table, before, _scale(cfg),
                                     interpret),
                    mla._attend_local(q_lat, q_rope, wc_l[:, :, 0],
                                      wr_l[:, :, 0], seen, _scale(cfg)))
            return (mla._latent_out(cfg, lp, out, x.dtype),
                    (wc.at[a].set(wc_l), wr.at[a].set(wr_l)))

        return attend

    def commit(w, bufs, pos):
        wc, wr = bufs
        return (llama.commit_window(w.kv_k, wc, w.page_table, w.start, pos),
                llama.commit_window(w.kv_v, wr, w.page_table, w.start, pos))

    return begin, attend_of, commit


LATENT = jamba.Attending(_latent_chunk, _latent_window)
BLOCKS = jamba.Blocks(KDA_KEYS, _kda, _ff, kda_step, WINDOW_COUNTS, LATENT,
                      kda_chunk)


# ----------------------------------------------------- jitted entry points


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """(prefill_step, decode_step): jamba.make_step_fns' programs on this
    family's blocks."""
    return jamba.make_step_fns(cfg, allow_pallas, mesh, blocks=BLOCKS)


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False):
    """jamba.make_decode_window_fn's fused window on this family's
    blocks."""
    return jamba.make_decode_window_fn(cfg, allow_pallas, max_top_k, mesh,
                                       pallas_interpret, blocks=BLOCKS)
