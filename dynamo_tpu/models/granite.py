"""Granite 4.0-H: Mamba-2 mixers with attention where ``layer_types``
says so, and in EVERY layer routed experts beside one shared expert (IBM
Granite 4.0-H family, ``model_type: granitemoehybrid``), on jamba.py's
layout: the runs of state-space layers between the attending ones, the
pool of per-sequence recurrent state, the attention over pages with no
positional embedding and the fused window are that module's
(``jamba.Blocks``); this one supplies the mixer, the layer's second
half, the pools' shapes and the step kernel's call.

Entry ``h = embedding_multiplier * embed[token]``; layer l:
``h += r * Mixer_l(rms_norm(h))`` then ``h += r * (Routed(x) +
Shared(x))``, ``x = rms_norm(h)``, ``r = residual_multiplier``; exit
``logits = (rms_norm(h) @ head) / logits_scaling``. An attending layer
is GQA, causal, scores scaled by ``attention_multiplier`` (not
1/sqrt(head_dim)). A Mamba-2 mixer has H heads of P channels (H * P =
d_inner), a state of N a channel, and G groups (``mamba_n_groups``;
granite-4.0-h-small has ONE, models/nemotron_h.py runs this mixer with
eight): head h reads the B and C of group g = h // (H / G), and the gated
norm is over each group's d_inner / G channels:

    [z, xBC, dt] = split(W_in u)                   d_inner / d_inner + 2GN / H
    xBC  = silu(causal depthwise conv1d(xBC; conv_w, b_conv))
    [x, B, C] = split(xBC)                         d_inner / GN / GN
    dt   = softplus(dt + b_dt)   a head,           A = -exp(A_log) a head
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] (x_t[h] outer B_t[g])
    y_t[h] = S_t[h] C_t[g] + d_skip[h] x_t[h]
    out  = W_out(group_rms_norm(y * silu(z)) * ssm_norm)   the gate, THEN the norm

The number of groups is taken in Python at trace time: with one, every
function below lowers to what it lowered to before there were groups.

Routed: the router keeps its published width (``cfg.router_width``
outputs) and its top-k, the softmax is over the chosen logits; the
experts HELD here are ``[first_expert, first_expert + num_experts)``
and only the pairs routed to them are computed (llama.moe_experts'
``first``): the chip's share of a layer under expert parallelism. What
the absent experts would add is left out, and the partial sum plus the
shared expert is what goes on to the next layer.

**State.** A sequence carries, a Mamba-2 layer, the matrix state of its
heads (float32) and the last ``d_conv - 1`` inputs of the convolution
(``d_inner + 2GN`` channels). The pool keeps the matrix as ``[N, H * P]``:
the published ``[H, P, N]`` with N in front, so that what is H * P wide
a token (x, dt, y) lies along the lanes as the projections make and take
it and only B and C, N wide, cross to the sublanes
(ops/selective_scan.py ``ssd_step``); both pools then have jamba.py's
ranks and axes, ``[S, M, N, H * P]`` slot-major and the conv tails ``[M,
S, (d_conv - 1) * (d_inner + 2GN)]`` layer-major, oldest input first. At
granite-4.0-h-small's widths a row is 4 MiB a layer, 36 MiB at nine
layers: the module declares no snapshots, so a prefix hit counts as a
miss, as for Jamba.

**Two forms of the scan.** A chunk of T tokens runs the chunked form the
family publishes, in matrix products (``_ssd_chunk``): for a chunk of Q
tokens entered with S_in, ``a_t = dt_t A``, ``L_t = sum_{s<=t} a_s``,

    y_t   = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
            + exp(L_t) S_in C_t
    S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) dt_s (x_s outer B_s)

with ``C_t . B_s`` ONE [Q, Q] matrix a GROUP, which the group's heads
share (one for all heads where there is one group), and every exponent a
difference ``<= 0``: nothing overflows whatever is drawn. One token from
a stored state: the kernel on the pool where the attention kernels run,
``_ssd_step`` on gathered rows elsewhere. Scopes: ``ssm`` around the
mixer with ``ssm.proj``, ``ssm.conv``, ``ssm.scan``, ``ssm.norm``
inside; ``moe`` with ``moe.router``, ``moe.dispatch``, ``moe.experts``,
``moe.shared``; ``attn``, ``lm_head``, ``sample``, ``kv_carry`` as in
jamba.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import jamba
from .config import ModelConfig, held_experts, hf_base, refuser
from .jamba import (_at, _causal_conv, init_kv_cache,  # noqa: F401
                    num_mamba_layers, segments)
from .llama import (Params, _moe_use_blocked, held_first,  # noqa: F401
                    moe_experts, pairs_counted, rms_norm)
from ..ops.selective_scan import ssd_step

MAMBA2_KEYS = ("w_in", "conv_w", "b_conv", "b_dt", "A_log", "d_skip",
               "ssm_norm", "w_out")
EXPERT_KEYS = ("w_gate", "w_up", "w_down")
SHARED_KEYS = ("w_gate_s", "w_up_s", "w_down_s")
# what a decode window counts, a live row-step a layer: the (token,
# expert) pairs the router chose, and those whose expert is held here
WINDOW_COUNTS = ("moe_pairs_routed_total", "moe_pairs_held_total")


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``granitemoehybrid`` config.json
    (``num_local_experts``: config.held_experts)."""
    refuse = refuser("granitemoehybrid")
    c = hf_base(cfg)
    L = cfg["num_hidden_layers"]
    kinds = tuple(cfg["layer_types"][:L])
    odd = sorted(set(kinds) - {"mamba", "attention"})
    if odd or len(kinds) != L:
        refuse(f"layer_types {odd or len(kinds)}",
               "it must name num_hidden_layers layers, each mamba or "
               "attention")
    if cfg.get("position_embedding_type", "nope") != "nope":
        refuse(f"position_embedding_type "
               f"{cfg['position_embedding_type']!r}",
               "its attending layers apply no positional embedding")
    if cfg.get("mamba_proj_bias") or cfg.get("attention_bias"):
        refuse("mamba_proj_bias or attention_bias true",
               "the mixers' projections are computed without a bias")
    if not cfg.get("mamba_conv_bias", True):
        refuse("mamba_conv_bias false",
               "the causal convolution adds its bias leaf")
    heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    expand = cfg.get("mamba_expand", 2)
    if heads * d_head != expand * cfg["hidden_size"]:
        refuse(f"mamba_n_heads x mamba_d_head = {heads * d_head}",
               f"mamba_expand x hidden_size is "
               f"{expand * cfg['hidden_size']}, the mixer's one inner "
               f"width")
    groups = cfg.get("mamba_n_groups", 1)
    if groups < 1 or heads % groups:
        refuse(f"mamba_n_groups {groups}",
               f"a group is a whole number of the {heads} heads")
    c.num_experts, c.router_experts, c.first_expert = held_experts(
        cfg, "num_local_experts", "num_experts_per_tok", refuse)
    c.model_type = "granitemoehybrid"
    c.layer_types = kinds
    c.mamba_n_heads, c.mamba_d_head = heads, d_head
    c.mamba_d_state = cfg["mamba_d_state"]
    c.mamba_n_groups = groups
    c.mamba_d_conv = cfg.get("mamba_d_conv", 4)
    c.mamba_expand = expand
    c.mamba_chunk_size = cfg.get("mamba_chunk_size", 256)
    c.shared_intermediate_size = cfg.get("shared_intermediate_size", 0)
    c.embedding_multiplier = float(cfg.get("embedding_multiplier", 1))
    c.attention_multiplier = cfg.get("attention_multiplier")
    c.residual_multiplier = float(cfg.get("residual_multiplier", 1))
    c.logits_scaling = float(cfg.get("logits_scaling", 1))
    c.num_experts_per_tok = cfg["num_experts_per_tok"]
    c.tie_word_embeddings = cfg.get("tie_word_embeddings", True)
    return c


def conv_width(cfg: ModelConfig) -> int:
    """Channels the convolution runs over: x, and B and C a group."""
    return cfg.mamba_d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


# ------------------------------------------------------- params and pools


def init_state(cfg: ModelConfig, slots: int, dtype=None) -> jamba.State:
    """The recurrent-state pools for ``slots`` sequences: [S, M, N, H *
    P] float32, slot-major, and the conv tails [M, S, (d_conv - 1) *
    conv_width], layer-major as jamba.init_state's (what declares to the
    engine that this module's sequences carry state beside pages). M
    counts the layers ``layer_types`` names "mamba"."""
    M = cfg.layer_types.count("mamba")
    return (jnp.zeros((slots, M, cfg.mamba_d_state, cfg.mamba_d_inner),
                      jnp.float32),
            jnp.zeros((M, slots, (cfg.mamba_d_conv - 1) * conv_width(cfg)),
                      dtype or cfg.jax_dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params; each kind of leaf stacked on its own axis 0
    (experts, shared expert, router and pre-norms over all L layers,
    Mamba-2 leaves over the M Mamba layers, attention leaves over the
    attending ones). The expert stacks hold the experts HELD
    (``cfg.num_experts``); the router is ``cfg.router_width`` wide."""
    dtype = dtype or cfg.jax_dtype
    D, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    M, A = num_mamba_layers(cfg), len(cfg.attn_layer_ids)
    di, N, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    Hm, E, Is = cfg.mamba_n_heads, cfg.num_experts, \
        cfg.shared_intermediate_size
    ks = iter(jax.random.split(key, 20))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    # the published Mamba-2 init: dt between 1e-3 and 1e-1 through the
    # bias, A between 1 and 16 a head, skip of ones
    dt = jnp.exp(jax.random.uniform(next(ks), (M, Hm), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "embed": w(V, D),
        "ln_mixer": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        "wq": w(A, D, H * hd), "wk": w(A, D, KV * hd),
        "wv": w(A, D, KV * hd), "wo": w(A, H * hd, D),
        "w_in": w(M, D, di + conv_width(cfg) + Hm),
        "conv_w": w(M, dc, conv_width(cfg)),
        "b_conv": jnp.zeros((M, conv_width(cfg)), dtype),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            next(ks), (M, Hm), jnp.float32, 1.0, 16.0)).astype(dtype),
        "d_skip": jnp.ones((M, Hm), dtype),
        "ssm_norm": jnp.ones((M, di), dtype),
        "w_out": w(M, di, D),
        "w_router": w(L, D, cfg.router_width),
        "w_gate": w(L, E, D, I), "w_up": w(L, E, D, I),
        "w_down": w(L, E, I, D),
        "w_gate_s": w(L, D, Is), "w_up_s": w(L, D, Is),
        "w_down_s": w(L, Is, D),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p


# ----------------------------------------------------------- the mixer


def _ssd_step(s, dec, dtx, b, c):
    """One token of the recurrence for every row. s [B, N, C] float32, C
    = H * P; dec = exp(dt A) and dtx = dt * x [B, C] (a head's dt and
    decay repeated over its channels); b, c [B, N], or by group [B, G,
    N]: group g's are those of channels [g C / G, (g + 1) C / G). A row
    whose dt is 0 keeps its state (dec 1, nothing added)."""
    if b.ndim == 3:
        B, G, N = b.shape
        b, c = (jnp.swapaxes(v, 1, 2)[..., None] for v in (b, c))
        s = s.reshape(B, N, G, -1)                  # [B, N, G, C / G]
        s = (dec.reshape(B, 1, G, -1) * s + dtx.reshape(B, 1, G, -1) * b)
        return (s.reshape(B, N, -1),
                jnp.sum(s * c, axis=1).reshape(B, -1))
    s = dec[:, None, :] * s + dtx[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def _ssd_chunk(s0, dt, x, b, c, a_neg, chunk: int):
    """T tokens of the recurrence from the carried state s0 [B, N, H *
    P], in T / Q chunks of the matmul form (the module's docstring). dt
    [B, T, H] (0 at a token that does not count); x [B, T, H, P]; b, c
    [B, T, N], or by group [B, T, G, N] (head h reads group h // (H /
    G)); a_neg [H] = -exp(A_log), all float32. Returns (s after
    the last token, y [B, T, H, P]). Per chunk and row: one [Q, Q]
    product C . B (a group), one [Q, Q, H] table of decays (exponents
    are differences L_t - L_s with s <= t, never positive), and three
    products with the heads as a batch."""
    B, T, H = dt.shape
    P, N = x.shape[-1], b.shape[-1]
    G = b.shape[2] if b.ndim == 4 else None
    Q = math.gcd(T, chunk)
    nb = T // Q

    def chunks(v):          # [B, T, ...] -> [nb, B, Q, ...], chunk-major
        return jnp.moveaxis(v.reshape(B, nb, Q, *v.shape[2:]), 1, 0)

    later = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[:, :, None]

    def by_group(s, cum, dtx, b_c, c_c):
        """``one``'s three products with (group, head of the group) as
        the batch: b_c, c_c [B, Q, G, N]."""
        J = H // G
        s = s.reshape(B, N, G, J, P)
        decay = jnp.exp(jnp.where(later, cum[:, :, None] - cum[:, None],
                                  -jnp.inf)).reshape(B, Q, Q, G, J)
        w = jnp.einsum("btgn,bsgn->btsg", c_c, b_c)[..., None] * decay
        dtx = dtx.reshape(B, Q, G, J, P)
        y = jnp.einsum("btsgj,bsgjp->btgjp", w, dtx)
        y = y + (jnp.exp(cum).reshape(B, Q, G, J, 1)
                 * jnp.einsum("btgn,bngjp->btgjp", c_c, s))
        to_end = jnp.exp(cum[:, -1:] - cum).reshape(B, Q, G, J, 1)
        s = (jnp.exp(cum[:, -1]).reshape(B, 1, G, J, 1) * s
             + jnp.einsum("bsgn,bsgjp->bngjp", b_c, to_end * dtx))
        return s.reshape(B, N, H * P), y.reshape(B, Q, H, P)

    def one(s, xs):
        dt_c, x_c, b_c, c_c = xs
        cum = jnp.cumsum(dt_c * a_neg, axis=1)              # [B, Q, H]
        dtx = dt_c[..., None] * x_c                         # [B, Q, H, P]
        if G is not None:
            return by_group(s, cum, dtx, b_c, c_c)
        s = s.reshape(B, N, H, P)
        # inside the chunk: (C_t . B_s) exp(L_t - L_s) for s <= t
        w = (jnp.einsum("btn,bsn->bts", c_c, b_c)[..., None]
             * jnp.exp(jnp.where(later, cum[:, :, None] - cum[:, None],
                                 -jnp.inf)))                # [B, Q, Q, H]
        y = jnp.einsum("btsh,bshp->bthp", w, dtx)
        # what the entry state adds to every token
        y = y + (jnp.exp(cum)[..., None]
                 * jnp.einsum("btn,bnhp->bthp", c_c, s))
        # the state after the chunk's last token
        to_end = jnp.exp(cum[:, -1:] - cum)                 # [B, Q, H]
        s = (jnp.exp(cum[:, -1])[:, None, :, None] * s
             + jnp.einsum("bsn,bshp->bnhp", b_c, to_end[..., None] * dtx))
        return s.reshape(B, N, H * P), y

    s, y = lax.scan(one, s0, (chunks(dt), chunks(x), chunks(b), chunks(c)))
    return s, jnp.moveaxis(y, 0, 1).reshape(B, T, H, P)


def _mamba2(cfg: ModelConfig, mp, u, valid, s, tail, step=_ssd_step,
            tail_step=None):
    """The Mamba-2 mixer on a chunk: jamba._mamba's call form. u [B, T,
    D] (normed); valid [B, T] (a row's valid tokens lead); s [B, N, H *
    P] float32 and tail [B, (d_conv - 1) * conv_width]: the rows' state
    on entry. Returns (out [B, T, D], s, tail) with the state after each
    row's last valid token. ``step`` is the one-token recurrence (T ==
    1) with _ssd_step's operands and results, ``s`` being whatever it
    carries: the rows' states, or the pool they lie in; ``tail_step``
    likewise the one-token advance of the conv tails
    (jamba._causal_conv)."""
    f32 = jnp.float32
    B, T, _ = u.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    G = cfg.mamba_n_groups
    di = H * P

    def dot(a, w):
        # operands in the weights' type, the result in float32: between
        # the mixer's matmuls nothing is rounded (jamba._mamba)
        return jnp.dot(a.astype(w.dtype), w, preferred_element_type=f32)

    with jax.named_scope("ssm"):
        with jax.named_scope("ssm.proj"):
            z, xbc, dt = jnp.split(dot(u, mp["w_in"]),
                                   [di, 2 * di + 2 * G * N], axis=-1)
            dt = jax.nn.softplus(dt + mp["b_dt"].astype(f32))
            dt = jnp.where(valid[:, :, None], dt, 0.0)          # [B, T, H]
        xbc, tail = _causal_conv(mp, xbc, valid, tail, cfg.mamba_d_conv,
                                 tail_step=tail_step)
        x, b, c = jnp.split(xbc, [di, di + G * N], axis=-1)
        if G > 1:       # B and C a group: [B, T, G, N]
            b, c = b.reshape(B, T, G, N), c.reshape(B, T, G, N)
        with jax.named_scope("ssm.scan"):
            a_neg = -jnp.exp(mp["A_log"].astype(f32))           # [H]
            if T == 1:      # one token from a stored state
                dt_c = jnp.repeat(dt[:, 0], P, axis=-1)         # [B, di]
                dec = jnp.repeat(jnp.exp(dt[:, 0] * a_neg), P, axis=-1)
                s, y = step(s, dec, dt_c * x[:, 0], b[:, 0], c[:, 0])
                y = y[:, None]
            else:           # a chunk from a carried state, by matmuls
                s, y = _ssd_chunk(s, dt, x.reshape(B, T, H, P), b, c,
                                  a_neg, cfg.mamba_chunk_size)
                y = y.reshape(B, T, di)
            y = y + jnp.repeat(mp["d_skip"].astype(f32), P) * x
        with jax.named_scope("ssm.norm"):
            g = y * jax.nn.silu(z)
            w = mp["ssm_norm"].astype(f32)
            if G > 1:   # the RMS over each group's di / G channels
                g = rms_norm(g.reshape(B, T, G, di // G),
                             w.reshape(G, di // G),
                             cfg.rms_norm_eps).reshape(B, T, di)
            else:
                g = rms_norm(g, w, cfg.rms_norm_eps)
        with jax.named_scope("ssm.proj"):
            out = dot(g, mp["w_out"])
    return out, s, tail


# ------------------------------------------------- the layer's second half


def _moe_ff(params: Params, cfg: ModelConfig, norm, h, l, valid, l0=None):
    """(h + r * (routed experts held here + the shared expert) of
    norm(h), WINDOW_COUNTS of this layer), layer l (traced inside a
    run): jamba._dense_ff's call form."""
    x = norm(h, lax.dynamic_index_in_dim(params["ln_mlp"], l, 0, False))
    B, T, _ = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    first = held_first(cfg)
    with jax.named_scope("moe"):
        with jax.named_scope("moe.router"):
            # the router keeps its published width and its top-k; the
            # softmax is over the chosen logits
            logits = (x @ lax.dynamic_index_in_dim(
                params["w_router"], l, 0, False)).astype(jnp.float32)
            weights, idx = lax.top_k(logits, k)
            weights = jax.nn.softmax(weights, axis=-1)
            counted = pairs_counted(cfg, idx, valid)
        # the sorted form reads w[layer, expert] from the whole stacks,
        # the dense form one layer's (llama._moe_use_blocked: the rule)
        if _moe_use_blocked(None, B * T, E, k):
            routed = moe_experts(x, weights, idx,
                                 *(params[n] for n in EXPERT_KEYS), True,
                                 live=valid, layer=l, out_dtype=x.dtype,
                                 first=first, width=cfg.router_width)
        else:
            lp = _at(params, EXPERT_KEYS, l)
            routed = moe_experts(x, weights, idx, lp["w_gate"], lp["w_up"],
                                 lp["w_down"], False, out_dtype=x.dtype,
                                 first=first)
        with jax.named_scope("moe.shared"):
            sp = _at(params, SHARED_KEYS, l)
            shared = (jax.nn.silu(x @ sp["w_gate_s"])
                      * (x @ sp["w_up_s"])) @ sp["w_down_s"]
    out = routed.astype(jnp.float32) + shared.astype(jnp.float32)
    return h + cfg.residual_multiplier * out, counted


BLOCKS = jamba.Blocks(MAMBA2_KEYS, _mamba2, _moe_ff, ssd_step,
                      WINDOW_COUNTS)


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
