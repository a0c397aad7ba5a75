"""Phi-4-mini-flash: a decoder-hybrid-decoder (SambaY, arXiv:2507.06607,
``model_type: phi4flash``) with differential attention (arXiv:2410.05258),
on the engine's paged step-fn contract with **a state slot AND pages of
two pools a sequence**: the first family whose record declares both
``init_state`` and ``pool_by_kind``.

Entry ``h = embed[token]``; layer l: ``h += Mixer_l(ln(h))`` then ``h +=
W_down(silu(W_gate x) * W_up x)``, ``x = ln(h)``; exit ``logits = ln(h)
@ head``. ``ln`` is a LayerNorm WITH a bias, at both places of every
layer and at the exit. **No positional embedding anywhere.** With
``half = num_layers // 2`` the kinds follow the layer's index alone
(``kinds``):

- l even, l <= half: **Mamba-1** (jamba._mamba WITHOUT Jamba's three
  inner norms). The last of them, layer ``half``, also hands down its
  scan output ``m_t = y_t`` (before the gate and the output projection).
- l odd, l <= half + 1: **differential attention**, causal; the last,
  layer ``half + 1``, sees every position, the others the last
  ``sliding_window``.
- l even, l > half: a **gated memory unit**: ``out = W_2(silu(W_1 u) *
  m)``, ``m`` of the same position. No recurrence, no cache.
- l odd, l > half + 1: **cross attention**: a query and an output
  projection and no key or value of its own; it attends, in the
  differential form, to layer ``half + 1``'s K and V.

**Differential attention.** Query heads are paired (2i, 2i + 1), KV heads
(2j, 2j + 1); pair i reads KV pair j = i // 2:

    a1 = softmax(q_{2i} k_{2j}^T / sqrt(hd)) V_j,    V_j = [v_{2j}, v_{2j+1}]
    a2 = softmax(q_{2i+1} k_{2j+1}^T / sqrt(hd)) V_j
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 l)
    o_i = rms_norm(a1 - lam a2; 2 hd) * (1 - lam0)

Two softmaxes, computed and subtracted. Both run as ONE grouped-query
attention at twice the head size (``_pair_q``): K and V are kept a KV
PAIR a head, ``K_j = [k_{2j}, k_{2j+1}]`` and ``V_j`` (a reshape of the
projection's output: [.., KV, hd] read as [.., KV / 2, 2 hd]), and query
head h is laid in the half of a 2 hd-wide vector that faces its key
(even heads the lower half, odd heads the upper, zeros in the other), so
that ``q'_h . K_{h // 4} = q_h . k`` of its own key and the four heads
(2i, 2i + 1 of the two pairs over KV pair j) are one group. Every
attention path the other families have then serves as it is (the page
scatter, the prefill and decode kernels at 128 lanes, the window pools,
the fused window's buffers); the zero halves double the scores' products
and nothing that is read from memory.

**What a sequence keeps.** Pages of ONE layer's K and V for its whole
context (``init_kv_cache``: ``[1, pages, KV / 2, ps, 2 hd]``: layer
``half + 1`` writes them, and it and every cross layer read them); the
window layers' K/V in the pool by kind (``init_window_kv_cache``, the
pages behind a row's window given back while it runs: llama.py's by-kind
tables); and a slot of jamba.py's state pools for its Mamba layers
(``init_state``). The gated memory units and the cross layers keep
nothing.

**The cross half runs on ONE position a row.** Layers past ``half + 1``
keep nothing a position, so their output at a position nobody samples
from is read by nobody: a program runs the self half over its ``[B,
T]`` chunk, gathers ``h`` and ``m`` at each row's ``last_idx`` and runs
the cross half on ``[B, 1]`` (a decode step is the same at T 1). Exact,
not an approximation (``forward(cross_all=True)`` is the other form,
which tests/test_phi4flash.py sets beside this one). A prefill program
in which no row ends its prompt runs none of it (``forward``'s
``head``): nobody samples from a chunk in the middle of a prompt.

The stack is three scanned runs: ``n_win`` x (Mamba-1, window attention),
one (Mamba-1 that hands down m, full attention) unrolled, ``n_cross`` x
(memory unit, cross attention); each layer's MLP with it. Leaves are
stacked a kind: norms and MLPs over all L layers, Mamba leaves over the
Mamba layers, ``wq`` / ``wo`` and the lambdas over all attending layers
(self first, then cross), ``wk`` / ``wv`` over the self-attending ones.
The residual stream and the state are float32 (jamba._stack's reasons).

Scopes: ``ssm`` > ``ssm.proj``, ``ssm.conv``, ``ssm.scan``; ``attn`` >
``attn.proj``, ``attn.window`` / ``attn.full`` / ``attn.cross`` (the
reads), ``attn.diff`` (the subtraction and the pair norm); ``gmu``;
``mlp``, ``lm_head``, ``sample``, ``kv_carry``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import jamba
from .config import ModelConfig, hf_base, refuser
from .jamba import State, _mamba, _store_rows, _store_tails
from .llama import (KVCacheSpec, Params, _at, _attention, _flat_pool, _mlp,
                    _relative, _write_layer_pages, commit_window,
                    embed_tokens, kernel_mode, layer_norm, logits_at,
                    no_logits, rms_norm, wanted, window_attention,
                    window_table_slots)  # noqa: F401
from .window import Family, make_window
from ..ops.conv_step import conv_tail_step
from ..ops.selective_scan import selective_scan_step

MAMBA_KEYS = ("w_in", "conv_w", "b_conv", "w_x", "w_dt", "b_dt", "A_log",
              "d_skip", "w_out")
MLP_KEYS = ("ln_mlp", "b_ln_mlp", "w_gate", "w_up", "w_down")
DIFF_KEYS = ("wq", "bq", "wo", "bo", "lq1", "lk1", "lq2", "lk2", "diff_norm")
KV_KEYS = ("wk", "bk", "wv", "bv")
GMU_KEYS = ("w_gmu_in", "w_gmu_out")
DIFF_NORM_EPS = 1e-5


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``phi4flash`` config.json, as published; the Mamba
    sizes the file lacks are the family's defaults (state 16, conv 4,
    expand 2, dt rank hidden / 16)."""
    refuse = refuser("phi4flash")
    L = cfg["num_hidden_layers"]
    if cfg.get("mb_per_layer", 2) != 2:
        refuse(f"mb_per_layer {cfg['mb_per_layer']}",
               "every second layer is a Mamba mixer or a memory unit")
    if L % 4:
        refuse(f"num_hidden_layers {L}",
               "the self half ends in a (Mamba, full attention) pair at "
               "layers L/2 and L/2 + 1, which needs L a multiple of 4")
    for key in ("mlp_bias", "lm_head_bias"):
        if cfg.get(key):
            refuse(f"{key} true", "the MLPs and the head are computed "
                   "without a bias")
    for key in ("embd_pdrop", "resid_pdrop", "attention_dropout"):
        if cfg.get(key):
            refuse(f"{key} {cfg[key]}", "no dropout is computed")
    if cfg["num_attention_heads"] % 4 or cfg["num_key_value_heads"] % 2 \
            or cfg["num_attention_heads"] % cfg["num_key_value_heads"]:
        refuse(f"{cfg['num_attention_heads']} / "
               f"{cfg['num_key_value_heads']} heads",
               "differential attention pairs the query and the KV heads")
    if not cfg.get("sliding_window"):
        refuse("no sliding_window", "the self half's attention layers "
               "but the last see a window")
    c = hf_base(cfg)
    c.model_type = "phi4flash"
    c.rms_norm_eps = cfg.get("layer_norm_eps", 1e-5)
    c.layer_norm = c.attn_bias = True
    c.hidden_act = cfg.get("hidden_act", "silu")
    if c.hidden_act != "silu":
        refuse(f"hidden_act {c.hidden_act}", "the MLP is SwiGLU")
    c.mamba_d_state = cfg.get("mamba_d_state", 16)
    c.mamba_d_conv = cfg.get("mamba_d_conv", 4)
    c.mamba_expand = cfg.get("mamba_expand", 2)
    c.mamba_dt_rank = cfg.get("mamba_dt_rank") or -(-cfg["hidden_size"] // 16)
    c.sliding_window = cfg["sliding_window"]
    c.layer_window = tuple(
        c.sliding_window if kind == "window" else None for kind in kinds(L))
    c.kv_pool_by_kind = True
    return c


def kinds(L: int) -> Tuple[str, ...]:
    """Each layer's mixer by the family's rule."""
    half = L // 2
    return tuple(
        ("mamba" if l <= half else "gmu") if l % 2 == 0 else
        ("window" if l < half + 1 else "full" if l == half + 1 else "cross")
        for l in range(L))


def counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(window layers = Mamba layers - 1, cross layers = memory units)."""
    return cfg.num_layers // 4, cfg.num_layers // 4 - 1


def lam0(l):
    """The differential form's fixed part at layer l (a traced or a
    Python number)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, jnp.float32))


# ------------------------------------------------------- params and pools


def _paired(cfg: ModelConfig) -> Tuple[int, int]:
    """(KV pairs, their width): the heads K and V are kept in."""
    return cfg.num_kv_heads // 2, 2 * cfg.head_dim_


def _pools(cfg: ModelConfig, layers: int, spec: KVCacheSpec, dtype):
    pairs, width = _paired(cfg)
    shape = (layers, spec.num_pages, pairs, spec.page_size, width)
    dtype = dtype or cfg.jax_dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    """K and V of the ONE layer that sees everything, a KV pair a head:
    every cross layer reads these pages and no other."""
    return _pools(cfg, 1, spec, dtype)


def init_window_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                         dtype=None) -> Tuple[jax.Array, jax.Array]:
    """The window layers' K and V pools, window layer a at index a."""
    return _pools(cfg, counts(cfg)[0], spec, dtype)


def init_state(cfg: ModelConfig, slots: int, dtype=None) -> State:
    """jamba.py's two pools over this family's Mamba layers."""
    return jamba.init_state(cfg, slots, dtype, layers=counts(cfg)[0] + 1)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params, each kind of layer stacked on its own axis 0;
    the Mamba leaves drawn as jamba.init_params draws them, the lambdas
    N(0, 0.1), norms ones, biases zeros."""
    dtype = dtype or cfg.jax_dtype
    D, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    n_win, n_cross = counts(cfg)
    M, S, A = n_win + 1, n_win + 1, n_win + 1 + n_cross
    di, N, R, dc = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                    cfg.mamba_d_conv)
    ks = iter(jax.random.split(key, 24))

    def w(*shape, scale=None):
        scale = scale or 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (M, di), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "embed": w(V, D),
        "ln_mixer": jnp.ones((L, D), dtype), "b_ln_mixer": zeros(L, D),
        "ln_mlp": jnp.ones((L, D), dtype), "b_ln_mlp": zeros(L, D),
        "ln_final": jnp.ones((D,), dtype), "b_ln_final": zeros(D),
        "w_gate": w(L, D, I), "w_up": w(L, D, I), "w_down": w(L, I, D),
        "w_in": w(M, D, 2 * di),
        "conv_w": w(M, dc, di),
        "b_conv": zeros(M, di),
        "w_x": w(M, di, R + 2 * N),
        "w_dt": w(M, R, di),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
            (M, di, N)).astype(dtype),
        "d_skip": jnp.ones((M, di), dtype),
        "w_out": w(M, di, D),
        "wq": w(A, D, H * hd), "bq": zeros(A, H * hd),
        "wk": w(S, D, KV * hd), "bk": zeros(S, KV * hd),
        "wv": w(S, D, KV * hd), "bv": zeros(S, KV * hd),
        "wo": w(A, H * hd, D), "bo": zeros(A, D),
        "lq1": w(A, hd, scale=0.1), "lk1": w(A, hd, scale=0.1),
        "lq2": w(A, hd, scale=0.1), "lk2": w(A, hd, scale=0.1),
        "diff_norm": jnp.ones((A, 2 * hd), dtype),
        "w_gmu_in": w(n_cross, D, di), "w_gmu_out": w(n_cross, di, D),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p


# ------------------------------------------------------------- the layers


def _ln(cfg: ModelConfig, h, w, b, dtype):
    """LayerNorm with a bias of the float32 stream, handed on in the
    weights' type."""
    return (layer_norm(h, w, cfg.rms_norm_eps)
            + b.astype(jnp.float32)).astype(dtype)


def _ff(cfg: ModelConfig, params: Params, h, l):
    """Layer l's second half onto the stream."""
    lp = _at(params, MLP_KEYS, l)
    x = _ln(cfg, h, lp["ln_mlp"], lp["b_ln_mlp"], lp["w_gate"].dtype)
    return h + _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _mixer_in(cfg: ModelConfig, params: Params, h, l):
    lp = _at(params, ("ln_mixer", "b_ln_mixer"), l)
    return _ln(cfg, h, lp["ln_mixer"], lp["b_ln_mixer"],
               params["embed"].dtype)


def _pair_q(q):
    """Query heads [B, T, H, hd] as the paired K faces them, [B, T, H,
    2 hd]: an even head in the lower half, an odd head in the upper, the
    other half zeros (the module's docstring)."""
    B, T, H, hd = q.shape
    q = q.reshape(B, T, H // 2, 2, hd)
    z = jnp.zeros_like(q[:, :, :, 0])
    return jnp.stack([jnp.concatenate([q[:, :, :, 0], z], axis=-1),
                      jnp.concatenate([z, q[:, :, :, 1]], axis=-1)],
                     axis=3).reshape(B, T, H, 2 * hd)


def _q(cfg: ModelConfig, ap, x):
    B, T, _ = x.shape
    with jax.named_scope("attn.proj"):
        q = x @ ap["wq"] + ap["bq"]
        return _pair_q(q.reshape(B, T, cfg.num_heads, cfg.head_dim_))


def _kv(cfg: ModelConfig, kp, x):
    """K and V of a self-attending layer, a KV pair a head."""
    B, T, _ = x.shape
    with jax.named_scope("attn.proj"):
        k, v = x @ kp["wk"] + kp["bk"], x @ kp["wv"] + kp["bv"]
        return (k.reshape(B, T, *_paired(cfg)),
                v.reshape(B, T, *_paired(cfg)))


def _diff_out(cfg: ModelConfig, ap, l, attn, dtype):
    """The paired heads' two attentions [B, T, H, 2 hd] into the layer's
    output [B, T, D]: a1 - lam a2 under the pair norm, times 1 - lam0,
    through W_o."""
    f32 = jnp.float32
    B, T, H, w = attn.shape
    with jax.named_scope("attn.diff"):
        a = attn.reshape(B, T, H // 2, 2, w).astype(f32)
        fixed = lam0(l)
        lam = (jnp.exp(jnp.sum(ap["lq1"].astype(f32) * ap["lk1"].astype(f32)))
               - jnp.exp(jnp.sum(ap["lq2"].astype(f32)
                                 * ap["lk2"].astype(f32))) + fixed)
        o = rms_norm(a[:, :, :, 0] - lam * a[:, :, :, 1],
                     ap["diff_norm"].astype(f32), DIFF_NORM_EPS)
        o = (o * (1.0 - fixed)).reshape(B, T, H // 2 * w).astype(dtype)
    with jax.named_scope("attn.proj"):
        return o @ ap["wo"] + ap["bo"]


def _mamba_layer(cfg: ModelConfig, params: Params, m, x, valid, ssm, conv,
                 in_pool, hand_down: bool = False):
    """Mamba layer m on the normed x, its state sliced out of the rows'
    (``ssm`` [B, M, N, di], ``conv`` [M, B, W]) and written back, or with
    ``in_pool`` (jamba._stack's) advanced where it lies in the pool.
    Returns (out, ssm, conv[, y])."""
    mp = _at(params, MAMBA_KEYS, m)
    if in_pool is None:
        out, s, tail, *y = _mamba(
            cfg, mp, x, valid, lax.dynamic_index_in_dim(ssm, m, 1, False),
            lax.dynamic_index_in_dim(conv, m, 0, False),
            hand_down=hand_down)
        return (out, lax.dynamic_update_index_in_dim(ssm, s, m, 1),
                lax.dynamic_update_index_in_dim(conv, tail, m, 0), *y)
    slots, fresh, interpret = in_pool
    return _mamba(
        cfg, mp, x, valid, ssm, conv,
        lambda pool, *row: selective_scan_step(
            pool, slots, m, *row, fresh, interpret=interpret),
        tail_step=lambda tails, *row: conv_tail_step(
            tails, m, *row, interpret=interpret),
        hand_down=hand_down)


def _self_half(cfg: ModelConfig, params: Params, h, valid, ssm, conv,
               in_pool, attend_window, cache, attend_full):
    """Layers 0 .. half + 1 on h [B, T, D] float32. ``attend_window(a,
    q, k, v, cache) -> (attn, cache)`` is window layer a's read of its
    pool and write of its K/V, ``attend_full(q, k, v) -> (attn, kept)``
    the full layer's; the callers own how K/V are kept (pages for a
    chunk, buffers inside the fused window). Returns (h, m, ssm, conv,
    cache, kept)."""
    n_win = counts(cfg)[0]
    dtype = params["embed"].dtype

    def attention(h, l, a, attend):
        with jax.named_scope("attn"):
            x = _mixer_in(cfg, params, h, l)
            ap = _at(params, DIFF_KEYS, a)
            k, v = _kv(cfg, _at(params, KV_KEYS, a), x)
            attn, kept = attend(_q(cfg, ap, x), k, v)
            return h + _diff_out(cfg, ap, l, attn, dtype), kept

    def pair(carry, p):
        h, ssm, conv, cache = carry
        out, ssm, conv = _mamba_layer(
            cfg, params, p, _mixer_in(cfg, params, h, 2 * p), valid, ssm,
            conv, in_pool)
        h = _ff(cfg, params, h + out, 2 * p)
        h, cache = attention(
            h, 2 * p + 1, p, lambda q, k, v: attend_window(p, q, k, v, cache))
        return (_ff(cfg, params, h, 2 * p + 1), ssm, conv, cache), None

    (h, ssm, conv, cache), _ = lax.scan(
        pair, (h, ssm, conv, cache), jnp.arange(n_win, dtype=jnp.int32))
    l = 2 * n_win
    out, ssm, conv, m = _mamba_layer(
        cfg, params, jnp.int32(n_win), _mixer_in(cfg, params, h, l), valid,
        ssm, conv, in_pool, hand_down=True)
    h = _ff(cfg, params, h + out, l)
    h, kept = attention(h, l + 1, n_win, attend_full)
    return _ff(cfg, params, h, l + 1), m, ssm, conv, cache, kept


def _cross_half(cfg: ModelConfig, params: Params, h, m, attend_cross):
    """The layers past half + 1 on h [B, T, D] float32 with the memory
    ``m`` [B, T, di] float32 of the same positions; ``attend_cross(q) ->
    attn`` reads the full layer's K and V. Returns the final norm of h."""
    n_win, n_cross = counts(cfg)
    dtype = params["embed"].dtype
    f32 = jnp.float32

    def pair(h, c):
        l = 2 * (n_win + 1 + c)
        with jax.named_scope("gmu"):
            gp = _at(params, GMU_KEYS, c)
            x = _mixer_in(cfg, params, h, l)
            g = jax.nn.silu(jnp.dot(x, gp["w_gmu_in"],
                                    preferred_element_type=f32)) * m
            out = jnp.dot(g.astype(dtype), gp["w_gmu_out"],
                          preferred_element_type=f32)
        h = _ff(cfg, params, h + out, l)
        with jax.named_scope("attn"):
            ap = _at(params, DIFF_KEYS, n_win + 1 + c)
            with jax.named_scope("attn.cross"):
                attn = attend_cross(
                    _q(cfg, ap, _mixer_in(cfg, params, h, l + 1)))
            h = h + _diff_out(cfg, ap, l + 1, attn, dtype)
        return _ff(cfg, params, h, l + 1), None

    h, _ = lax.scan(pair, h, jnp.arange(n_cross, dtype=jnp.int32))
    return _ln(cfg, h, params["ln_final"], params["b_ln_final"], dtype)


def forward(params: Params, cfg: ModelConfig, tokens, positions, kv_k, kv_v,
            page_table, flat_slots, last_idx, pools, places,
            allow_pallas: bool = True, page_slots=None, mesh=None,
            cross_all: bool = False, head=None):
    """A chunk [B, T] for every row from what it keeps: prefill, and K=1
    decode at T = 1. ``pools`` = (window K/V pools, state pools),
    ``places`` = ((table, base, write slots), state slots): the engine's
    ``_state_args``. The self half runs over the chunk; the cross half on
    each row's ``last_idx`` alone (every position with ``cross_all``).
    Returns (hidden [B, 1 | T, D], kv_k, kv_v, pools).

    With ``head`` (hidden [B, 1, D] -> logits: a prefill program's) the
    first result is the logits, and the gather, the cross half and the
    head run under ONE conditional on ``wanted(last_idx)``
    (llama.prefill_logits' rule: a negative entry is a row nobody samples
    from): a chunk that ends no prompt runs the self half, writes every
    pool and the state, and returns zeros."""
    (wk, wv), state = pools
    (wtable, wbase, wslots), state_slots = places
    B, T = tokens.shape
    scale = cfg.attn_scale
    W = cfg.sliding_window
    valid = positions >= 0
    fresh = positions[:, 0] == 0
    conv = jnp.where(fresh[None, :, None], 0, state[1][:, state_slots])
    interpret = kernel_mode(allow_pallas) if T == 1 else None
    in_pool = None
    if interpret is not None:
        in_pool, ssm = (state_slots, fresh, interpret), state[0]
    else:
        ssm = jnp.where(fresh[:, None, None, None], 0.0,
                        state[0][state_slots])
    paged = page_slots is not None
    slots = page_slots if paged else flat_slots
    rel_pos = _relative(positions, wbase)
    NPf, NPw = kv_k.shape[1], wk.shape[1]
    attention = partial(_attention, scale=scale, allow_pallas=allow_pallas,
                        mesh=mesh)

    def attend_window(a, q, k, v, cache):
        pk, pv = cache
        off = a * NPw
        pk = _write_layer_pages(pk, k, wslots, off, NPw, wtable, rel_pos,
                                paged)
        pv = _write_layer_pages(pv, v, wslots, off, NPw, wtable, rel_pos,
                                paged)
        with jax.named_scope("attn.window"):
            return attention(q, pk, pv, wtable + off, rel_pos, window=W,
                             is_sliding=True), (pk, pv)

    def attend_full(q, k, v):
        fk = _write_layer_pages(_flat_pool(kv_k), k, slots, 0, NPf,
                                page_table, positions, paged)
        fv = _write_layer_pages(_flat_pool(kv_v), v, slots, 0, NPf,
                                page_table, positions, paged)
        with jax.named_scope("attn.full"):
            return attention(q, fk, fv, page_table, positions), (fk, fv)

    h = embed_tokens(params, cfg, tokens).astype(jnp.float32)
    h, m, ssm, conv, (pk, pv), (fk, fv) = _self_half(
        cfg, params, h, valid, ssm, conv, in_pool, attend_window,
        (_flat_pool(wk), _flat_pool(wv)), attend_full)

    def suffix(idx):
        hc, mc, pos_c = h, m, positions
        if not cross_all:
            rows = jnp.arange(B)
            hc, mc = h[rows, idx][:, None], m[rows, idx][:, None]
            pos_c = positions[rows, idx][:, None]
        return _cross_half(cfg, params, hc, mc,
                           lambda q: attention(q, fk, fv, page_table, pos_c))

    if head is None:
        h = suffix(last_idx)
    else:
        assert not cross_all
        h = lax.cond(wanted(last_idx),
                     lambda: head(suffix(jnp.maximum(last_idx, 0))),
                     lambda: no_logits(cfg, B))
    if in_pool is None:
        ssm = _store_rows(state[0], state_slots, ssm)
    state = (ssm, _store_tails(state[1], state_slots, conv))
    return (h, fk.reshape(kv_k.shape), fv.reshape(kv_v.shape),
            ((pk.reshape(wk.shape), pv.reshape(wv.shape)), state))


# ----------------------------------------------------- jitted entry points


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """(prefill_step, decode_step) under the names and call forms of
    every family, with the two trailing operands a family with state or
    with pools by kind has, here pairs: ``state`` = (window pools, state
    pools), donated like the K/V pools and returned last, ``state_slots``
    = ((table, base, write slots), state slots)."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None, state=None,
                     state_slots=None):
        return forward(
            params, cfg, tokens, positions, kv_k, kv_v, page_table,
            flat_slots, last_idx, state, state_slots,
            allow_pallas=allow_pallas, page_slots=page_slots, mesh=mesh,
            head=lambda h: logits_at(params, cfg, h,
                                     jnp.zeros_like(last_idx)))

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots, state=None, state_slots=None):
        zero = jnp.zeros(tokens.shape[0], jnp.int32)
        h, kv_k, kv_v, state = forward(
            params, cfg, tokens[:, None], positions[:, None], kv_k, kv_v,
            page_table, flat_slots[:, None], zero, state, state_slots,
            allow_pallas=allow_pallas, mesh=mesh)
        return logits_at(params, cfg, h, zero), kv_k, kv_v, state

    return prefill_step, decode_step


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False):
    """The fused K-step window (models/window.py's program): every pool
    read-only in the steps; the window layers' and the full layer's K/V
    of the window's tokens in buffers, the full layer's ONE buffer read
    by the full layer and by every cross layer of every step; the rows'
    state carried as jamba.py's window carries it. The commit writes each
    kind's buffer to its own pool by whole pages and the state back."""
    mode = kernel_mode(allow_pallas, pallas_interpret, mesh)
    use_pallas = mode is not None
    n_win = counts(cfg)[0]
    scale, W = cfg.attn_scale, cfg.sliding_window

    def begin(w):
        state = w.state[1]
        B, wdt = w.start.shape[0], w.kv_k.dtype
        full = jnp.zeros((B, w.k_steps, *_paired(cfg)), wdt)
        win = jnp.zeros((n_win, *full.shape), wdt)
        return ((win, win), (full, full),
                state[0] if use_pallas else state[0][w.state_slots[1]],
                state[1][:, w.state_slots[1]])

    def step(w, bufs, tok, pos, active, i):
        (pk, pv), _ = w.state
        (wtable, wbase), slots = w.state_slots
        (wk, wv), (fk, fv), ssm, conv = bufs
        B = tok.shape[0]
        in_pool = (slots, None, mode) if use_pallas else None
        safe_pos = jnp.maximum(pos, 0)
        rel_start, rel_pos = _relative(w.start, wbase), safe_pos - wbase

        def put(buf, new):
            return buf.at[:, i].set(new[:, 0].astype(buf.dtype))

        def attend_window(a, q, k, v, cache):
            wk, wv = cache
            wk_l, wv_l = put(wk[a], k), put(wv[a], v)
            with jax.named_scope("attn.window"):
                attn = window_attention(
                    q, pk, pv, a, wtable, rel_start, wk_l, wv_l, i, scale,
                    mode, window=W, is_sliding=True, q_pos=rel_pos)
            return attn, (wk.at[a].set(wk_l), wv.at[a].set(wv_l))

        def read_full(q, fk, fv):
            return window_attention(q, w.kv_k, w.kv_v, 0, w.page_table,
                                    w.start, fk, fv, i, scale, mode)

        def attend_full(q, k, v):
            kept = put(fk, k), put(fv, v)
            with jax.named_scope("attn.full"):
                return read_full(q, *kept), kept

        h = embed_tokens(w.params, cfg, tok)[:, None].astype(jnp.float32)
        h, m, ssm, conv, win, full = _self_half(
            cfg, w.params, h, active[:, None], ssm, conv, in_pool,
            attend_window, (wk, wv), attend_full)
        h = _cross_half(cfg, w.params, h, m, lambda q: read_full(q, *full))
        return (logits_at(w.params, cfg, h, jnp.zeros(B, jnp.int32)),
                (win, full, ssm, conv), None)

    def commit(w, bufs, pos):
        (pk, pv), state = w.state
        (wtable, wbase), slots = w.state_slots
        (wk, wv), (fk, fv), ssm, conv = bufs
        start_w, pos_w = _relative(w.start, wbase), _relative(pos, wbase)
        if not use_pallas:
            ssm = _store_rows(state[0], slots, ssm)
        return (commit_window(w.kv_k, fk[None], w.page_table, w.start, pos),
                commit_window(w.kv_v, fv[None], w.page_table, w.start, pos),
                ((commit_window(pk, wk, wtable, start_w, pos_w),
                  commit_window(pv, wv, wtable, start_w, pos_w)),
                 (ssm, _store_tails(state[1], slots, conv))))

    return make_window(Family(begin, step, commit), max_top_k)
