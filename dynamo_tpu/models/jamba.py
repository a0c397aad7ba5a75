"""Jamba: Mamba-1 mixers with attention every ``attn_layer_period`` layers
(AI21 Jamba family, ``model_type: jamba``), on the engine's paged step-fn
contract plus the thing this module brought and every module on its
layout has since: **per-sequence recurrent state that is not a KV page**.

Layer l: ``h += Mixer_l(rms_norm(h))`` then ``h += MLP(rms_norm(h))``
(SwiGLU, dense, every layer). ``Mixer_l`` attends where ``(l -
attn_layer_offset) % attn_layer_period == 0`` (GQA, no bias, **no
positional embedding of any kind**) and is a Mamba-1 mixer elsewhere:

    [x, z] = split(W_in u)
    x      = silu(causal depthwise conv1d(x; conv_w, b_conv))   kernel d_conv
    [dt_r, B, C] = split(W_x x)            each through its own RMSNorm
    dt     = softplus(W_dt dt_r + b_dt),   A = -exp(A_log)
    s_t    = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) outer B_t
    y_t    = s_t . C_t + d_skip * x_t
    out    = W_out(y_t * silu(z_t))

What a sequence carries between programs is ``s`` (float32, kept as
``[N, d_inner]``: d_inner on the TPU's 128 lanes, the published
``[d_inner, N]`` would pad 16 to 128) and the last ``d_conv - 1`` conv
inputs. The engine owns one pool of each (``init_state``), S slots, M
Mamba layers: the scan states ``[S, M, N, d_inner]`` float32, slot-major,
so a row's whole state is one contiguous 8.5 MB and the gather and
scatter by slot move whole slabs; and the conv tails ``[M, S, (d_conv -
1) * d_inner]`` in the model's dtype, LAYER-major, the oldest input
first along the last axis: a program gathers its rows' tails along axis
1 into ``[M, B, (d_conv - 1) * d_inner]``, where a layer's tails are one
contiguous block with the rows on the sublanes and the channels on the
lanes, which a layer-step reads and writes where it lies (no axis of 3,
6, 9 or 26 layers is ever tiled). The engine passes both through every
program with the rows' slot indices, as it passes the KV pools with page
tables. A prefill chunk gathers its few rows' state, carries it through
the layers and scatters it back, row by row in place; a decode step (the
fused window's, and decode_step) on a TPU leaves ``s`` in the pool: a
Pallas kernel reads each row's ``[N, d_inner]`` block where it lies and
writes it back there (ops/selective_scan.py), and only the conv tails (a
tenth of the bytes) are gathered and scattered once a program. A row
that does not advance (padding, frozen by a stop) writes back what it
read. A chunk that starts at position 0 starts from zeros, whatever the
slot held.

The Mamba layers are stacked on a leading axis and ``lax.scan``-ned in
their runs between the attending layers, so a program holds one Mamba
layer's trace per run, not 28 layers. KV pools hold the attending layers
only (``[n_attn, pages, KV, ps, hd]``) and go through llama.py's paged
attention: the page scatter, ``_attention``, and the read-only-pool
window attention with the Pallas decode kernel on a TPU.

The selective scan has two forms: a chunk of T tokens from a carried
state, plain XLA (``_ssm_chunk``: time blocks run side by side from
zero and are stitched by their entry states, so nothing of size ``[T,
d_inner, N]`` is ever held), and one token from a stored state: the
kernel on the pool where the attention kernels run (``llama.kernel_mode``),
``_ssm_step`` on gathered rows elsewhere. Scopes: ``ssm`` around the mixer with
``ssm.proj``, ``ssm.conv``, ``ssm.scan`` inside; ``attn``, ``mlp``,
``lm_head``, ``sample``, ``kv_carry`` as in llama.py.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig, hf_base
from .llama import (KVCacheSpec, Params, _at, _attention, _mlp,
                    _scatter_pages, _scatter_pages_paged, commit_window,
                    embed_tokens, kernel_mode, logits_at, prefill_logits,
                    rms_norm, window_attention)
from .window import Family, make_window
from ..ops.conv_step import conv_tail_step
from ..ops.selective_scan import selective_scan_step

State = Tuple[jax.Array, jax.Array]     # (ssm [S,M,N,di] f32, conv [M,S,(dc-1)*di])

MAMBA_KEYS = ("w_in", "conv_w", "b_conv", "w_x", "dt_norm", "ssm_b_norm",
              "ssm_c_norm", "w_dt", "b_dt", "A_log", "d_skip", "w_out")
SCAN_BLOCK = 32     # tokens per time block of the prefill scan


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``jamba`` config.json."""
    c = hf_base(cfg)
    if (cfg.get("num_experts") or 1) > 1:
        # every layer's MLP is dense here; the expert_layer_* keys would
        # select routed layers
        raise NotImplementedError(
            "jamba with num_experts > 1 is not supported (every layer's "
            "MLP is computed dense)")
    if cfg.get("sliding_window"):
        raise NotImplementedError(
            "jamba with sliding_window set is not supported (its "
            "attention layers attend to the whole context)")
    c.model_type = "jamba"
    c.mamba_d_state = cfg.get("mamba_d_state", 16)
    c.mamba_d_conv = cfg.get("mamba_d_conv", 4)
    c.mamba_expand = cfg.get("mamba_expand", 2)
    c.mamba_dt_rank = cfg.get("mamba_dt_rank") or -(-cfg["hidden_size"] // 16)
    c.attn_layer_period = cfg.get("attn_layer_period", 8)
    c.attn_layer_offset = cfg.get("attn_layer_offset", 4)
    c.rms_norm_eps = cfg.get("rms_norm_eps", 1e-6)
    return c


def segments(cfg: ModelConfig) -> List[tuple]:
    """The layer pattern as runs: ("mamba", first mamba index, first
    layer, count) and ("attn", attention index, layer), in layer order."""
    out: List[tuple] = []
    m = first = 0           # next Mamba index, first layer of the run
    # a run is cut where the layers' second halves change kind (dense
    # MLPs in the first first_k_dense_replace layers, experts after)
    cut = cfg.first_k_dense_replace
    for a, l in enumerate((*cfg.attn_layer_ids, cfg.num_layers)):
        for lo, hi in ((first, min(l, cut)), (max(first, cut), l)):
            if hi > lo:
                out.append(("mamba", m, lo, hi - lo))
                m += hi - lo
        if l < cfg.num_layers:
            out.append(("attn", a, l))
        first = l + 1
    return out


def num_mamba_layers(cfg: ModelConfig) -> int:
    return cfg.num_layers - len(cfg.attn_layer_ids)


# ------------------------------------------------------- params and pools


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    """K and V pools of the attending layers only."""
    shape = (len(cfg.attn_layer_ids), spec.num_pages, cfg.num_kv_heads,
             spec.page_size, cfg.head_dim_)
    dtype = dtype or cfg.jax_dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_state(cfg: ModelConfig, slots: int, dtype=None,
               layers: Optional[int] = None) -> State:
    """The recurrent-state pools for ``slots`` sequences (what declares
    to the engine that this module's sequences carry state beside pages):
    the scan states ``[S, M, N, d_inner]`` float32, slot-major, and the
    conv tails ``[M, S, (d_conv - 1) * d_inner]``, layer-major (a row's
    last d_conv - 1 inputs of a layer, oldest first, d_inner each).
    ``layers``: M where the Mamba layers are not Jamba's
    (models/phi4flash.py)."""
    M = layers or num_mamba_layers(cfg)
    N, di = cfg.mamba_d_state, cfg.mamba_d_inner
    return (jnp.zeros((slots, M, N, di), jnp.float32),
            jnp.zeros((M, slots, (cfg.mamba_d_conv - 1) * di),
                      dtype or cfg.jax_dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params; each kind of layer stacked on its own axis 0
    (MLP and pre-norms over all L layers, Mamba leaves over the M Mamba
    layers, attention leaves over the attending ones)."""
    dtype = dtype or cfg.jax_dtype
    D, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    M, A = num_mamba_layers(cfg), len(cfg.attn_layer_ids)
    di, N, R, dc = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                    cfg.mamba_d_conv)
    ks = iter(jax.random.split(key, 16))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    # the published Mamba init: dt between 1e-3 and 1e-1 through the
    # bias, A = -(1..N) per channel, skip of ones
    dt = jnp.exp(jax.random.uniform(next(ks), (M, di), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    p: Params = {
        "embed": w(V, D),
        "ln_mixer": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        "w_gate": w(L, D, I), "w_up": w(L, D, I), "w_down": w(L, I, D),
        "wq": w(A, D, H * hd), "wk": w(A, D, KV * hd),
        "wv": w(A, D, KV * hd), "wo": w(A, H * hd, D),
        "w_in": w(M, D, 2 * di),
        "conv_w": w(M, dc, di),
        "b_conv": jnp.zeros((M, di), dtype),
        "w_x": w(M, di, R + 2 * N),
        "dt_norm": jnp.ones((M, R), dtype),
        "ssm_b_norm": jnp.ones((M, N), dtype),
        "ssm_c_norm": jnp.ones((M, N), dtype),
        "w_dt": w(M, R, di),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
            (M, di, N)).astype(dtype),
        "d_skip": jnp.ones((M, di), dtype),
        "w_out": w(M, di, D),
    }
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p


# ----------------------------------------------------------- the mixers


def _ssm_step(s, dt_t, x_t, b_t, c_t, a_neg):
    """One token of the recurrence for every row. s [B, N, di] float32;
    dt_t, x_t [B, di]; b_t, c_t [B, N]; a_neg [N, di] = -exp(A_log).T.
    A row whose dt_t is 0 keeps its state (exp(0) = 1, nothing added)."""
    s = (jnp.exp(dt_t[:, None, :] * a_neg[None]) * s
         + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
    return s, jnp.sum(s * c_t[:, :, None], axis=1)


def _ssm_chunk(s0, dt, x, b, c, a_neg):
    """T tokens of the recurrence from the carried state s0 [B, N, di],
    in nb = T / tb time blocks. dt, x [B, T, di]; b, c [B, T, N] float32.
    A token-by-token loop is T dependent steps of a few microseconds of
    work each; here every block runs its tb tokens from a ZERO state side
    by side (tb steps over [B, nb, N, di]), the blocks' carried states
    follow from their totals (nb steps), and what a block's entry state
    adds to each of its tokens is one fused pass:

        s_t = P_t * s_entry + local_t,    P_t = exp(A * sum_{j<=t} dt_j)
        y_t = sum_n C_t * local_t  +  sum_n C_t * P_t * s_entry

    P_t <= 1 everywhere (nothing is divided), and nothing of size
    [T, N, di] is ever held: the local states are read out to y inside
    their loop. Returns (s after the last token, y [B, T, di])."""
    B, T, di = dt.shape
    N = b.shape[-1]
    tb = math.gcd(T, SCAN_BLOCK)
    nb = T // tb

    def blocks(v):          # [B, T, w] -> [tb, B, nb, w], time-major
        return jnp.moveaxis(v.reshape(B, nb, tb, v.shape[-1]), 2, 0)

    def token(carry, xs):
        local, cum = carry                      # [B, nb, N, di], [B, nb, di]
        dt_j, x_j, b_j, c_j = xs
        local = (jnp.exp(dt_j[:, :, None, :] * a_neg) * local
                 + (dt_j * x_j)[:, :, None, :] * b_j[..., None])
        cum = cum + dt_j
        return (local, cum), (jnp.sum(local * c_j[..., None], axis=2), cum)

    (local, cum_end), (y_local, cum) = lax.scan(
        token, (jnp.zeros((B, nb, N, di), jnp.float32),
                jnp.zeros((B, nb, di), jnp.float32)),
        (blocks(dt), blocks(x), blocks(b), blocks(c)))

    def block(s, xs):       # entry state of each block, then the next
        total, local_k = xs
        return total * s + local_k, s

    s, entry = lax.scan(
        block, s0,
        (jnp.moveaxis(jnp.exp(cum_end[:, :, None, :] * a_neg), 1, 0),
         jnp.moveaxis(local, 1, 0)))
    entry = jnp.moveaxis(entry, 0, 1)           # [B, nb, N, di]
    # [tb, B, nb, N, di] only inside the fusion that reduces it over N
    carried = jnp.sum(
        blocks(c)[..., None] * jnp.exp(cum[:, :, :, None, :] * a_neg)
        * entry[None], axis=3)
    y = jnp.moveaxis(y_local + carried, 0, 2).reshape(B, T, di)
    return s, y


def _causal_conv(mp, x, valid, tail, dc: int, scope: str = "ssm.conv",
                 tail_step=None):
    """silu(causal depthwise conv1d(x; conv_w, b_conv)) on a chunk x
    [B, T, C] float32 entered with the rows' last dc - 1 inputs ``tail``
    [B, (dc - 1) * C], oldest first, as the pool keeps them (no bias
    where the family has no ``b_conv`` leaf). Returns (the result [B, T,
    C], the next chunk's tail). Tail and chunk are laid end to end, every
    token's dc taps summed in float32, and each row's next tail cut where
    its valid tokens end. ``tail_step`` (T == 1, where the kernels run)
    is the same sum and the same next tail by ops/conv_step.py, bound to
    a layer by _stack: ``(tails, x [B, C], valid [B], w, bias) ->
    (tails, the sum before its SiLU)``; ``tail`` is then whatever it
    carries, every layer's tails [M, B, (dc - 1) * C], of which it
    advances its layer's where they lie."""
    f32 = jnp.float32
    B, T, C = x.shape
    with jax.named_scope(scope):
        cw = mp["conv_w"].astype(f32)                       # [dc, C]
        bias = mp["b_conv"].astype(f32) if "b_conv" in mp else 0.0
        if tail_step is not None:
            tail, pre = tail_step(tail, x[:, 0], valid[:, 0], cw,
                                  jnp.broadcast_to(bias, (C,)))
            return jax.nn.silu(pre)[:, None], tail
        xp = jnp.concatenate(
            [tail.reshape(B, dc - 1, C).astype(f32), x], axis=1)
        xc = jax.nn.silu(bias + sum(
            xp[:, k:k + T] * cw[k] for k in range(dc)))
        # the next chunk's tail: the dc - 1 inputs that end at the
        # row's last valid token (the old tail where it has none)
        n_valid = jnp.sum(valid, axis=1)
        tail = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
            row, n, dc - 1, 0))(xp, n_valid).astype(tail.dtype)
    return xc, tail.reshape(B, (dc - 1) * C)


def _mamba(cfg: ModelConfig, mp, u, valid, s, tail, step=_ssm_step,
           tail_step=None, hand_down: bool = False):
    """The Mamba-1 mixer on a chunk. u [B, T, D] (normed); valid [B, T]
    (a row's valid tokens lead); s [B, N, di] float32 and tail
    [B, (d_conv - 1) * di]: the rows' state on entry. Returns (out [B, T, D],
    s, tail) with the state after each row's last valid token. ``step``
    is the one-token recurrence (T == 1) with _ssm_step's operands and
    results, ``s`` being whatever it carries: the rows' states, or the
    pool they lie in (_stack); ``tail_step`` likewise the one-token
    advance of the conv tails (_causal_conv). dt_r, B and C go through
    Jamba's three inner RMSNorms where ``mp`` holds their leaves
    (``dt_norm``), and as they are where it does not (the published
    Mamba-1 mixer: models/phi4flash.py). ``hand_down``: the scan's
    output ``y`` [B, T, di] float32, before the gate and the output
    projection, is returned as a fourth result."""
    f32 = jnp.float32
    B, T, _ = u.shape
    di, N, R, dc = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                    cfg.mamba_d_conv)
    eps = cfg.rms_norm_eps

    def dot(a, w):
        """Operands in the weights' type, the result in float32 (the
        accumulator's own type): between the mixer's matmuls nothing is
        rounded to bf16. 28 layers deep, rounding x, z, dt, B, C and y
        at every layer is what the agreement with the float32 reference
        loses first (PERF.md, Findings PR 27)."""
        return jnp.dot(a.astype(w.dtype), w, preferred_element_type=f32)

    with jax.named_scope("ssm"):
        with jax.named_scope("ssm.proj"):
            x, z = jnp.split(dot(u, mp["w_in"]), 2, axis=-1)    # [B, T, di]
        xc, tail = _causal_conv(mp, x, valid, tail, dc,
                                tail_step=tail_step)
        with jax.named_scope("ssm.proj"):
            dt_r, b, c = jnp.split(dot(xc, mp["w_x"]), [R, R + N], axis=-1)
            if "dt_norm" in mp:
                dt_r = rms_norm(dt_r, mp["dt_norm"].astype(f32), eps)
                b = rms_norm(b, mp["ssm_b_norm"].astype(f32), eps)
                c = rms_norm(c, mp["ssm_c_norm"].astype(f32), eps)
            dt = jax.nn.softplus(dot(dt_r, mp["w_dt"])
                                 + mp["b_dt"].astype(f32))
            dt = jnp.where(valid[:, :, None], dt, 0.0)          # [B, T, di]
        with jax.named_scope("ssm.scan"):
            a_neg = -jnp.exp(mp["A_log"].astype(f32)).T         # [N, di]
            if T == 1:      # one token from a stored state
                s, y = step(s, dt[:, 0], xc[:, 0], b[:, 0], c[:, 0], a_neg)
                y = y[:, None]
            else:           # a chunk from a carried state, in time blocks
                s, y = _ssm_chunk(s, dt, xc, b, c, a_neg)
            y = y + mp["d_skip"].astype(f32) * xc
        with jax.named_scope("ssm.proj"):
            out = dot(y * jax.nn.silu(z), mp["w_out"])
    return (out, s, tail, y) if hand_down else (out, s, tail)


def _dense_ff(params: Params, cfg: ModelConfig, norm, h, l, valid,
              l0=None):
    """Jamba's second half of layer l (traced inside the run that starts
    at layer l0, a Python int): h + the dense SwiGLU MLP of norm(h), and
    nothing counted."""
    lp = _at(params, ("ln_mlp", "w_gate", "w_up", "w_down"), l)
    return h + _mlp(norm(h, lp["ln_mlp"]), lp["w_gate"], lp["w_up"],
                    lp["w_down"]), None


class Attending(NamedTuple):
    """The attending half of a family on this layout: the attending
    layers' attention over their pools (the module's ``init_kv_cache``
    shapes them) in a chunk and in the fused window. ``GQA`` below is
    Jamba's, Granite's and Solar Open 2's (K and V pages a KV head, no
    positions, llama.py's paged attention and kernels; an output gate
    where the params hold ``wg``: ``_gated``); models/kimi_linear.py
    supplies the latent one from models/mla.py's functions. ``a`` counts
    the attending layers; every ``attend(a, x, cache) -> (out [B, T, D],
    cache)`` is _stack's."""
    # (cfg, params, positions, kv_k, kv_v, page_table, flat_slots,
    #  page_slots, allow_pallas, mesh) -> (attend, the cache _stack
    #  carries, finish(cache) -> (kv_k, kv_v)): a prefill chunk or a K=1
    #  decode step over the pools
    chunk: Callable
    # (cfg, interpret, mesh) -> (begin(w) -> buffers, attend_of(w, i,
    #  pos) -> attend over the buffers, commit(w, buffers, pos) -> (kv_k,
    #  kv_v)): the window's read-only pools and its own tokens' buffers
    window: Callable


def _gqa_chunk(cfg: ModelConfig, params: Params, positions, kv_k, kv_v,
               page_table, flat_slots, page_slots, allow_pallas, mesh):
    def attend(a, x, cache):
        kv_k, kv_v = cache
        q, k, v = _qkv(cfg, params, a, x)
        if page_slots is not None:
            k_l = _scatter_pages_paged(kv_k[a], k, page_slots)
            v_l = _scatter_pages_paged(kv_v[a], v, page_slots)
        else:
            k_l = _scatter_pages(kv_k[a], k, flat_slots)
            v_l = _scatter_pages(kv_v[a], v, flat_slots)
        out = _attention(q, k_l, v_l, page_table, positions, cfg.attn_scale,
                         allow_pallas=allow_pallas, mesh=mesh)
        return (_gated(params, a, x, out) @ params["wo"][a],
                (kv_k.at[a].set(k_l), kv_v.at[a].set(v_l)))

    return attend, (kv_k, kv_v), lambda cache: cache


def _gqa_window(cfg: ModelConfig, interpret, mesh):
    KV, hd = cfg.num_kv_heads, cfg.head_dim_
    n_attn = len(cfg.attn_layer_ids)

    def begin(w):
        wk = jnp.zeros((n_attn, w.start.shape[0], w.k_steps, KV, hd),
                       w.kv_k.dtype)
        return wk, jnp.zeros_like(wk)

    def attend_of(w, i, pos):
        def attend(a, x, cache):
            wk, wv = cache
            q, k, v = _qkv(cfg, w.params, a, x)
            wk_l = wk[a].at[:, i].set(k[:, 0].astype(wk.dtype))
            wv_l = wv[a].at[:, i].set(v[:, 0].astype(wv.dtype))
            out = window_attention(q, w.kv_k, w.kv_v, a, w.page_table,
                                   w.start, wk_l, wv_l, i, cfg.attn_scale,
                                   interpret)
            return (_gated(w.params, a, x, out) @ w.params["wo"][a],
                    (wk.at[a].set(wk_l), wv.at[a].set(wv_l)))

        return attend

    def commit(w, bufs, pos):
        wk, wv = bufs
        return (commit_window(w.kv_k, wk, w.page_table, w.start, pos),
                commit_window(w.kv_v, wv, w.page_table, w.start, pos))

    return begin, attend_of, commit


GQA = Attending(_gqa_chunk, _gqa_window)


class Blocks(NamedTuple):
    """What a family of this layout supplies (runs of state-space mixers
    between attending layers, one pool of scan state ``[S, M, N, C]``
    float32 and one of conv tails ``[M, S, (d_conv - 1) * channels]``, by
    the module's ``init_state``);
    the layer loops, the pools' traffic and the window are this module's
    for all of them. models/granite.py is the second,
    models/kimi_linear.py the third, whose attending half is latent and
    whose prefill chunk has a kernel of its own (``chunk``),
    models/solar_open2.py the fourth: kimi_linear.py's mixer and second
    half beside ``GQA``; and models/nemotron_h.py the fifth, whose layer
    is ONE sub-block (a mixer, or a second half, alone): its own
    ``segments`` names, a run, where the second halves lie, and a run
    may have none."""
    keys: tuple             # the state-space mixer's leaves, stacked [M, ...]
    mixer: Callable         # _mamba's call form
    ff: Callable            # _dense_ff's call form: the layer's second half
    step: Callable          # selective_scan_step's call form: the kernel
    # names of what ``ff`` counts a layer (int32, one each, summed over
    # the layers and a window's steps and returned by the window before
    # the state: the engine adds them to stats()); none: ff returns None
    counts: tuple = ()
    attending: Attending = GQA
    # the scan of a chunk of T > 1 tokens as a kernel on the gathered
    # rows (ops/kda.py kda_chunk's call form; ``forward`` binds it to
    # ``mixer`` as ``chunk=`` where the kernels run); None: the mixer's
    # XLA form
    chunk: Optional[Callable] = None
    # the layer pattern as runs (``segments``' form). A family whose
    # layers do not all have both halves appends to a run where its
    # second halves start in THEIR stacks (``ln_mlp`` and what ``ff``
    # reads; None: the run's layers have no second half), its ``l`` then
    # counting the mixers' ``ln_mixer``; and names a second half that no
    # mixer precedes ("ff", its index)
    segments: Callable = segments


MAMBA1 = Blocks(MAMBA_KEYS, _mamba, _dense_ff, selective_scan_step)


def _stack(params: Params, cfg: ModelConfig, h, valid, ssm, conv, attend,
           cache, in_pool=None, blocks: Blocks = MAMBA1):
    """All layers on h [B, T, D]. conv [M, B, (dc-1)*di] is the ROWS' conv
    tails (gathered by the caller), layer-major: a layer's are one
    contiguous block, sliced out and written back a layer. ssm is their
    scan state: the rows' own, [B, M, N, di], sliced and updated
    likewise; or, with ``in_pool`` = (the rows' slots [B], the rows
    that start from zeros [B], the kernel's ``interpret`` flag) and T ==
    1, the POOL ``[S, M, N, di]`` itself, which each layer's kernel call
    reads and writes at ``[slots, m]`` and nowhere else: the loops carry
    the pool's buffer, never a copy of the rows; the tails are then
    advanced in the carried array too, a layer's block where it lies
    (ops/conv_step.py). ``attend(a, x, cache) ->
    (out, cache)`` is the attention mixer of attending layer a on the
    normed input: the caller owns how K/V are cached (pages for a chunk,
    the window buffer inside the fused window). Returns (the final norm
    of h, ssm, conv, cache, what the layers' second halves counted: None
    for blocks that count nothing)."""
    eps = cfg.rms_norm_eps
    res = cfg.residual_multiplier
    wdt = params["embed"].dtype
    # the residual stream is float32 and every block reads it through a
    # norm that hands the matmuls the weights' type: 56 additions deep,
    # a bf16 stream's rounding is the other half of what the agreement
    # loses (a [B, T, D] float32 array: nothing beside the weights)
    h = h.astype(jnp.float32)

    def norm(h, w):
        return rms_norm(h, w.astype(jnp.float32), eps).astype(wdt)

    tally = jnp.zeros(len(blocks.counts), jnp.int32) if blocks.counts \
        else None

    def mlp(h, l, tally, l0):
        h, counted = blocks.ff(params, cfg, norm, h, l, valid, l0)
        return h, tally if counted is None else tally + counted

    def add(h, out):        # a mixer's output onto the residual stream
        return h + (out if res == 1.0 else res * out)

    for seg in blocks.segments(cfg):
        if seg[0] == "ff":
            h, tally = mlp(h, seg[1], tally, seg[1])
            continue
        if seg[0] == "attn":
            _, a, l, *f = seg
            f = f[0] if f else l
            with jax.named_scope("attn"):
                x = norm(h, params["ln_mixer"][l])
                out, cache = attend(a, x, cache)
                h = add(h, out)
            if f is not None:
                h, tally = mlp(h, f, tally, f)
            continue
        _, m0, l0, count, *f0 = seg
        f0 = f0[0] if f0 else l0

        def layer(carry, i, m0=m0, l0=l0, f0=f0):
            h, ssm, conv, tally = carry
            m = m0 + i
            mp = _at(params, blocks.keys, m)
            x = norm(h, lax.dynamic_index_in_dim(
                params["ln_mixer"], l0 + i, 0, False))
            if in_pool is None:
                out, s, tail = blocks.mixer(
                    cfg, mp, x, valid,
                    lax.dynamic_index_in_dim(ssm, m, 1, False),
                    lax.dynamic_index_in_dim(conv, m, 0, False))
                ssm = lax.dynamic_update_index_in_dim(ssm, s, m, 1)
                conv = lax.dynamic_update_index_in_dim(conv, tail, m, 0)
            else:
                slots, fresh, interpret = in_pool
                out, ssm, conv = blocks.mixer(
                    cfg, mp, x, valid, ssm, conv,
                    lambda pool, *row: blocks.step(
                        pool, slots, m, *row, fresh, interpret=interpret),
                    tail_step=lambda tails, *row: conv_tail_step(
                        tails, m, *row, interpret=interpret))
            h = add(h, out)
            if f0 is not None:
                h, tally = mlp(h, f0 + i, tally, f0)
            return (h, ssm, conv, tally), None

        (h, ssm, conv, tally), _ = lax.scan(
            layer, (h, ssm, conv, tally), jnp.arange(count, dtype=jnp.int32))
    return norm(h, params["ln_final"]), ssm, conv, cache, tally


def _qkv(cfg: ModelConfig, params: Params, a: int, x):
    B, T, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    return ((x @ params["wq"][a]).reshape(B, T, H, hd),
            (x @ params["wk"][a]).reshape(B, T, KV, hd),
            (x @ params["wv"][a]).reshape(B, T, KV, hd))


def _gated(params: Params, a: int, x, out):
    """Attention's output [B, T, H, hd] as the output projection takes it,
    [B, T, H * hd]: as it is for a family without the leaf ``wg`` (Jamba,
    Granite: their programs hold nothing of this), and for one that has
    it (models/solar_open2.py) times ``sigmoid(x @ wg[a])``, an
    elementwise gate of the layer's normed input at full width, made and
    applied in float32 under the scope ``attn.gate``."""
    out = out.reshape(*x.shape[:2], -1)
    if "wg" not in params:
        return out
    with jax.named_scope("attn.gate"):
        gate = jax.nn.sigmoid(jnp.dot(x, params["wg"][a],
                                      preferred_element_type=jnp.float32))
        return (out.astype(jnp.float32) * gate).astype(x.dtype)


def _store_rows(pool, slots, rows):
    """The rows written back to their slots: a scatter along the pool's
    major axis, in place in a donated pool (row by row, a slab each)."""
    return pool.at[slots].set(rows.astype(pool.dtype))


def _store_tails(pool, slots, tails):
    """The rows' conv tails [M, B, W] written back to their slots of the
    layer-major pool [M, S, W]: _store_rows along axis 1. For this and
    for the gather the chip's compiler relays the pool slot-major and
    back (a row of bf16 shares its tiles' words with its neighbour):
    with the moves 4 x the bytes, once a program; a (layer, slot) pair
    an index, which needs no relayout, moves the rows one at a time and
    is 4 x slower still (tools/conv_step_timing.py; PERF.md, PR 55)."""
    return pool.at[:, slots].set(tails.astype(pool.dtype))


def forward(params: Params, cfg: ModelConfig, tokens, positions, kv_k, kv_v,
            page_table, flat_slots, state: State, state_slots,
            allow_pallas: bool = True, page_slots=None, mesh=None,
            blocks: Blocks = MAMBA1):
    """A chunk [B, T] for every row from its stored state (zeros where
    the chunk starts at position 0): prefill, and K=1 decode at T = 1.
    Arguments as llama.forward, plus the state pool and the rows' slots.
    Returns (hidden [B, T, D], kv_k, kv_v, state)."""
    valid = positions >= 0
    fresh = positions[:, 0] == 0
    conv = jnp.where(fresh[None, :, None], 0, state[1][:, state_slots])
    # one token from a stored state: the kernel advances it in the pool
    # where the attention kernels run (llama.kernel_mode); else the rows'
    # state is gathered and a chunk runs on it: the family's chunk kernel
    # where it has one and the kernels run, else the XLA step or chunk
    one = tokens.shape[1] == 1
    interpret = kernel_mode(allow_pallas) \
        if one or blocks.chunk is not None else None
    in_pool = None
    if one and interpret is not None:
        in_pool, ssm = (state_slots, fresh, interpret), state[0]
    else:
        ssm = jnp.where(fresh[:, None, None, None], 0.0,
                        state[0][state_slots])
        if interpret is not None:   # the mixer with the chunk kernel bound
            blocks = blocks._replace(mixer=partial(
                blocks.mixer,
                chunk=partial(blocks.chunk, interpret=interpret)))

    attend, cache, finish = blocks.attending.chunk(
        cfg, params, positions, kv_k, kv_v, page_table, flat_slots,
        page_slots, allow_pallas, mesh)
    h = embed_tokens(params, cfg, tokens)
    h, ssm, conv, cache, _ = _stack(params, cfg, h, valid, ssm, conv,
                                    attend, cache, in_pool, blocks)
    kv_k, kv_v = finish(cache)
    if in_pool is None:
        ssm = _store_rows(state[0], state_slots, ssm)
    return h, kv_k, kv_v, (ssm, _store_tails(state[1], state_slots, conv))


# ----------------------------------------------------- jitted entry points


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None,
                  blocks: Blocks = MAMBA1):
    """(prefill_step, decode_step) as llama.make_step_fns builds them, each
    with two more operands, the state pool (donated like the KV pools)
    and the rows' slots, and one more result, the pool."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None, state=None,
                     state_slots=None):
        h, kv_k, kv_v, state = forward(
            params, cfg, tokens, positions, kv_k, kv_v, page_table,
            flat_slots, state, state_slots, allow_pallas=allow_pallas,
            page_slots=page_slots, mesh=mesh, blocks=blocks)
        return prefill_logits(params, cfg, h, last_idx), kv_k, kv_v, state

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots, state=None, state_slots=None):
        h, kv_k, kv_v, state = forward(
            params, cfg, tokens[:, None], positions[:, None], kv_k, kv_v,
            page_table, flat_slots[:, None], state, state_slots,
            allow_pallas=allow_pallas, mesh=mesh, blocks=blocks)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)),
                kv_k, kv_v, state)

    return prefill_step, decode_step


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False,
                          blocks: Blocks = MAMBA1):
    """The fused K-step window of llama.make_decode_window_fn (read-only
    KV pool + window buffer + on-device carry: models/window.py's
    program) with the rows' recurrent state carried beside it, advanced
    by every step a row is active in: the conv tails gathered from the
    pool once and scattered back once; the scan state likewise on the XLA
    arm, and left in the pool where the kernels run, every step reading
    and writing the rows' blocks where they lie (and a layer's tails
    where they lie in the gathered array)."""
    # one choice for both kernels, the window's attention and the scan
    scan_interpret = kernel_mode(allow_pallas, pallas_interpret)
    use_pallas = scan_interpret is not None
    att_begin, attend_of, att_commit = blocks.attending.window(
        cfg, scan_interpret, mesh)

    def begin(w):
        att = att_begin(w)
        conv = w.state[1][:, w.state_slots]
        ssm = w.state[0] if use_pallas else w.state[0][w.state_slots]
        return att, ssm, conv

    def step(w, bufs, tok, pos, active, i):
        # a frozen or padding row flows through the matmuls; its state
        # does not move (dt masked to 0, conv tail kept) and its K/V
        # never commit
        att, ssm, conv = bufs
        B = tok.shape[0]
        in_pool = (w.state_slots, None, scan_interpret) if use_pallas \
            else None
        h = embed_tokens(w.params, cfg, tok)[:, None]
        h, ssm, conv, att, counted = _stack(
            w.params, cfg, h, active[:, None], ssm, conv,
            attend_of(w, i, pos), att, in_pool, blocks)
        return (logits_at(w.params, cfg, h, jnp.zeros(B, jnp.int32)),
                (att, ssm, conv), counted)

    def commit(w, bufs, pos):
        att, ssm, conv = bufs
        kv_k, kv_v = att_commit(w, att, pos)
        if not use_pallas:
            ssm = _store_rows(w.state[0], w.state_slots, ssm)
        return kv_k, kv_v, (ssm, _store_tails(w.state[1], w.state_slots,
                                              conv))

    return make_window(Family(begin, step, commit), max_top_k)
