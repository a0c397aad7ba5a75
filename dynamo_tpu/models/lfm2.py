"""LFM2-MoE: gated short convolutions with attention every few layers and
routed experts (Liquid AI LFM2 family, ``model_type: lfm2_moe``), on the
engine's paged step-fn contract with per-sequence recurrent state, as
jamba.py, and one thing jamba.py cannot have: **a state small enough to
be snapshotted by the page, so that a prefix hit hands over pages AND
state**.

Layer l, with ``u = rms_norm(h; ln_op_l)`` and ``v = rms_norm(h; ln_ffn_l)``:
``h += Op_l(u)`` then ``h += FF_l(v)``.

``Op_l`` where ``layer_types[l] == "conv"`` (no activation, no bias):

    [B, C, x] = split3(W_in u)                 three blocks of D
    z_t = B_t * x_t
    c_t = sum_{j<K} conv_w[j] * z_{t-(K-1)+j}  depthwise, causal, K taps
    Op  = W_out (C_t * c_t)

and GQA attention where it is ``"full_attention"`` (per-head RMS norm of
q and k, RoPE, no bias). ``FF_l`` is a dense SwiGLU for the first
``num_dense_layers`` layers and routed experts after them: the gate is
mla.py's (sigmoid scores, selection by score + bias, the unbiased scores
of the chosen renormalised), the execution llama.py's ``moe_experts``
(sorted by the live (token, expert) pairs in a prefill, dense over the
experts in a decode window); neither is copied here.

**State.** What a sequence carries between programs is the last ``K - 1``
gated inputs ``z`` of every conv layer: ``(K - 1) * D`` values a layer,
kept in the weights' type (``z`` is rounded to it where it is made, so a
chunk boundary rounds nothing that the middle of a chunk does not).
``init_state`` declares the engine's pool of it by SLOT, ``[S, W]`` with
``W = conv layers * (K - 1) * D``, and ``init_state_snapshots`` a second
pool by PAGE id, ``[pages, W]``: a program that writes the last token of a
page (a prefill chunk, a window step, a decode step) writes the row's
state after that token to the page's row, in the same program as the
page's K/V, and a row admitted on a prefix hit reads the last hit page's
row in its first chunk (``state_src``, -1: none). The snapshot shares the
page's lifetime in PageManager: nothing else tracks it.

**KV pools** hold the attending layers only. Heads narrower than the
TPU's 128 lanes are packed side by side: ``[A, pages, KV / pack, ps,
hd * pack]`` with ``pack = 128 / hd``. A pool whose minor dim is 64 is
relayouted around every program that touches it (PERF.md, Findings
PR 31) and the decode kernel's fast form cannot slice it (PR 32); packed,
the kernel sees ``KV / pack`` heads of 128: a query is placed in its KV
head's lanes (zeros in the others), and the same lanes of the output are
taken. The bytes read are the true ones; the dot products are ``pack``
times as wide, which a memory-bound kernel does not feel.

Scopes: ``conv`` around the operator with ``conv.proj`` and ``conv.mix``
inside; ``state.snapshot`` around the restore after a hit and the writes
of page tails; ``attn``, ``moe`` (``moe.router``, ``moe.experts`` /
``moe.dispatch``), ``mlp``, ``kv_carry``, ``lm_head``, ``sample`` as the
other modules.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig, hf_base
from .llama import (KVCacheSpec, Params, _at, _attention, _mlp,
                    _moe_use_blocked, _qk_headnorm, _scatter_pages,
                    _scatter_pages_paged, apply_rope, commit_window,
                    embed_tokens, kernel_mode, logits_at, moe_experts,
                    prefill_logits, rms_norm, rope_freqs, window_attention)
from .mla import _deepseek_gate
from .window import Family, make_window

State = Tuple[jax.Array, jax.Array]     # (by slot [S, W], by page [pages, W])

CONV_KEYS = ("w_in", "conv_w", "w_out")
DENSE_KEYS = ("w_gate_d", "w_up_d", "w_down_d")
EXPERT_KEYS = ("w_gate_e", "w_up_e", "w_down_e")
_LANES = 128


def read_config(cfg: dict) -> ModelConfig:
    """The keys of an ``lfm2_moe`` config.json. A cut in depth keeps the
    published ``layer_types`` whole: the first ``num_hidden_layers``
    entries are the layers that run."""
    c = hf_base(cfg)
    kinds = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    odd = sorted(set(kinds) - {"conv", "full_attention"})
    if odd or len(kinds) != cfg["num_hidden_layers"]:
        raise NotImplementedError(
            "lfm2_moe: layer_types must name num_hidden_layers layers, "
            f"each conv or full_attention (got {odd or len(kinds)})")
    if cfg.get("conv_bias"):
        raise NotImplementedError(
            "lfm2_moe with conv_bias true is not supported (the short "
            "convolution is computed without a bias)")
    if not (cfg.get("use_expert_bias", True)
            and cfg.get("norm_topk_prob", True)):
        raise NotImplementedError(
            "lfm2_moe without use_expert_bias or norm_topk_prob is not "
            "supported (the gate selects by score + bias and renormalises "
            "the chosen scores)")
    rope = cfg.get("rope_parameters") or {}
    c.model_type = "lfm2_moe"
    c.layer_types = kinds
    c.conv_l_cache = cfg.get("conv_L_cache", 3)
    c.num_dense_layers = cfg.get("num_dense_layers", 0)
    c.rms_norm_eps = cfg.get("norm_eps", 1e-5)
    c.rope_theta = rope.get("rope_theta", cfg.get("rope_theta", 1000000.0))
    c.qk_norm = True
    c.num_experts = cfg.get("num_experts", 0)
    c.num_experts_per_tok = cfg.get("num_experts_per_tok", 4)
    c.moe_intermediate_size = cfg.get("moe_intermediate_size")
    # sigmoid scores, selection by score + bias, the unbiased scores of
    # the chosen renormalised: DeepSeek-V3's gate without groups
    # (models/mla.py _deepseek_gate)
    c.moe_router = "deepseek_v3"
    c.norm_topk_prob = True
    c.moe_renorm_eps = 1e-6
    c.routed_scaling_factor = cfg.get("routed_scaling_factor", 1.0)
    c.tie_word_embeddings = cfg.get("tie_word_embeddings", True)
    return c


def segments(cfg: ModelConfig) -> List[tuple]:
    """The layer pattern as runs: ("conv", first conv index, first layer,
    count) and ("attn", attention index, layer), in layer order. A run of
    conv layers ends at an attending layer and where the dense MLPs give
    way to experts, so one run has one kind of FF."""
    out: List[tuple] = []
    m = first = 0           # next conv index, first layer of the run
    for a, l in enumerate((*cfg.attn_layer_ids, cfg.num_layers)):
        cut = cfg.num_dense_layers
        for lo, hi in ((first, min(l, cut)), (max(first, cut), l)):
            if hi > lo:
                out.append(("conv", m, lo, hi - lo))
                m += hi - lo
        if l < cfg.num_layers:
            out.append(("attn", a, l))
        first = l + 1
    return out


def num_conv_layers(cfg: ModelConfig) -> int:
    return cfg.num_layers - len(cfg.attn_layer_ids)


def state_width(cfg: ModelConfig) -> int:
    """Values of one sequence's state: (K - 1) * D a conv layer."""
    return num_conv_layers(cfg) * (cfg.conv_l_cache - 1) * cfg.hidden_size


def kv_pack(cfg: ModelConfig) -> int:
    """KV heads stored side by side in one 128-lane row."""
    hd, pack = cfg.head_dim_, max(_LANES // cfg.head_dim_, 1)
    return pack if hd * pack == _LANES and cfg.num_kv_heads % pack == 0 else 1


# ------------------------------------------------------- params and pools


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    """K and V pools of the attending layers only, heads packed."""
    pack = kv_pack(cfg)
    shape = (len(cfg.attn_layer_ids), spec.num_pages,
             cfg.num_kv_heads // pack, spec.page_size, cfg.head_dim_ * pack)
    dtype = dtype or cfg.jax_dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_state(cfg: ModelConfig, slots: int, dtype=None) -> tuple:
    """The state pool by slot: what declares to the engine that this
    module's sequences carry state beside pages."""
    return (jnp.zeros((slots, state_width(cfg)), dtype or cfg.jax_dtype),)


def init_state_snapshots(cfg: ModelConfig, spec: KVCacheSpec,
                         dtype=None) -> jax.Array:
    """The state pool by page id: what declares that a page's end can be
    snapshotted, so the engine leaves the prefix cache on. The programs
    take it as the last member of ``state``."""
    return jnp.zeros((spec.num_pages, state_width(cfg)),
                     dtype or cfg.jax_dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params; each kind of layer stacked on its own axis 0
    (pre-norms over all L layers, conv leaves over the conv layers,
    attention leaves over the attending ones, the dense MLPs over the
    first num_dense_layers, router and experts over the rest)."""
    dtype = dtype or cfg.jax_dtype
    D, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    Mc, A = num_conv_layers(cfg), len(cfg.attn_layer_ids)
    Ld = min(cfg.num_dense_layers, L)
    Le, E = L - Ld, cfg.num_experts
    I, Ie = cfg.intermediate_size, cfg.moe_intermediate_size
    ks = iter(jax.random.split(key, 24))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    p: Params = {
        "embed": w(V, D),
        "ln_op": jnp.ones((L, D), dtype),
        "ln_ffn": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        "w_in": w(Mc, D, 3 * D), "conv_w": w(Mc, cfg.conv_l_cache, D),
        "w_out": w(Mc, D, D),
        "wq": w(A, D, H * hd), "wk": w(A, D, KV * hd),
        "wv": w(A, D, KV * hd), "wo": w(A, H * hd, D),
        "q_norm": jnp.ones((A, hd), dtype),
        "k_norm": jnp.ones((A, hd), dtype),
        "w_gate_d": w(Ld, D, I), "w_up_d": w(Ld, D, I),
        "w_down_d": w(Ld, I, D),
    }
    if Le > 0 and E > 0:
        p.update({
            "w_router": w(Le, D, E),
            "router_bias": jnp.zeros((Le, E), dtype),
            "w_gate_e": w(Le, E, D, Ie), "w_up_e": w(Le, E, D, Ie),
            "w_down_e": w(Le, E, Ie, D),
        })
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w(D, V)
    return p


# ------------------------------------------------------------ the layers


def _gated_input(b, x, dtype):
    """z = B * x in the state's type, from the start: what a later
    program reads back from a slot or a page is what this one used."""
    return (b * x).astype(dtype)


def _short_conv(cfg: ModelConfig, cp, u, valid, tail, ends):
    """The gated short convolution on a chunk. u [B, T, D] (normed);
    valid [B, T] (a row's valid tokens lead); tail [B, K - 1, D]: the
    rows' last K - 1 gated inputs on entry; ends [B, S] or None: chunk
    indices of tokens whose state is wanted too (a page's last token).
    Returns (out [B, T, D] float32, the tail after each row's last valid
    token, the tails after the tokens at ``ends`` [B, S, K - 1, D])."""
    f32 = jnp.float32
    B, T, _ = u.shape
    K = cfg.conv_l_cache
    with jax.named_scope("conv"):
        with jax.named_scope("conv.proj"):
            b, c, x = jnp.split(jnp.dot(u, cp["w_in"],
                                        preferred_element_type=f32),
                                3, axis=-1)
        with jax.named_scope("conv.mix"):
            z = _gated_input(b, x, tail.dtype)
            zp = jnp.concatenate([tail, z], axis=1)     # [B, T + K - 1, D]
            cw = cp["conv_w"].astype(f32)               # [K, D]
            y = c * sum(zp[:, j:j + T].astype(f32) * cw[j]
                        for j in range(K))
            # zp[n : n + K - 1] are the K - 1 inputs ending at chunk
            # token n - 1: the old tail where a row has no valid token
            tail = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
                row, n, K - 1, 0))(zp, jnp.sum(valid, axis=1))
            at_ends = None
            if ends is not None:
                idx = jnp.clip(ends[:, :, None] + 1
                               + jnp.arange(K - 1, dtype=jnp.int32),
                               0, T + K - 2)            # [B, S, K - 1]
                at_ends = jax.vmap(lambda row, i: row[i])(
                    zp, idx.reshape(B, -1)).reshape(*idx.shape, -1)
        with jax.named_scope("conv.proj"):
            out = jnp.dot(y.astype(u.dtype), cp["w_out"],
                          preferred_element_type=f32)
    return out, tail, at_ends


def _stack(params: Params, cfg: ModelConfig, h, valid, conv, attend, cache,
           ends=None, mesh=None):
    """All layers on h [B, T, D]. conv [B, Mc, K - 1, D] is the ROWS'
    state (gathered by the caller), updated layer by layer.
    ``attend(a, x, cache) -> (out, cache)`` is attending layer a on the
    normed input: the caller owns how K/V are cached. With ``ends`` [B,
    S] the state after those chunk tokens comes back too, [B, S, Mc,
    K - 1, D] (None otherwise)."""
    eps = cfg.rms_norm_eps
    B, T, _ = h.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    wdt = params["embed"].dtype
    # a float32 residual stream, read through norms that hand the matmuls
    # the weights' type (jamba.py; PERF.md, Findings PR 27)
    h = h.astype(jnp.float32)
    # the sorted form reads w[layer, expert] from the whole stacks; the
    # dense form takes one layer's (llama._moe_use_blocked has the rule)
    blocked = E > 0 and _moe_use_blocked(mesh, B * T, E, k)
    snaps = None if ends is None else jnp.zeros(
        (B, ends.shape[1], *conv.shape[1:]), conv.dtype)

    def norm(h, w):
        return rms_norm(h, w.astype(jnp.float32), eps).astype(wdt)

    def ff(h, l):
        """h + FF_l(norm(h)); l may be traced inside a run, whose layers
        are all dense or all routed (segments)."""
        x = norm(h, lax.dynamic_index_in_dim(params["ln_ffn"], l, 0, False))
        return h + ff_kind(x, l)

    def dense(x, l):
        lp = _at(params, DENSE_KEYS, l)
        return _mlp(x, lp["w_gate_d"], lp["w_up_d"], lp["w_down_d"])

    def routed(x, l):
        e = l - cfg.num_dense_layers
        with jax.named_scope("moe"):
            with jax.named_scope("moe.router"):
                w, idx = _deepseek_gate(
                    x.astype(jnp.float32),
                    lax.dynamic_index_in_dim(params["w_router"], e, 0, False),
                    lax.dynamic_index_in_dim(params["router_bias"], e, 0,
                                             False), cfg)
            if blocked:
                return moe_experts(x, w, idx, *(params[name] for name in
                                                EXPERT_KEYS), True,
                                   live=valid, layer=e)
            lp = _at(params, EXPERT_KEYS, e)
            return moe_experts(x, w, idx, lp["w_gate_e"], lp["w_up_e"],
                               lp["w_down_e"], False)

    for seg in segments(cfg):
        ff_kind = dense if seg[2] < cfg.num_dense_layers else routed
        if seg[0] == "attn":
            _, a, l = seg
            with jax.named_scope("attn"):
                out, cache = attend(a, norm(h, params["ln_op"][l]), cache)
                h = h + out
            h = ff(h, l)
            continue
        _, m0, l0, count = seg

        def layer(carry, i, m0=m0, l0=l0):
            h, conv, snaps = carry
            m = m0 + i
            x = norm(h, lax.dynamic_index_in_dim(params["ln_op"], l0 + i, 0,
                                                 False))
            out, tail, at_ends = _short_conv(
                cfg, _at(params, CONV_KEYS, m), x, valid,
                lax.dynamic_index_in_dim(conv, m, 1, False), ends)
            conv = lax.dynamic_update_index_in_dim(conv, tail, m, 1)
            if snaps is not None:
                snaps = lax.dynamic_update_index_in_dim(snaps, at_ends, m, 2)
            return (ff(h + out, l0 + i), conv, snaps), None

        (h, conv, snaps), _ = lax.scan(layer, (h, conv, snaps),
                                       jnp.arange(count, dtype=jnp.int32))
    return norm(h, params["ln_final"]), conv, snaps, cache


def _qkv(cfg: ModelConfig, params: Params, a: int, x, positions, inv_freq):
    """q, k, v of attending layer a on x [B, T, D], head-normed and
    rotated, in the pools' packed form: q [B, T, H, hd * pack] with each
    head in its KV head's lanes, k and v [B, T, KV / pack, hd * pack]."""
    B, T, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    pack = kv_pack(cfg)
    q = (x @ params["wq"][a]).reshape(B, T, H, hd)
    k = (x @ params["wk"][a]).reshape(B, T, KV, hd)
    v = (x @ params["wv"][a]).reshape(B, T, KV, hd)
    q, k = _qk_headnorm(q, k, {"q_norm": params["q_norm"][a],
                               "k_norm": params["k_norm"][a]}, cfg)
    pos = jnp.maximum(positions, 0)
    q, k = apply_rope(q, pos, inv_freq), apply_rope(k, pos, inv_freq)
    if pack > 1:
        q = (q[..., None, :] * _lanes(cfg, q.dtype)[:, :, None]).reshape(
            B, T, H, pack * hd)
    return (q, k.reshape(B, T, KV // pack, pack * hd),
            v.reshape(B, T, KV // pack, pack * hd))


def _lanes(cfg: ModelConfig, dtype):
    """[H, pack] one-hot: which of the pack lane blocks of a packed KV
    row holds query head h's KV head."""
    kv_head = jnp.arange(cfg.num_heads) // (cfg.num_heads // cfg.num_kv_heads)
    return jax.nn.one_hot(kv_head % kv_pack(cfg), kv_pack(cfg), dtype=dtype)


def _attn_out(cfg: ModelConfig, params: Params, a: int, out):
    """The output projection of packed attention output [B, T, H, hd *
    pack]: each head's own lanes, then wo."""
    B, T, H, _ = out.shape
    pack = kv_pack(cfg)
    if pack > 1:
        out = jnp.sum(out.reshape(B, T, H, pack, -1)
                      * _lanes(cfg, out.dtype)[:, :, None], axis=3)
    return out.reshape(B, T, -1) @ params["wo"][a]


def _rows(state: State, slots, cfg: ModelConfig):
    """The rows' state out of the pool by slot: [B, Mc, K - 1, D]."""
    return state[0][slots].reshape(len(slots), num_conv_layers(cfg),
                                   cfg.conv_l_cache - 1, cfg.hidden_size)


def forward(params: Params, cfg: ModelConfig, tokens, positions, kv_k, kv_v,
            page_table, flat_slots, state: State, state_slots,
            state_src=None, allow_pallas: bool = True, page_slots=None,
            mesh=None):
    """A chunk [B, T] for every row: prefill, and K=1 decode at T = 1.
    Arguments as jamba.forward; ``state`` is (by slot, by page) and
    ``state_src`` [B] the page whose snapshot a row starts from (-1: from
    its slot; zeros where the chunk starts at position 0, whatever the
    slot held). Every page whose last token the chunk writes gets the
    row's state after that token. Returns (hidden [B, T, D], kv_k, kv_v,
    state)."""
    by_slot, by_page = state
    B, T = tokens.shape
    A, NP, _, ps, _ = kv_k.shape
    P = page_table.shape[1]
    valid = positions >= 0
    start = jnp.maximum(positions[:, 0], 0)
    conv = _rows(state, state_slots, cfg)
    with jax.named_scope("state.snapshot"):
        if state_src is not None:
            conv = jnp.where(
                (state_src >= 0)[:, None, None, None],
                by_page[jnp.clip(state_src, 0, NP - 1)].reshape(conv.shape),
                conv)
    conv = jnp.where((positions[:, 0] == 0)[:, None, None, None], 0, conv)
    # chunk indices of the tokens that end a page: at most one more than
    # T / ps of them where a chunk does not start on a page
    ends = ((ps - 1 - start % ps)[:, None]
            + ps * jnp.arange(T // ps + 1, dtype=jnp.int32))      # [B, S]
    inv_freq = rope_freqs(cfg)
    # the pools seen as [A * pages, ...]: a layer's pages are written and
    # gathered along the major axis, and no layer is sliced out
    flat = (kv_k.reshape(A * NP, *kv_k.shape[2:]),
            kv_v.reshape(A * NP, *kv_v.shape[2:]))

    def attend(a, x, cache):
        fk, fv = cache
        q, k, v = _qkv(cfg, params, a, x, positions, inv_freq)
        if page_slots is not None:
            dst = jnp.where((page_slots >= 0) & (page_slots < NP),
                            page_slots + a * NP, A * NP)
            fk = _scatter_pages_paged(fk, k, dst)
            fv = _scatter_pages_paged(fv, v, dst)
        else:
            dst = jnp.where((flat_slots >= 0) & (flat_slots < NP * ps),
                            flat_slots + a * NP * ps, A * NP * ps)
            fk = _scatter_pages(fk, k, dst)
            fv = _scatter_pages(fv, v, dst)
        out = _attention(q, fk, fv, page_table + a * NP, positions,
                         cfg.attn_scale, allow_pallas=allow_pallas,
                         mesh=mesh)
        return _attn_out(cfg, params, a, out), (fk, fv)

    h = embed_tokens(params, cfg, tokens)
    h, conv, snaps, (fk, fv) = _stack(params, cfg, h, valid, conv, attend,
                                      flat, ends=ends, mesh=mesh)
    with jax.named_scope("state.snapshot"):
        col = (start[:, None] + ends) // ps
        page = jnp.take_along_axis(page_table, jnp.minimum(col, P - 1),
                                   axis=1)
        page = jnp.where((ends < jnp.sum(valid, axis=1)[:, None])
                         & (col < P), page, NP)         # NP: dropped
        by_page = by_page.at[page.reshape(-1)].set(
            snaps.reshape(page.size, -1), mode="drop")
    by_slot = by_slot.at[state_slots].set(conv.reshape(B, -1))
    return (h, fk.reshape(kv_k.shape), fv.reshape(kv_v.shape),
            (by_slot, by_page))


# ----------------------------------------------------- jitted entry points


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """(prefill_step, decode_step) as jamba.make_step_fns builds them;
    prefill_step takes one operand more, ``state_src``."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots=None, state=None,
                     state_slots=None, state_src=None):
        h, kv_k, kv_v, state = forward(
            params, cfg, tokens, positions, kv_k, kv_v, page_table,
            flat_slots, state, state_slots, state_src,
            allow_pallas=allow_pallas, page_slots=page_slots, mesh=mesh)
        return prefill_logits(params, cfg, h, last_idx), kv_k, kv_v, state

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "state"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots, state=None, state_slots=None):
        h, kv_k, kv_v, state = forward(
            params, cfg, tokens[:, None], positions[:, None], kv_k, kv_v,
            page_table, flat_slots[:, None], state, state_slots,
            allow_pallas=allow_pallas, mesh=mesh)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)),
                kv_k, kv_v, state)

    return prefill_step, decode_step


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False):
    """The fused K-step window of llama.make_decode_window_fn (read-only
    KV pool + window buffer + on-device carry: models/window.py's
    program) with the rows' state (48 KB a row) carried beside it as
    jamba's conv tails are: gathered once, advanced by every step a row
    is active in, scattered back once. A row that fills a page inside the
    window leaves its state after it in the page's snapshot (at most one
    page a row: k_steps <= page size)."""
    n_attn = len(cfg.attn_layer_ids)
    inv_freq = rope_freqs(cfg)
    mode = kernel_mode(allow_pallas, pallas_interpret)

    def begin(w):
        _, NP, KVp, ps, hdp = w.kv_k.shape
        B = w.start.shape[0]
        assert w.k_steps <= ps, "a window may fill one page a row at most"
        wk = jnp.zeros((n_attn, B, w.k_steps, KVp, hdp), w.kv_k.dtype)
        wv = jnp.zeros_like(wk)
        conv = _rows(w.state, w.state_slots, cfg)
        # the state after the token that filled a row's page, and the page
        # (NP: none, dropped)
        return (wk, wv, conv, jnp.zeros_like(conv),
                jnp.full((B,), NP, jnp.int32))

    def step(w, bufs, tok, pos, active, i):
        # a frozen or padding row flows through the matmuls; its state
        # does not move and its K/V never commit
        wk, wv, conv, snap, snap_page = bufs
        B = tok.shape[0]
        ps, P = w.kv_k.shape[3], w.page_table.shape[1]

        def attend(a, x, cache):
            wk, wv = cache
            q, k, v = _qkv(cfg, w.params, a, x, pos[:, None], inv_freq)
            wk_l = wk[a].at[:, i].set(k[:, 0].astype(wk.dtype))
            wv_l = wv[a].at[:, i].set(v[:, 0].astype(wv.dtype))
            out = window_attention(q, w.kv_k, w.kv_v, a, w.page_table,
                                   w.start, wk_l, wv_l, i, cfg.attn_scale,
                                   mode)
            return (_attn_out(cfg, w.params, a, out),
                    (wk.at[a].set(wk_l), wv.at[a].set(wv_l)))

        h = embed_tokens(w.params, cfg, tok)[:, None]
        h, conv, _, (wk, wv) = _stack(
            w.params, cfg, h, active[:, None], conv, attend, (wk, wv))
        logits = logits_at(w.params, cfg, h, jnp.zeros(B, jnp.int32))
        with jax.named_scope("state.snapshot"):
            fills = active & ((pos + 1) % ps == 0)
            col = jnp.clip(pos // ps, 0, P - 1)
            snap = jnp.where(fills[:, None, None, None], conv, snap)
            snap_page = jnp.where(
                fills, jnp.take_along_axis(w.page_table, col[:, None],
                                           axis=1)[:, 0], snap_page)
        return logits, (wk, wv, conv, snap, snap_page), None

    def commit(w, bufs, pos):
        wk, wv, conv = bufs[:3]
        by_slot, by_page = w.state
        return (commit_window(w.kv_k, wk, w.page_table, w.start, pos),
                commit_window(w.kv_v, wv, w.page_table, w.start, pos),
                (by_slot.at[w.state_slots].set(
                    conv.reshape(len(w.state_slots), -1)), by_page))

    def settle(w, bufs, state):
        snap, snap_page = bufs[3:]
        with jax.named_scope("state.snapshot"):
            by_page = state[1].at[snap_page].set(
                snap.reshape(len(snap_page), -1), mode="drop")
        return state[0], by_page

    return make_window(Family(begin, step, commit, settle), max_top_k)
