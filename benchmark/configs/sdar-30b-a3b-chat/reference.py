"""The plain reference of SDAR-30B-A3B-Chat (``model_type: sdar_moe``):
what a Qwen3-MoE layer under a BLOCK mask computes, and how the family
generates text a block at a time by iterated unmasking, written from the
published description in straightforward ``jax.numpy`` and float32: no
paged cache, no kernel, no batching of requests, none of the program's
model code.

    logits = reference_logits(params, cfg, tokens)        # [T, V] float32
    toks, rows, counts = reference_generate(params, cfg, prompt, n)

**The layer.** With block length L = ``cfg.block_length`` and
``b(t) = t // L``: ``h += Wo . attn(rope(qnorm(Wq x)), rope(knorm(Wk x)),
Wv x)`` with ``x = rms_norm(h)``, scores scaled by 1/sqrt(head_dim),
position t attending to ``{ j : b(j) <= b(t) }`` (causal across blocks,
bidirectional inside one), KV heads shared by groups of H/KV query
heads, rotary embedding in the half-split (HF ``rotate_half``) layout;
then ``h += sum over the top-k experts e of softmax(top-k router
logits)_e * W_down_e(silu(W_gate_e x) * W_up_e x)`` (Qwen3-MoE's
``norm_topk_prob``: softmax over all experts, top-k, renormalise, is
the same function). Final RMSNorm, untied head. **No shift**: the logits
at position t are the distribution of the token AT t (the input there is
``cfg.mask_token_id``).

**The generation** of one block starting at s (the prompt's tail, if
any, fills its first positions and is final from the start): input
``[final tokens..., MASK...]`` at s .. s+L-1 after all earlier blocks.
Repeat: forward; at each masked position ``x0 = argmax(logits)`` and
``p = softmax(logits)[x0]``; make final, by ``cfg.remasking_strategy``:
``sequential`` the leftmost n masked, ``low_confidence_static`` the n
masked of highest p, ``low_confidence_dynamic`` every masked position
with ``p > cfg.confidence_threshold`` if those are at least n, else as
static; ``n = ceil(masked at block start / cfg.denoising_steps)``, the
last step of the schedule taking what is left. A final position never
changes again. A system that keeps K/V needs one more forward on the
final tokens to have the block's K/V (the commit forward); this
reference keeps nothing and only counts it.

**Departures, each noted where it is made.** (1) Every expert is
evaluated for every token and weighted by its routing weight (zero when
not chosen): exact, no dispatch to get wrong, and only ONE expert's
float32 matrices are alive (the engine's bf16 parameters stay on the
chip while this runs). (2) ``reference_logits`` computes the rows the
agreement check reads (row i = the logits at position i + 1 of a
forward over the whole blocks before it, then its block with the tokens
before i + 1 final and the mask from i + 1 to the block's end) for ALL
positions at once, by L + 1 streams of one length instead of one forward
a row: a context stream of the final tokens, and for each j < L a stream
in which every block's positions >= j are the mask; a stream's queries
attend to the CONTEXT stream's keys of earlier blocks and to their OWN
stream's keys of their own block. Block-causality makes the context's
K/V of a block the same function of the tokens in every forward that
holds the block whole, so each row is exactly the forward the docstring
above names; nothing is approximated and every row is exact, those
before ``len(prompt) - 1`` too (the harness reads from there on).
(3) Attention is computed in blocks of ``QUERY_BLOCK`` queries so that
the scores of a long sequence fit the chip beside the engine. (4)
Sequences are padded with the mask token to a multiple of ``PAD_TO``:
under the block mask a later position is invisible to an earlier one,
and the padding keeps the number of compiled shapes small.

Callers wrap the calls in ``jax.default_matmul_precision("highest")``
(``reference_generate`` does so itself): on a TPU a float32 matmul
otherwise runs in bf16 passes. The rule of agreement is the harness's
(``benchmark/reference.py judge``).
"""

from __future__ import annotations

import math
from functools import partial

QUERY_BLOCK = 512
PAD_TO = 16


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, inv_freq):
    """x: [S, P, heads, hd]; half-split rotation by position."""
    import jax.numpy as jnp

    P = x.shape[1]
    ang = jnp.arange(P, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, params, h, l):
    """One layer on h [S, P, D] float32: S streams of P positions, stream
    0 the context (module docstring, departure 2; S = 1 is a plain
    forward). ``l`` is a traced layer index so one compiled program
    serves every layer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    S, P, D = h.shape
    L = cfg.block_length
    eps = cfg.rms_norm_eps

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    x = _rms(h, at("ln_attn"), eps)
    q = (x @ at("wq").astype(f32)).reshape(S, P, H, hd)
    k = (x @ at("wk").astype(f32)).reshape(S, P, KV, hd)
    v = (x @ at("wv").astype(f32)).reshape(S, P, KV, hd)
    q = _rms(q, at("q_norm"), eps)
    k = _rms(k, at("k_norm"), eps)
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    q, k = _rope(q, inv), _rope(k, inv)
    g = H // KV
    k = jnp.repeat(k, g, axis=2)        # [S, P, H, hd]
    v = jnp.repeat(v, g, axis=2)
    # a query of any stream sees the CONTEXT's keys of earlier blocks and
    # its OWN stream's keys of its own block (for stream 0 the two
    # together are b(j) <= b(t))
    keys = jnp.concatenate(
        [jnp.broadcast_to(k[:1], k.shape), k], axis=1)   # [S, 2P, H, hd]
    vals = jnp.concatenate([jnp.broadcast_to(v[:1], v.shape), v], axis=1)
    pos = jnp.arange(P)
    outs = []
    for q0 in range(0, P, QUERY_BLOCK):     # departure 3
        t = pos[q0:q0 + QUERY_BLOCK]
        see = jnp.concatenate(
            [pos[None, :] // L < t[:, None] // L,
             pos[None, :] // L == t[:, None] // L], axis=1)
        s = jnp.einsum("sthd,sjhd->shtj", q[:, q0:q0 + QUERY_BLOCK],
                       keys) * (hd ** -0.5)
        s = jnp.where(see[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("shtj,sjhd->sthd",
                               jax.nn.softmax(s, axis=-1), vals))
    a = jnp.concatenate(outs, axis=1)
    h = h + a.reshape(S, P, H * hd) @ at("wo").astype(f32)

    x = _rms(h, at("ln_mlp"), eps).reshape(S * P, D)
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = x @ at("w_router").astype(f32)             # [S*P, E]
    top, idx = lax.top_k(logits, K)
    w = jax.nn.softmax(top, axis=-1)                    # over the chosen K
    route = jnp.zeros((S * P, E), f32).at[
        jnp.arange(S * P)[:, None], idx].set(w)         # 0 where not chosen

    def expert(acc, e):                                 # departure 1
        def w_(name):
            return lax.dynamic_index_in_dim(
                at(name), e, 0, False).astype(f32)

        y = (jax.nn.silu(x @ w_("w_gate")) * (x @ w_("w_up"))) @ w_("w_down")
        r = lax.dynamic_index_in_dim(route, e, 1, True)  # [S*P, 1]
        return acc + r * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return h + out.reshape(S, P, D)


def layer(cfg, params, h, l):
    """The one-layer program benchmark/rehearse.py compiles for its
    memory count: h [T, D], as the agreement check runs it (L + 1
    streams)."""
    import jax.numpy as jnp

    S = cfg.block_length + 1
    return _layer(cfg, params, jnp.broadcast_to(h[None], (S,) + h.shape),
                  l)[0]


def _check(cfg):
    if cfg.block_length < 1 or not cfg.qk_norm or cfg.num_experts <= 0:
        raise NotImplementedError(
            "this reference covers the sdar_moe shape: q/k norm, routed "
            "experts in every layer, a block mask")
    for flag in ("embed_scale", "norm_unit_offset", "sandwich_norms",
                 "sliding_window", "attn_logit_softcap", "attn_bias",
                 "final_logit_softcap", "rope_scaling", "is_mla",
                 "tie_word_embeddings"):
        if getattr(cfg, flag, None):
            raise NotImplementedError(
                f"the plain reference does not cover cfg.{flag}")


_PROGRAMS: dict = {}     # id(cfg) -> (cfg, layer, embed, head), jitted once


def _programs(cfg):
    """The three jitted programs of a configuration, made once: a new
    ``jax.jit`` object a call would trace and compile again a call, and
    ``reference_generate`` calls once a denoising step."""
    import jax
    import jax.numpy as jnp

    if id(cfg) not in _PROGRAMS:
        _check(cfg)

        @jax.jit
        def embed(params, toks):
            return params["embed"][toks].astype(jnp.float32)

        @jax.jit
        def head(params, h):
            x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
            return x @ params["lm_head"].astype(jnp.float32)

        _PROGRAMS[id(cfg)] = (cfg, jax.jit(partial(_layer, cfg)), embed,
                              head)
    return _PROGRAMS[id(cfg)][1:]


def _streams(params, cfg, toks):
    """Logits [S, P, V] of the streams ``toks`` [S, P] (int32)."""
    import jax.numpy as jnp

    one, embed, head = _programs(cfg)
    h = embed(params, jnp.asarray(toks, jnp.int32))
    for l in range(cfg.num_layers):
        h = one(params, h, jnp.int32(l))
    return head(params, h)


def _padded(cfg, n: int) -> int:
    """A length of whole blocks that holds n positions (departure 4)."""
    unit = math.lcm(cfg.block_length, PAD_TO)
    return -(-n // unit) * unit


def block_causal_logits(params, cfg, tokens):
    """[T, V]: one plain forward over ``tokens`` under the block mask;
    row t is the distribution of the token AT t."""
    import numpy as np

    T = len(tokens)
    toks = np.full((1, _padded(cfg, T)), cfg.mask_token_id, np.int32)
    toks[0, :T] = tokens
    return _streams(params, cfg, toks)[0, :T]


def reference_logits(params, cfg, tokens):
    """[T, V] float32 for one sequence of token ids: row i holds the
    logits at position i + 1 of a forward over ``tokens[:b(i+1) * L]``
    followed by block b(i+1) with ``tokens`` before i + 1 final and the
    mask token from i + 1 to the block's end: what a generation that had
    produced ``tokens`` saw when position i + 1 was the leftmost masked
    (under ``sequential`` unmasking, the forward that made it final).
    Every row is exact (module docstring, departure 2)."""
    import jax.numpy as jnp
    import numpy as np

    L = cfg.block_length
    T = len(tokens)
    P = _padded(cfg, T + 1)
    toks = np.full((L + 1, P), cfg.mask_token_id, np.int32)
    toks[0, :T] = tokens
    for j in range(L):      # stream 1 + j: positions >= j of a block masked
        keep = (np.arange(T) % L) < j
        toks[1 + j, :T][keep] = np.asarray(tokens, np.int32)[keep]
    logits = _streams(params, cfg, toks)                # [L + 1, P, V]
    t = np.arange(1, T + 1)
    return logits[jnp.asarray(1 + t % L), jnp.asarray(t)]


def reference_generate(params, cfg, prompt, n: int, strategy=None):
    """Greedy generation of n tokens after ``prompt`` by the loop of the
    module docstring, one plain forward a denoising step, nothing kept
    between forwards. Returns (tokens [n], rows [n, V]: for each token
    the logits of the forward that made its position final, counts: the
    blocks, denoising forwards, commit forwards a system with a cache
    would add, blocks that took fewer forwards than their schedule, and
    tokens generated past n inside the last block)."""
    import jax
    import numpy as np

    L, S = cfg.block_length, cfg.denoising_steps
    strategy = strategy or cfg.remasking_strategy
    seq = [int(t) for t in prompt]
    rows = {}
    counts = {"blocks": 0, "denoise_forwards": 0, "commit_forwards": 0,
              "early_exits": 0, "dropped_tokens": 0}
    target = len(seq) + n
    with jax.default_matmul_precision("highest"):
        while len(seq) < target:
            s = len(seq) // L * L
            block = seq[s:] + [None] * (L - len(seq) % L)
            first_new = len(seq) - s
            n0 = L - first_new
            per = -(-n0 // S)
            took = 0
            while any(t is None for t in block):
                x = seq[:s] + [cfg.mask_token_id if t is None else t
                               for t in block]
                logits = np.asarray(
                    block_causal_logits(params, cfg, x)[s:s + L],
                    np.float64)
                took += 1
                masked = [j for j in range(L) if block[j] is None]
                x0 = {j: int(np.argmax(logits[j])) for j in masked}
                p = {}
                for j in masked:
                    z = logits[j] - logits[j].max()
                    p[j] = float(np.exp(z[x0[j]]) / np.exp(z).sum())
                if took >= S:
                    pick = masked
                elif strategy == "sequential":
                    pick = masked[:per]
                else:
                    by_conf = sorted(masked, key=lambda j: (-p[j], j))
                    pick = by_conf[:per]
                    if strategy == "low_confidence_dynamic":
                        high = [j for j in masked
                                if p[j] > cfg.confidence_threshold]
                        if len(high) >= per:
                            pick = high
                    elif strategy != "low_confidence_static":
                        raise ValueError(strategy)
                for j in pick:
                    block[j] = x0[j]
                    rows[s + j] = logits[j].astype(np.float32)
            counts["blocks"] += 1
            counts["denoise_forwards"] += took
            counts["commit_forwards"] += 1
            counts["early_exits"] += took < -(-n0 // per)
            seq = seq[:s] + block
            counts["dropped_tokens"] += max(len(seq) - target, 0)
            seq = seq[:target]
    new = range(len(prompt), target)
    return (seq[len(prompt):], np.stack([rows[t] for t in new]), counts)
