"""The plain reference: what a GQA transformer with top-k-softmax routed
experts computes, written from the published description in
straightforward ``jax.numpy`` and float32 — no paged cache, no kernel,
no batching, none of the program's model code.

    logits = reference_logits(params, cfg, tokens)      # [T, V] float32

Layer: h += Wo . attn(rope(qk_norm(Wq x)), rope(qk_norm(Wk x)), Wv x)
with x = rms_norm(h) and causal full attention over the T tokens, KV
heads shared by groups of H/KV query heads, rotary embedding in the
half-split (HF ``rotate_half``) layout; then h += sum over the top-k
experts e of softmax(top-k router logits)_e * W_down_e(silu(W_gate_e x)
* W_up_e x). Qwen3-MoE's ``norm_topk_prob`` (softmax over all experts,
top-k, renormalise) is the same function. Dense (non-expert) MLPs are
handled for tiny test configurations.

Memory: the engine's bf16 parameters stay on the chip while this runs,
so no float32 copy of the tree is ever made (8.6 GB of parameters twice
over does not fit 16 GB): layers are indexed one at a time, experts are
scanned one at a time, and only ONE expert's float32 matrices are alive.
Every expert is evaluated for every token and weighted by its routing
weight (zero when not chosen): exact, and no dispatch to get wrong.

Callers wrap the call in ``jax.default_matmul_precision("highest")``: on
a TPU a float32 matmul otherwise runs in bf16 passes.

The rule of agreement (``judge``), with its reasons. The engine is run
greedy on PROMPTS = 3 seeded prompts of 96 tokens, 1 + 8 tokens each;
the reference is teacher-forced on the engine's tokens; at each of the
27 compared positions the engine's top-20 log-probabilities are set
against the reference's (d = max-abs difference over those ids). All 27
are judged together, once; there is no retry and no choice among
prompts. The check passes when

  - the median of d is <= AGREE_ATOL = 0.1, and
  - no position has d > FLIP_ATOL = 2.5.

Why a median and not every position: top-k routing is discontinuous.
The engine's activations are bf16 and drift from these float32 ones by
a few parts in a thousand; where the k-th and (k+1)-th router logits of
a token lie closer than that, the two sides choose different experts and
are different functions of the input from there on: that position, and
through attention, more weakly, the ones after it. Measured on the chip
(PR 23, 10 prompts x 9 positions per configuration and router gain,
PERF.md Findings): no reference-side cure removes this. Forming the
router logits in bf16 as the published implementations do changed
nothing (the drift, not the rounding of the logits, decides); a larger
router gain makes a swapped expert lighter but the softmax over the
chosen ones steeper, and past 2 to 4 times the init scale d grows
again. At ROUTER_GAIN = 2 (harness/weights.py) Mixtral reads d = 0.02 to
0.08 at undisturbed positions and 0.3 to 0.6 at the 4 of 90 a swap hit;
Qwen3-30B-A3B, where a third of all routing decisions are that close,
reads a median of 0.045 with 12% of positions over 0.1 and none over
0.3. So a rule on every position fails sound engines (4 of 10 single
prompts on Mixtral, 9 of 10 on Qwen), while the median over 27 positions
does not move with a few swaps and moves with everything systematic:
the same engine against a reference that leaves out the last of the k
experts reads a median of 0.21 (Qwen) and 1.6 (Mixtral), against one
whose expert matrices are rounded to 8-bit floats [see PERF.md]. The
largest d a swap produced in some 500 positions was 1.44; wrong numbers
(a lost page, a wrong position) read 5 and more, hence FLIP_ATOL.

What the rule cannot see: a fault that moves fewer than half of the
positions by less than 2.5.
"""

from __future__ import annotations

from functools import partial

PROMPTS = 3
AGREE_ATOL = 0.1
FLIP_ATOL = 2.5


def judge(ref, toks, tops) -> dict:
    """ref: [n, V] reference logprobs at the compared positions (all
    prompts, one after another); toks / tops: the engine's tokens and
    per-token {id: logprob} top-20 at the same positions."""
    import statistics

    import numpy as np

    diffs = []
    for i in range(len(toks)):
        ids = np.fromiter(tops[i].keys(), int)
        vals = np.fromiter(tops[i].values(), float)
        diffs.append(float(np.max(np.abs(ref[i, ids] - vals))))
    median = statistics.median(diffs)
    over = [i for i, d in enumerate(diffs) if d > FLIP_ATOL]
    return {"positions": len(toks),
            "abs_logprob_diffs": [round(d, 4) for d in diffs],
            "median_abs_logprob_diff": median,
            "max_abs_logprob_diff": max(diffs),
            "positions_over_flip_atol": over,
            "ok": bool(median <= AGREE_ATOL and not over)}


def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, inv_freq):
    """x: [T, heads, hd]; half-split rotation by position."""
    import jax.numpy as jnp

    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, params, h, l):
    """One layer on h [T, D] float32; ``l`` is a traced layer index so
    one compiled program serves every layer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    T = h.shape[0]
    eps = cfg.rms_norm_eps

    def at(name):
        return lax.dynamic_index_in_dim(params[name], l, 0, False)

    x = _rms(h, at("ln_attn"), eps)
    q = (x @ at("wq").astype(f32)).reshape(T, H, hd)
    k = (x @ at("wk").astype(f32)).reshape(T, KV, hd)
    v = (x @ at("wv").astype(f32)).reshape(T, KV, hd)
    if cfg.attn_bias:
        q = q + at("bq").astype(f32).reshape(H, hd)
        k = k + at("bk").astype(f32).reshape(KV, hd)
        v = v + at("bv").astype(f32).reshape(KV, hd)
    if cfg.qk_norm:
        q = _rms(q, at("q_norm"), eps)
        k = _rms(k, at("k_norm"), eps)
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    q, k = _rope(q, inv), _rope(k, inv)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)        # [T, H, hd]
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * (hd ** -0.5)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    h = h + a.reshape(T, H * hd) @ at("wo").astype(f32)

    x = _rms(h, at("ln_mlp"), eps)
    if cfg.num_experts <= 0:
        up = jax.nn.silu(x @ at("w_gate").astype(f32)) \
            * (x @ at("w_up").astype(f32))
        return h + up @ at("w_down").astype(f32)
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    logits = x @ at("w_router").astype(f32)             # [T, E]
    top, idx = lax.top_k(logits, K)
    w = jax.nn.softmax(top, axis=-1)                    # over the chosen K
    route = jnp.zeros((T, E), f32).at[
        jnp.arange(T)[:, None], idx].set(w)             # 0 where not chosen

    def expert(acc, e):
        def w_(name):
            return lax.dynamic_index_in_dim(
                at(name), e, 0, False).astype(f32)

        y = (jax.nn.silu(x @ w_("w_gate")) * (x @ w_("w_up"))) @ w_("w_down")
        r = lax.dynamic_index_in_dim(route, e, 1, True)  # [T, 1]
        return acc + r * y, None

    out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    return h + out


layer = _layer      # the one-layer program rehearse.py compiles


def reference_logits(params, cfg, tokens):
    """Logits [T, V] float32 for one sequence of token ids."""
    import jax
    import jax.numpy as jnp

    for flag in ("embed_scale", "norm_unit_offset", "sandwich_norms",
                 "sliding_window", "attn_logit_softcap",
                 "final_logit_softcap", "rope_scaling", "is_mla"):
        if getattr(cfg, flag, None):
            raise NotImplementedError(
                f"the plain reference does not cover cfg.{flag}")
    layer = jax.jit(partial(_layer, cfg))

    @jax.jit
    def embed(params, toks):
        return params["embed"][toks].astype(jnp.float32)

    @jax.jit
    def head(params, h):
        x = _rms(h, params["ln_final"], cfg.rms_norm_eps)
        wh = params.get("lm_head")
        wh = params["embed"].T if wh is None else wh
        return x @ wh.astype(jnp.float32)

    h = embed(params, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg.num_layers):
        h = layer(params, h, jnp.int32(l))
    return head(params, h)
