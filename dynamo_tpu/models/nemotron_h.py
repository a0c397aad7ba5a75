"""Nemotron-H: a stack in which a layer is ONE sub-block under its own
norm and residual add, a Mamba-2 mixer, an attention mixer or a
feed-forward part alone (NVIDIA Nemotron-H family, ``model_type:
nemotron_h``, as Nemotron 3 Super states it), on jamba.py's layout: the
runs of layers between the attending ones, the pools of per-sequence
state, the attention over pages without positions and the fused window
are that module's (``jamba.Blocks``), the Mamba-2 mixer, its pools and
its step kernel are models/granite.py's, run with ``n_groups`` groups of
B and C; this module supplies the layer pattern and the feed-forward
part.

``hybrid_override_pattern`` names each layer: ``M`` Mamba-2, ``*``
attention, ``E`` experts (``-``, a dense MLP, is refused). Entry ``h =
embed[token]``; layer l: ``h += Block_l(rms_norm(h; ln_l))``; exit
``logits = rms_norm(h) @ lm_head``. ``*`` is GQA, causal, scores /
sqrt(head_dim), no bias and NO positional embedding. ``E``, on the
normed input x:

    s      = sigmoid(x W_r)                  float32, the router's width:
                                             the norm unrounded, the
                                             product at HIGHEST
    chosen = top-k of s + router_bias;       w = scale * s[chosen] / sum
    u      = x W_lat_in                      hidden -> moe_latent_size, ONCE
    r      = sum_k w_k relu(u W_up[e_k])^2 W_down[e_k]    experts HELD here
    out    = r W_lat_out + relu(x W_up_s)^2 W_down_s

An expert is NOT gated: two matrices, at the latent width, each read
once a use (llama.moe_experts' ``w_gate`` None with ``relu2``). The
router and the shared expert read the full-width input. The experts held
are ``[first_expert, first_expert + num_experts)`` of the router's
``router_width``: the chip's share of a layer under expert parallelism;
the partial sum goes through ``W_lat_out`` and, with the shared expert,
on to the next layer, and nothing stands in for the chips that hold the
rest or for the exchange of latent rows with them. The gate is
llama.deepseek_gate's sigmoid kind without a group limit.

**The layout's form of the pattern** (``segments``). jamba.py's layer has
two halves, a mixer and a second half; here a mixer takes the ``E`` that
follows it as its second half where there is one (``ME``, ``*E``), stands
alone where there is none (the ``M`` of ``M*E``), and an ``E`` no mixer
precedes is a second half alone. Runs of like Mamba-2 layers are
``lax.scan``-ned, so a program holds one trace a run, not one a layer.
``ln_mixer`` is stacked over the mixers (``M`` and ``*`` in order),
``ln_mlp`` and every leaf of the feed-forward part over the ``E`` layers,
Mamba-2 leaves over the ``M`` layers, attention leaves over the ``*``
layers: no leaf has a row that no layer reads.

**State**: granite.py's two pools, the matrix state ``[S, M, N, H * P]``
float32 and the conv tails ``[M, S, (d_conv - 1) * (d_inner + 2 G N)]``.
No snapshots: a prefix hit counts as a miss. The residual stream is
float32 (jamba._stack; the published ``residual_in_fp32`` is false).

Not computed, and refused by ``read_config`` where a file asks for it: a
dense MLP layer, any bias but the convolution's and dt's, a router with
group limits, the shared expert overlapped with the exchange, and the
self-drafting head (``num_nextn_predict_layers``: registry.REFUSALS
refuses spec_decode to every family with state).

Scopes: ``ssm`` > ``ssm.proj``, ``ssm.conv``, ``ssm.scan``, ``ssm.norm``
(granite._mamba2); ``moe`` > ``moe.router``, ``moe.latent`` (the two
latent projections), ``moe.experts``, ``moe.dispatch``, ``moe.shared``;
``attn``, ``lm_head``, ``sample``, ``kv_carry`` as in jamba.py.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from . import granite, jamba
from .config import ModelConfig, held_experts, hf_base, refuser
from .granite import MAMBA2_KEYS, WINDOW_COUNTS, init_state  # noqa: F401
from .jamba import _at, init_kv_cache  # noqa: F401
from .llama import (Params, _moe_use_blocked, deepseek_gate, held_first,
                    moe_experts, pairs_counted, relu2, rms_norm)
from ..ops.selective_scan import ssd_step

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
FF_KEYS = ("ln_mlp", "w_router", "router_bias", "w_lat_in", "w_lat_out",
           "w_up_s", "w_down_s")
EXPERT_KEYS = ("w_up", "w_down")


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``nemotron_h`` config.json (``n_routed_experts``:
    config.held_experts). The first ``num_hidden_layers`` characters of
    ``hybrid_override_pattern`` are the layers that run."""
    refuse = refuser("nemotron_h")
    c = hf_base(cfg)
    L = cfg["num_hidden_layers"]
    pattern = cfg["hybrid_override_pattern"][:L]
    odd = sorted(set(pattern) - set(KINDS))
    if odd or len(pattern) != L:
        refuse(f"hybrid_override_pattern {odd or len(pattern)}",
               "it must name num_hidden_layers layers, each M (Mamba-2), "
               "* (attention) or E (experts); a dense MLP layer (-) is "
               "not computed")
    for key in ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias"):
        if cfg.get(key):
            refuse(f"{key} true",
                   "the projections are computed without a bias")
    if not cfg.get("use_conv_bias", True):
        refuse("use_conv_bias false",
               "the causal convolution adds its bias leaf")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        refuse(f"n_group {cfg.get('n_group')} / topk_group "
               f"{cfg.get('topk_group')}",
               "the router chooses among all its outputs, without a "
               "group limit")
    if cfg.get("moe_shared_expert_overlap"):
        refuse("moe_shared_expert_overlap true",
               "no exchange between chips runs for the shared expert to "
               "overlap")
    if cfg.get("num_nextn_predict_layers"):
        refuse(f"num_nextn_predict_layers "
               f"{cfg['num_nextn_predict_layers']}",
               "the self-drafting head is not computed: a drafted token "
               "advances the layers' state, which nothing rolls back; 0 "
               "serves the main model, whose logits do not depend on it")
    if cfg.get("mlp_hidden_act", "relu2") != "relu2" \
            or cfg.get("mamba_hidden_act", "silu") != "silu":
        refuse(f"mlp_hidden_act {cfg.get('mlp_hidden_act')!r} / "
               f"mamba_hidden_act {cfg.get('mamba_hidden_act')!r}",
               "an expert is relu(x W_up)^2 W_down and the mixer's gate "
               "is a SiLU")
    if cfg.get("sliding_window"):
        refuse("sliding_window set",
               "its attention layers attend to the whole context")
    if not cfg.get("moe_latent_size"):
        refuse("no moe_latent_size",
               "the routed experts are computed at the latent width")
    heads, d_head = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    expand = cfg.get("expand", 2)
    if heads * d_head != expand * cfg["hidden_size"]:
        refuse(f"mamba_num_heads x mamba_head_dim = {heads * d_head}",
               f"expand x hidden_size is {expand * cfg['hidden_size']}, "
               f"the mixer's one inner width")
    groups = cfg.get("n_groups", 1)
    if groups < 1 or heads % groups:
        refuse(f"n_groups {groups}",
               f"a group is a whole number of the {heads} heads")
    c.num_experts, c.router_experts, c.first_expert = held_experts(
        cfg, "n_routed_experts", "num_experts_per_tok", refuse)
    c.model_type = "nemotron_h"
    c.layer_types = tuple(KINDS[k] for k in pattern)
    c.rms_norm_eps = cfg.get("layer_norm_epsilon",
                             cfg.get("norm_eps", 1e-5))
    c.mamba_n_heads, c.mamba_d_head = heads, d_head
    c.mamba_d_state = cfg["ssm_state_size"]
    c.mamba_n_groups = groups
    c.mamba_d_conv = cfg.get("conv_kernel", 4)
    c.mamba_expand = expand
    c.mamba_chunk_size = cfg.get("chunk_size", 128)
    c.num_experts_per_tok = cfg["num_experts_per_tok"]
    c.moe_intermediate_size = cfg["moe_intermediate_size"]
    c.moe_latent_size = cfg["moe_latent_size"]
    c.n_shared_experts = cfg.get("n_shared_experts", 1)
    # the shared experts side by side: their sum is one MLP
    c.shared_intermediate_size = (c.n_shared_experts * cfg.get(
        "moe_shared_expert_intermediate_size", 0))
    c.moe_router = "deepseek_v3"
    c.norm_topk_prob = cfg.get("norm_topk_prob", True)
    c.routed_scaling_factor = float(cfg.get("routed_scaling_factor", 1))
    c.hidden_act = "relu2"
    c.tie_word_embeddings = cfg.get("tie_word_embeddings", False)
    return c


def segments(cfg: ModelConfig) -> List[tuple]:
    """The layer pattern as jamba._stack's runs (``Blocks.segments``): a
    mixer with the ``moe`` layer that follows it as its second half.
    ("mamba", first Mamba-2 index, first mixer index, count, first
    second-half index or None), ("attn", attention index, mixer index,
    second-half index or None) and ("ff", second-half index) for a
    ``moe`` layer that no mixer precedes, in layer order."""
    kinds = cfg.layer_types
    out: List[tuple] = []
    m = a = x = f = 0   # next Mamba-2, attention, mixer, second-half index
    l = 0
    while l < len(kinds):
        if kinds[l] == "moe":
            out.append(("ff", f))
            f, l = f + 1, l + 1
            continue
        ff = l + 1 < len(kinds) and kinds[l + 1] == "moe"
        at = f if ff else None
        if kinds[l] == "attention":
            out.append(("attn", a, x, at))
            a += 1
        elif (out and out[-1][0] == "mamba"
              and (out[-1][4] is None) == (at is None)):
            out[-1] = (*out[-1][:3], out[-1][3] + 1, out[-1][4])
            m += 1
        else:
            out.append(("mamba", m, x, 1, at))
            m += 1
        x, f, l = x + 1, f + ff, l + 1 + ff
    return out


# ------------------------------------------------------- params and pools


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params, each kind of leaf stacked over the layers that
    read it (the module's docstring). The expert stacks hold the experts
    HELD (``cfg.num_experts``), two matrices each at the latent width;
    the router is ``cfg.router_width`` wide."""
    dtype = dtype or cfg.jax_dtype
    D, V = cfg.hidden_size, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    kinds = cfg.layer_types
    M, A, F = (kinds.count(k) for k in ("mamba", "attention", "moe"))
    di, dc = cfg.mamba_d_inner, cfg.mamba_d_conv
    Hm, cw = cfg.mamba_n_heads, granite.conv_width(cfg)
    E, I, R = cfg.num_experts, cfg.moe_intermediate_size, cfg.moe_latent_size
    Is = cfg.shared_intermediate_size
    ks = iter(jax.random.split(key, 20))

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    # the published Mamba-2 init (granite.init_params): dt between 1e-3
    # and 1e-1 through the bias, A between 1 and 16 a head, skip of ones
    dt = jnp.exp(jax.random.uniform(next(ks), (M, Hm), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": w(V, D),
        "lm_head": w(D, V),
        "ln_mixer": jnp.ones((M + A, D), dtype),
        "ln_mlp": jnp.ones((F, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        "wq": w(A, D, H * hd), "wk": w(A, D, KV * hd),
        "wv": w(A, D, KV * hd), "wo": w(A, H * hd, D),
        "w_in": w(M, D, di + cw + Hm),
        "conv_w": w(M, dc, cw),
        "b_conv": jnp.zeros((M, cw), dtype),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            next(ks), (M, Hm), jnp.float32, 1.0, 16.0)).astype(dtype),
        "d_skip": jnp.ones((M, Hm), dtype),
        "ssm_norm": jnp.ones((M, di), dtype),
        "w_out": w(M, di, D),
        "w_router": w(F, D, cfg.router_width),
        "router_bias": jnp.zeros((F, cfg.router_width), jnp.float32),
        "w_lat_in": w(F, D, R), "w_lat_out": w(F, R, D),
        "w_up": w(F, E, R, I), "w_down": w(F, E, I, R),
        "w_up_s": w(F, D, Is), "w_down_s": w(F, Is, D),
    }


# ---------------------------------------------------- the feed-forward part


def _dot(a, w):
    """a @ w: operands in the weights' type, the result in float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def latent_in(x, w_lat_in):
    """The routed experts' input, hidden -> moe_latent_size, once a token
    and not once a pair. A function of the module's so that
    tools/nemotron_h_long_context_check.py can compute it in 8-bit floats
    (its ``latent-8bit`` control)."""
    return _dot(x, w_lat_in)


def _moe_ff(params: Params, cfg: ModelConfig, norm, h, l, valid, l0=None):
    """(h + the routed experts held here through the latent pair + the
    shared expert, of norm(h); WINDOW_COUNTS of this layer), ``moe``
    layer l of them (traced inside a run): jamba._dense_ff's call
    form."""
    f32 = jnp.float32
    lp = _at(params, FF_KEYS, l)
    # ``norm``'s result before it is rounded to the weights' type: the
    # router reads that (below), the matrices x
    x32 = rms_norm(h, lp["ln_mlp"].astype(f32), cfg.rms_norm_eps)
    x = x32.astype(params["embed"].dtype)
    B, T, _ = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    first = held_first(cfg)

    with jax.named_scope("moe"):
        with jax.named_scope("moe.router"):
            # float32 in deed: the 22nd and the 23rd of 512 scores lie
            # 0.02 of the logits' spread apart, x rounded to bf16 (which
            # the default product does again on a TPU) moves a logit by
            # 0.001 of it, and a chosen expert weighs 5/22 whichever it is
            weights, idx = deepseek_gate(x32, lp["w_router"],
                                         lp["router_bias"], cfg,
                                         precision=lax.Precision.HIGHEST)
            counted = pairs_counted(cfg, idx, valid)
        with jax.named_scope("moe.latent"):
            u = latent_in(x, lp["w_lat_in"])
        # the sorted form reads w[layer, expert] from the whole stacks,
        # the dense form one layer's (llama._moe_use_blocked: the rule)
        if _moe_use_blocked(None, B * T, E, k):
            routed = moe_experts(u, weights, idx, None, params["w_up"],
                                 params["w_down"], True, live=valid,
                                 layer=l, first=first,
                                 width=cfg.router_width, act=relu2)
        else:
            ep = _at(params, EXPERT_KEYS, l)
            routed = moe_experts(u, weights, idx, None, ep["w_up"],
                                 ep["w_down"], False, first=first,
                                 act=relu2)
        with jax.named_scope("moe.latent"):
            out = _dot(routed, lp["w_lat_out"])
        with jax.named_scope("moe.shared"):
            shared = relu2(x @ lp["w_up_s"]) @ lp["w_down_s"]
    return h + out + shared.astype(f32), counted


BLOCKS = jamba.Blocks(MAMBA2_KEYS, granite._mamba2, _moe_ff, ssd_step,
                      WINDOW_COUNTS, segments=segments)


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
