"""Solar Open 2: Kimi Delta Attention (KDA) mixers with 64 heads whose
delta rule may have negative eigenvalues, beside gated GQA layers
without positions where the configuration's ``gqa_layers`` says so
(three KDA layers to one attending), and in every layer sigmoid-routed
experts beside a shared expert (Upstage Solar Open 2 family,
``model_type: solar_open2``), on jamba.py's layout (``jamba.Blocks``).

Nothing of the layers is written here: the KDA mixer, its chunked form,
its state pools and the held-experts second half are
models/kimi_linear.py's (``_kda`` with ``cfg.kda_beta_scale`` 2: beta in
(0, 2), so the transition ``Diag(exp(g)) (I - beta k k^T)`` may have an
eigenvalue in (-1, 0); ``_ff`` with ``first_k_dense_replace`` 0: no
dense layer, and no dense leaf is built), the kernels ops/kda.py's, and
the attending half is ``jamba.GQA`` (K and V pages of the attending
layers only, ``[n_attn, pages, KV, ps, hd]``, no rotation, llama.py's
paged attention and kernels), which multiplies attention's output by
``sigmoid(x @ wg)`` because this family's params hold the leaf ``wg``
(``jamba._gated``, scope ``attn.gate``). This module supplies the
params' tree and the four entry points.

**State.** A sequence carries, a KDA layer, S of every head (float32, 4
MiB at 64 heads of 128 x 128) and the last ``d_conv - 1`` inputs of the
three convolutions; beside it K and V of 8 KV heads a token an attending
layer. No snapshots: a prefix hit counts as a miss, as for Jamba,
Granite and Kimi Linear. Scopes: kimi_linear.py's ``kda`` (``kda.proj``,
``kda.conv``, ``kda.gate``, ``kda.scan``, ``kda.norm``) and ``moe``
(``moe.router``, ``moe.dispatch``, ``moe.experts``, ``moe.shared``);
``attn`` with ``attn.gate``; ``lm_head``, ``sample``, ``kv_carry``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import jamba, kimi_linear
from .config import ModelConfig, held_experts, hf_base, refuser
from .granite import WINDOW_COUNTS  # noqa: F401  (the engine reads it)
from .jamba import init_kv_cache  # noqa: F401  (K/V of the attending layers)
from .kimi_linear import init_state  # noqa: F401  (declares the state)
from .llama import Params
from ..ops.kda import kda_chunk, kda_step

BLOCKS = jamba.Blocks(kimi_linear.KDA_KEYS, kimi_linear._kda,
                      kimi_linear._ff, kda_step, WINDOW_COUNTS, jamba.GQA,
                      kda_chunk)


def read_config(cfg: dict) -> ModelConfig:
    """The keys of a ``solar_open2`` config.json. ``gqa_layers`` counts
    layers from 0 and is kept whole in a file cut in depth: the entries
    under ``num_hidden_layers`` are the layers that attend, every other
    layer is KDA (``n_routed_experts``: config.held_experts)."""
    refuse = refuser("solar_open2")
    c = hf_base(cfg)
    L = cfg["num_hidden_layers"]
    lin = cfg["linear_attn_config"]
    attending = {l for l in cfg["gqa_layers"] if 0 <= l < L}
    if not attending or len(attending) == L:
        refuse(f"gqa_layers {sorted(attending)} of {L} layers",
               "the state pool holds the KDA layers and the K/V pools "
               "the attending ones; a model of one kind is another "
               "module's")
    if cfg.get("use_rope", False):
        refuse("use_rope true",
               "its attending layers apply no positional embedding, "
               "and no cell would run the rotated form")
    if not cfg.get("use_gqa_gate", False):
        refuse("use_gqa_gate false",
               "its attending layers gate attention's output (the "
               "leaf wg), and no cell would run the ungated form")
    if cfg.get("kda_use_full_proj", False):
        refuse("kda_use_full_proj true",
               "the decay and the output gate are projected through a "
               "bottleneck of the head size (w_f1 / w_f2, w_g1 / w_g2)")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        refuse(f"linear_attn_config.num_kv_heads {lin['num_kv_heads']}",
               "q, k and v of a KDA layer all have num_heads heads")
    if cfg.get("first_k_dense_replace", 0):
        refuse(f"first_k_dense_replace {cfg['first_k_dense_replace']}",
               "every layer's second half is routed experts beside the "
               "shared expert; the module builds no dense MLP")
    if (cfg.get("n_group") or 1) != 1 or (cfg.get("topk_group") or 1) != 1:
        refuse(f"n_group {cfg.get('n_group')} / topk_group "
               f"{cfg.get('topk_group')}",
               "the gate chooses among all the router's outputs")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "no layer rotates")
    c.num_experts, c.router_experts, c.first_expert = held_experts(
        cfg, "n_routed_experts", "num_experts_per_tok", refuse)
    c.model_type = "solar_open2"
    c.layer_types = tuple("attention" if l in attending else "kda"
                          for l in range(L))
    c.kda_n_heads = lin["num_heads"]
    c.kda_head_dim = lin["head_dim"]
    c.mamba_d_conv = lin.get("short_conv_kernel_size", 4)
    c.kda_beta_scale = 2.0 if cfg.get("kda_allow_neg_eigval") else 1.0
    c.num_experts_per_tok = cfg["num_experts_per_tok"]
    # sigmoid scores, selection by score + bias, the unbiased scores of
    # the chosen renormalised and scaled: DeepSeek-V3's gate without
    # groups (models/mla.py _deepseek_gate)
    c.moe_router = "deepseek_v3"
    c.norm_topk_prob = bool(cfg.get("norm_topk_prob", True))
    c.routed_scaling_factor = float(cfg.get("routed_scaling_factor", 1.0))
    c.n_shared_experts = cfg.get("n_shared_experts", 0)
    c.first_k_dense_replace = 0
    c.moe_intermediate_size = cfg["moe_intermediate_size"]
    return c


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params; each kind of leaf stacked on its own axis 0
    (pre-norms over all L layers, KDA leaves over the M KDA layers, the
    attention leaves and the gate ``wg`` over the attending ones, router,
    experts and shared expert over all L layers: no layer is dense)."""
    dtype = dtype or cfg.jax_dtype
    D, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    A = len(cfg.attn_layer_ids)
    w, ks = kimi_linear._drawer(key, dtype)
    return {
        "embed": w(V, D), "lm_head": w(D, V),
        "ln_mixer": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
        "ln_final": jnp.ones((D,), dtype),
        **kimi_linear.kda_leaves(cfg, w, ks, dtype),
        "wq": w(A, D, H * hd), "wk": w(A, D, KV * hd),
        "wv": w(A, D, KV * hd), "wo": w(A, H * hd, D),
        **kimi_linear.moe_leaves(cfg, w, dtype),
        "wg": w(A, D, H * hd),
    }


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
