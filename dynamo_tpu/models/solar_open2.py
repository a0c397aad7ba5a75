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
from .config import ModelConfig
from .granite import WINDOW_COUNTS  # noqa: F401  (the engine reads it)
from .jamba import init_kv_cache  # noqa: F401  (K/V of the attending layers)
from .kimi_linear import init_state  # noqa: F401  (declares the state)
from .llama import Params
from ..ops.kda import kda_chunk, kda_step

BLOCKS = jamba.Blocks(kimi_linear.KDA_KEYS, kimi_linear._kda,
                      kimi_linear._ff, kda_step, WINDOW_COUNTS, jamba.GQA,
                      kda_chunk)


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
