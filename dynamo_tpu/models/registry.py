"""Model-family registry: ModelConfig → model module.

The engine resolves init_params / init_kv_cache / make_step_fns through
this table, so adding a family (reference: each engine adapter brings its
own model zoo, lib/llm/src/engines/) is one module with the shared paged
step-fn contract. Three modules:

- ``llama.py``: one homogeneous stack of attention + MLP-or-MoE layers,
  scanned (Llama / Qwen2 / Qwen3 / Qwen3-MoE / Mixtral / Gemma shapes);
- ``mla.py``: DeepSeek-V2/V3 latent attention (a latent and a rope pool);
- ``jamba.py``: layers of two kinds in a fixed pattern, Mamba-1 mixers
  and attention, with per-sequence **recurrent state** beside the KV
  pages. A module declares that by having ``init_state(cfg, slots)``;
  the engine then owns a state pool, every step program takes ``(state,
  state_slots)`` as its last two operands and returns the pool last, and
  a prefix hit counts as a miss (pages come without state).

What refuses a model with recurrent state, at construction, each by a
``NotImplementedError`` that says "<what> is not supported for a model
with recurrent state (models/jamba.py): <why>; nothing snapshots or
moves the state pool yet (ROADMAP B7)":

- ``EngineConfig.host_pages > 0`` (the host KV tier): "a page restored
  from the host comes without the state that goes with it";
- ``EngineConfig.spec_decode``: "a rejected draft token has already
  advanced the state, which cannot be rolled back";
- a mesh of more than one device: "no sharding rule places the state
  pool or the Mamba leaves";
- ``llm/disagg`` ``PrefillWorker``, ``DisaggDecodeEngine`` and
  ``KvTransferServer`` (disagg and KV transfer): "it moves KV pages
  between places, and a sequence's pages without its state are not the
  sequence".
"""

from __future__ import annotations

from .config import ModelConfig


def get_model_module(cfg: ModelConfig):
    if cfg.is_mla:
        from . import mla

        return mla
    if cfg.has_recurrent_state:
        from . import jamba

        return jamba
    from . import llama

    return llama
