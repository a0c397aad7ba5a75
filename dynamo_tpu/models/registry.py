"""Model-family registry: ModelConfig → model module.

The engine resolves init_params / init_kv_cache / make_step_fns through
this table, so adding a family (reference: each engine adapter brings its
own model zoo, lib/llm/src/engines/) is one module with the shared paged
step-fn contract. The table dispatches by what a configuration HAS
(latent ranks, a list of layer kinds, a state-space width), not by a
flag per family. Four modules:

- ``llama.py``: one homogeneous stack of attention + MLP-or-MoE layers,
  scanned (Llama / Qwen2 / Qwen3 / Qwen3-MoE / Mixtral / Gemma shapes);
- ``mla.py``: DeepSeek-V2/V3 latent attention (a latent and a rope pool);
- ``jamba.py``: layers of two kinds in a fixed pattern, Mamba-1 mixers
  and attention, dense MLPs;
- ``lfm2.py``: layers of two kinds by a list (``layer_types``), gated
  short convolutions and attention, two dense MLPs and then routed
  experts (mla.py's sigmoid gate, llama.py's expert execution).

**Which keep state.** ``jamba.py`` and ``lfm2.py`` carry per-sequence
**recurrent state** beside the KV pages. A module declares that by
having ``init_state(cfg, slots)`` (a tuple of pools indexed by slot);
the engine then owns the pools, every step program takes ``(state,
state_slots)`` as trailing operands and returns the state last.

**Which of those snapshot, and so take prefix hits.** ``lfm2.py`` also
has ``init_state_snapshots(cfg, spec)``: a pool indexed by PAGE id (48 KB
a page at LFM2-24B's widths cut to 8 layers). The engine appends it to
``state``, leaves ``PageManager.prefix_reuse`` True, and gives
``prefill_step`` one more operand, ``state_src``: for each row the page
whose snapshot its state starts from (-1: none). A program that writes
a page's last token writes the row's state after that token under the
page's id, so the snapshot lives and dies with the page. ``jamba.py``
declares none (a Mamba row is 9.3 MB; a snapshot a page is out of the
question): for it a prefix hit counts as a miss, as before.

What refuses a model with recurrent state, at construction, each by a
``NotImplementedError`` that says "<what> is not supported for a model
with recurrent state (models/jamba.py, models/lfm2.py): <why>; a state
snapshot lives in the device pool under its page's id, or not at all,
and nothing moves or rolls back a state (ROADMAP B7)":

- ``EngineConfig.host_pages > 0`` (the host KV tier): "a page restored
  from the host comes without the state that goes with it";
- ``EngineConfig.spec_decode``: "a rejected draft token has already
  advanced the state, which cannot be rolled back";
- a mesh of more than one device: "no sharding rule places the state
  pools or the leaves of the layers that keep state";
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
    if cfg.layer_types:
        from . import lfm2

        return lfm2
    if cfg.mamba_d_state > 0:
        from . import jamba

        return jamba
    from . import llama

    return llama
