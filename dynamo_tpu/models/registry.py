"""Model-family registry: ModelConfig → model module.

The engine resolves init_params / init_kv_cache / make_step_fns through
this table, so adding a family (reference: each engine adapter brings its
own model zoo, lib/llm/src/engines/) is one module with the shared paged
step-fn contract. The table dispatches by what a configuration HAS
(KDA heads with or without latent ranks, latent ranks, Mamba-2 heads, a
list of layer kinds, a state-space width, a parallel block), not by a
flag per family. Eight modules:

- ``llama.py``: one homogeneous stack of attention + MLP-or-MoE layers,
  scanned (Llama / Qwen2 / Qwen3 / Qwen3-MoE / Mixtral / Gemma shapes);
  and, for a configuration whose layers differ in what they may see
  (``kv_pool_by_kind``: SmallThinker), the by-kind path: a K/V pool a
  kind of layer, a period's layers unrolled, whose form follows what the
  configuration has (a sequential or a PARALLEL block, RMSNorm or
  LayerNorm, the half-split or the interleaved rotation, the Mixtral
  gate over all experts or the DeepSeek kind's second half over the
  experts held). It also holds what several modules run: the expert
  execution (``moe_experts``), the one DeepSeek gate (``deepseek_gate``)
  and the held-experts second half (``deepseek_moe_mlp``), which
  ``mla.py`` keeps under their old names;
- ``cohere2_moe.py`` (``parallel_block``: ``cohere2_moe``, Command A+):
  the params' tree (one LayerNorm a layer, no ``ln_mlp``; the router at
  its published width; the experts held; the shared experts side by
  side), ``WINDOW_COUNTS`` and ``llama.py``'s by-kind programs under the
  names the engine calls; nothing of a layer is written in the module;
- ``mla.py``: DeepSeek-V2/V3 latent attention (a latent and a rope pool);
- ``jamba.py``: layers of two kinds in a fixed pattern, Mamba-1 mixers
  and attention, dense MLPs; it owns the layout the next module shares
  (runs of state-space layers scanned between the attending ones, a
  pool of scan state and one of conv tails, the window's carry) and
  takes a family's mixer, second half of a layer and step kernel as
  ``jamba.Blocks``;
- ``granite.py`` (``mamba_n_heads`` > 0: ``granitemoehybrid``): on that
  layout, Mamba-2 mixers (a matrix of state a head, a chunked matmul
  form for prompts, ``ops/selective_scan.py ssd_step`` for decode),
  attention without positions scaled by ``attention_multiplier``, and
  in every layer routed experts beside one shared expert (llama.py's
  expert execution, told which experts it holds), under the embedding,
  residual and logits multipliers;
- ``solar_open2.py`` (``kda_n_heads`` > 0 and no ``kv_lora_rank``:
  ``solar_open2``): on jamba.py's layout, ``kimi_linear.py``'s KDA mixer
  (64 heads, ``kda_beta_scale`` 2: beta in (0, 2)), chunk and step
  kernels and held-experts second half (no dense layer) with
  ``jamba.GQA`` as its ``jamba.Attending``: K/V pages of the attending
  layers only, no positions, attention's output gated by ``sigmoid(x @
  wg)`` because its params hold ``wg``; nothing of a layer is written in
  the module;
- ``kimi_linear.py`` (``kda_n_heads`` > 0 with ``kv_lora_rank``:
  ``kimi_linear``, asked before ``is_mla``, which is true of it too): on jamba.py's layout, Kimi
  Delta Attention mixers (a gated delta rule with a decay a key channel
  on a matrix of state a head, a chunked matmul form for prompts,
  ``ops/kda.py kda_step`` for decode) and, as its ``jamba.Attending``,
  latent attention without positions built from mla.py's functions over
  pools of the attending layers only; a dense first layer, then mla.py's
  sigmoid gate and expert execution told which experts it holds, beside
  a shared expert;
- ``lfm2.py``: layers of two kinds by a list (``layer_types``), gated
  short convolutions and attention, two dense MLPs and then routed
  experts (llama.py's sigmoid gate and expert execution).

| the configuration has | module | K/V | state |
|---|---|---|---|
| ``kda_n_heads`` and ``kv_lora_rank`` | ``kimi_linear.py`` | latent pools of the attending layers | KDA state + conv tails |
| ``kda_n_heads`` alone | ``solar_open2.py`` | K/V pages of the attending layers | KDA state + conv tails |
| ``kv_lora_rank`` | ``mla.py`` | a latent and a rope pool | none |
| ``mamba_n_heads`` | ``granite.py`` | K/V pages of the attending layers | Mamba-2 state + conv tails |
| ``layer_types`` (``conv`` / ``full_attention``) | ``lfm2.py`` | K/V pages of the attending layers | conv tails, snapshotted by the page |
| ``mamba_d_state`` | ``jamba.py`` | K/V pages of the attending layers | Mamba-1 state + conv tails |
| ``parallel_block`` | ``cohere2_moe.py`` | a pool a kind of layer (``llama.py`` by kind) | none |
| none of these | ``llama.py`` | one pool, or with ``kv_pool_by_kind`` a pool a kind | none |

**What a module writes.** Four functions the engine calls by name:
``init_params(cfg, key)``, ``init_kv_cache(cfg, spec)``,
``make_step_fns(cfg, allow_pallas=True, mesh=None)`` (the jitted
``prefill_step`` and ``decode_step``) and ``make_decode_window_fn(cfg,
allow_pallas=True, max_top_k=64, mesh=None, pallas_interpret=False)``,
the fused window, of which a module writes its buffers, ONE step and
the commit: ``models/window.py`` says what those are and owns the rest.
Four more are optional and found by ``hasattr`` (ROADMAP C18):
``init_state`` and ``init_state_snapshots`` (below), ``make_verify_fn``
(the speculative verify forward) and ``WINDOW_COUNTS`` (the names of
what the window's steps count).

**Which change the step's shape.** For every configuration but one
kind, a decode step takes one token a row in and gives one out. A
configuration with ``block_length`` > 1 (``model_type: sdar_moe``: the
Qwen3-MoE layer under a block mask, text generated by diffusion over
blocks) stays in ``llama.py``, whose ``_visible`` / prefill kernel take
the block mask and whose ``make_decode_window_fn`` then returns the
BLOCK window (``_make_block_window_fn``, same name ``decode_window``,
same call form): a window of whole blocks, each up to
``denoising_steps`` forwards of ``[B, L]`` positions and one commit
forward; the carry's token operand is a block a row and the program
returns its own per-row counts last. The engine keys its bookkeeping on
``cfg.block_length`` (``JaxEngine.block``), never on an option. What
refuses such a configuration, each by a ``NotImplementedError`` that
says "<what> is not supported for a model that generates by diffusion
over blocks (block_length > 1, models/llama.py _make_block_window_fn):
<why>; its step yields a block a row, and only JaxEngine's window arm
on one device keeps the books of that (ROADMAP B10)": ``host_pages >
0``, ``spec_decode``, ``long_prefill_threshold``, a mesh of more than
one device (``engine/jax_engine.py _refuse_block_generation``), the
three disagg / KV-transfer classes below (``kv_manager.
refuse_recurrent_state``), and, by the request, a sampling penalty or
``logit_bias``. ``page_size`` must be a multiple of ``block_length``
and ``decode_steps`` a multiple of it (``ValueError``).

**Which hold a share of their experts.** A configuration whose
``num_experts`` (held here) is less than its ``router_width`` is one
chip's share of a layer under expert parallelism: the router keeps its
published width and top-k, ``llama.moe_experts(first=...)`` computes the
pairs routed to experts ``[first_expert, first_expert + num_experts)``
in both execution forms, and the partial sum goes on; nothing stands in
for the other chips or their exchange (one device: ROADMAP B9).
``granite.py``, ``kimi_linear.py`` (through
``llama.deepseek_moe_mlp(first=...)``; ``solar_open2.py`` runs the same
``kimi_linear._ff``) and ``llama.py``'s by-kind path (``_ff_out``, for
``cohere2_moe.py``) pass it; all four count the pairs routed and held a
decode window (``llama.pairs_counted``, ``WINDOW_COUNTS``).

**Which keep state.** ``jamba.py``, ``granite.py``, ``kimi_linear.py``,
``solar_open2.py`` and ``lfm2.py`` carry per-sequence **recurrent state** beside the KV
pages. A module declares that by
having ``init_state(cfg, slots)`` (a tuple of pools indexed by slot,
along whichever axis the module's own programs say: the engine never
looks inside; jamba.py's layout keeps the scan states slot-major ``[S,
M, ...]`` and the conv tails layer-major ``[M, S, ...]``);
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
question), nor does ``granite.py`` (36 MiB a row at nine layers of 128 x
64 x 128 float32) nor ``kimi_linear.py`` (12.4 MiB a row at six layers of
32 x 128 x 128 float32) nor ``solar_open2.py`` (12.4 MiB a row at three
layers of 64 x 128 x 128 float32, beside 4 KiB of K/V a token): for them
a prefix hit counts as a miss, as before.

What refuses a model with recurrent state, at construction, each by a
``NotImplementedError`` that says "<what> is not supported for a model
with recurrent state (models/jamba.py, models/lfm2.py,
models/granite.py, models/kimi_linear.py, models/solar_open2.py): <why>;
a state snapshot lives in the device pool under its page's id, or not at
all, and nothing moves or rolls back a state (ROADMAP B7)":

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
    if cfg.kda_n_heads > 0:     # before is_mla: the other layers keep state
        # the attending layers are latent, or K/V pages
        from . import kimi_linear, solar_open2

        return kimi_linear if cfg.is_mla else solar_open2
    if cfg.is_mla:
        from . import mla

        return mla
    if cfg.mamba_n_heads > 0:
        from . import granite

        return granite
    if cfg.layer_types:
        from . import lfm2

        return lfm2
    if cfg.mamba_d_state > 0:
        from . import jamba

        return jamba
    if cfg.parallel_block:
        from . import cohere2_moe

        return cohere2_moe
    from . import llama

    return llama
