"""Model families, declared once. ``FAMILIES`` names each family's
``model_type`` strings with their readers, what a ``ModelConfig`` of it
HAS, its module and what that module declares; ``REFUSALS`` says which
serving feature is refused to which capability, and why. The engine,
llm/disagg, ``ModelConfig.from_hf_config`` and the tools read these two
tables and spell no family out. A new family writes a module (each
module's docstring says what its layers are) with its ``read_config(dict)
-> ModelConfig`` in it, and one record. The order of the records is the
order ``family_of`` asks in: a family whose configuration also has what
a later one asks for stands first (kimi_linear and longcat_flash before
mla; nemotron_h,
whose layer is ONE sub-block and whose Mamba-2 mixer is granite.py's run
by groups, before granite; phi4flash, whose Mamba-1 layers stand beside
window layers with a pool of their own, before jamba).

**What a module writes.** Four functions the engine calls by name:
``init_params(cfg, key)``, ``init_kv_cache(cfg, spec)``,
``make_step_fns(cfg, allow_pallas=True, mesh=None)`` (the jitted
``prefill_step`` and ``decode_step``) and ``make_decode_window_fn(cfg,
allow_pallas=True, max_top_k=64, mesh=None, pallas_interpret=False)``,
the fused window, of which a module writes its buffers, ONE step and
the commit: ``models/window.py`` says what those are and owns the rest.
What else it has, its record declares:

- ``init_state(cfg, slots)``: per-sequence recurrent state beside the KV
  pages, a tuple of pools indexed by slot along whichever axis the
  module's programs say (the engine owns them and never looks inside).
  Every step program takes ``(state, state_slots)`` as trailing operands
  and returns the state last; a prefix hit counts as a miss.
- ``init_state_snapshots(cfg, spec)``: that state kept at each page's
  end in a pool indexed by PAGE id, appended to ``state``.
  ``prefill_step`` takes one more operand, ``state_src`` (for each row
  the page whose snapshot its state starts from, -1: none), a program
  that writes a page's last token writes the row's state under the
  page's id, and the prefix cache stays on: a hit hands over pages AND
  state.
- ``make_verify_fn``: the speculative verify forward (none: the flag
  leaves the standard path).
- ``window_counts``: what the window's steps count and return before
  the state (``models/window.py``).
- ``pool_by_kind``: the window layers keep K/V pools of their own
  (``init_window_kv_cache``, ``window_table_slots``; engine/kv_manager.py
  ``WindowPagePool``): the programs take ``(window pools, the rows'
  tables)`` in the two places where a family with state takes ``(state,
  state_slots)``, and nothing is published to the prefix cache. A
  family that declares ``init_state`` as well (models/phi4flash.py)
  takes both in those two places, as pairs in one order, window first:
  ``((window pools, state), (tables, state_slots))``, and returns
  ``(window pools, state)`` last; a sequence of it owns a state slot
  and pages of both pools, and is refused what either capability is.
- ``cross_on_last``: the module's programs run the layers that keep
  nothing a position (models/phi4flash.py: the cross half) on each
  row's last position alone, and a prefill program only where a row's
  logits are wanted (a ``last_idx`` >= 0: ``llama.prefill_logits``; a
  chunk that ends no prompt runs none of them); the engine counts, a
  prefill dispatch, the positions each half ran on (``self_rows_total``,
  and ``cross_rows_total`` where it asked for logits, in ``stats()``).
- ``by_blocks``: a decode forward yields ``cfg.block_length`` tokens a
  row (diffusion over blocks). ``make_decode_window_fn`` returns the
  BLOCK window (same name ``decode_window``, same call form), whose
  token operand is a block a row and which returns its own per-row
  counts last; ``prefill_step`` samples nothing; ``page_size`` and
  ``decode_steps`` must be multiples of the block (``ValueError``).
"""

from __future__ import annotations

from types import ModuleType
from typing import Callable, Dict, NamedTuple, Optional

from . import (cohere2_moe, config, granite, jamba, kimi_linear, lfm2,
               llama, longcat_flash, mla, nemotron_h, phi4flash, solar_open2)
from .config import ModelConfig


class ModelFamily(NamedTuple):
    """One family (the module's docstring)."""
    name: str
    # model_type of a config.json it claims -> its reader (dict ->
    # ModelConfig)
    readers: Dict[str, Callable]
    # whether a ModelConfig is of this family, asked in FAMILIES' order
    has: Callable[[ModelConfig], bool]
    module: ModuleType
    init_state: Optional[Callable] = None
    init_state_snapshots: Optional[Callable] = None
    make_verify_fn: Optional[Callable] = None
    window_counts: tuple = ()
    pool_by_kind: bool = False
    cross_on_last: bool = False
    by_blocks: bool = False

    def refusal(self, feature: str) -> Optional[str]:
        """Why REFUSALS refuses ``feature`` to this family, or None where
        it is served."""
        what, whys = REFUSALS[feature]
        for capability, (has, sentence) in CAPABILITIES.items():
            if capability in whys and has(self):
                return sentence.format(what=what, why=whys[capability])
        return None

    def refuse(self, feature: str) -> None:
        """Raise where REFUSALS refuses ``feature`` to this family: what
        the engine's constructor and the classes of llm/disagg call."""
        said = self.refusal(feature)
        if said is not None:
            raise NotImplementedError(said)


def _llama(name: str, readers: dict, has: Callable, **declares):
    return ModelFamily(name, readers, has, llama,
                       make_verify_fn=llama.make_verify_fn, **declares)


FAMILIES = (
    # KDA before latent ranks: kimi_linear has both, and keeps state
    ModelFamily("kimi_linear", {"kimi_linear": kimi_linear.read_config},
                lambda c: c.kda_n_heads > 0 and c.is_mla, kimi_linear,
                init_state=kimi_linear.init_state,
                window_counts=kimi_linear.WINDOW_COUNTS),
    ModelFamily("solar_open2", {"solar_open2": solar_open2.read_config},
                lambda c: c.kda_n_heads > 0, solar_open2,
                init_state=solar_open2.init_state,
                window_counts=solar_open2.WINDOW_COUNTS),
    # two latent attentions a layer and identity experts before latent
    # ranks: longcat_flash has those too, and pools of 2 x num_layers
    ModelFamily("longcat_flash", {"longcat_flash": longcat_flash.read_config},
                lambda c: c.is_mla and c.moe_router == "longcat_flash",
                longcat_flash, window_counts=longcat_flash.WINDOW_COUNTS),
    ModelFamily("mla", {"deepseek_v2": mla.read_config,
                        "deepseek_v3": mla.read_config},
                lambda c: c.is_mla, mla),
    # experts in a latent before Mamba-2 heads: nemotron_h has both, and
    # its layer is one sub-block
    ModelFamily("nemotron_h", {"nemotron_h": nemotron_h.read_config},
                lambda c: c.mamba_n_heads > 0 and c.moe_latent_size > 0,
                nemotron_h, init_state=nemotron_h.init_state,
                window_counts=nemotron_h.WINDOW_COUNTS),
    ModelFamily("granite", {"granitemoehybrid": granite.read_config},
                lambda c: c.mamba_n_heads > 0, granite,
                init_state=granite.init_state,
                window_counts=granite.WINDOW_COUNTS),
    ModelFamily("lfm2", {"lfm2_moe": lfm2.read_config},
                lambda c: bool(c.layer_types), lfm2,
                init_state=lfm2.init_state,
                init_state_snapshots=lfm2.init_state_snapshots),
    # Mamba-1 beside pools by kind before Mamba-1: phi4flash has both
    ModelFamily("phi4flash", {"phi4flash": phi4flash.read_config},
                lambda c: c.mamba_d_state > 0 and c.kv_pool_by_kind,
                phi4flash, init_state=phi4flash.init_state,
                pool_by_kind=True, cross_on_last=True),
    ModelFamily("jamba", {"jamba": jamba.read_config},
                lambda c: c.mamba_d_state > 0, jamba,
                init_state=jamba.init_state),
    ModelFamily("cohere2_moe", {"cohere2_moe": cohere2_moe.read_config},
                lambda c: c.parallel_block, cohere2_moe,
                window_counts=cohere2_moe.WINDOW_COUNTS, pool_by_kind=True),
    # models/llama.py's three forms: a pool a kind of layer, generation
    # by blocks, and everything else
    _llama("llama_by_kind", {"smallthinker": config.read_smallthinker},
           lambda c: c.kv_pool_by_kind, pool_by_kind=True),
    _llama("llama_by_blocks", {"sdar_moe": config.read_sdar_moe},
           lambda c: c.block_length > 1, by_blocks=True),
    _llama("llama", {"llama": config.read_llama,
                     "mistral": config.read_llama,
                     "mixtral": config.read_mixtral,
                     "qwen2": config.read_qwen2,
                     "qwen3": config.read_qwen3,
                     "qwen3_moe": config.read_qwen3_moe,
                     "gemma": config.read_gemma,
                     "gemma2": config.read_gemma2},
           lambda c: True),
)


def family_of(cfg: ModelConfig) -> ModelFamily:
    """The first family of the table that ``cfg`` is of."""
    return next(f for f in FAMILIES if f.has(cfg))


def get_model_module(cfg: ModelConfig) -> ModuleType:
    return family_of(cfg).module


def reader_of(model_type: str) -> Callable:
    """The reader of the family that claims ``model_type``."""
    for f in FAMILIES:
        if model_type in f.readers:
            return f.readers[model_type]
    claimed = sorted(t for f in FAMILIES for t in f.readers)
    raise NotImplementedError(
        f"model_type {model_type!r} is claimed by no family of "
        f"models/registry.py FAMILIES (claimed: {', '.join(claimed)}); "
        f"it is not read as llama")


# ------------------------------------------------------------- refusals
#
# capability -> (whether a family has it, the sentence that refuses it
# {what} because {why}). Each (feature, capability) of REFUSALS that holds
# a reason is an item of ROADMAP queue B (B10, B7, B6).
CAPABILITIES = {
    "by_blocks": (
        lambda f: f.by_blocks,
        "{what} is not supported for a model that generates by diffusion "
        "over blocks (block_length > 1, models/llama.py "
        "_make_block_window_fn): {why}; its step yields a block a row, "
        "and only JaxEngine's window arm on one device keeps the books of "
        "that (ROADMAP B10)"),
    "state": (
        lambda f: f.init_state is not None,
        "{what} is not supported for a model with recurrent state ("
        + ", ".join(f"models/{f.module.__name__.rpartition('.')[2]}.py"
                    for f in reversed(FAMILIES) if f.init_state is not None)
        + "): {why}; a state snapshot lives in the device pool under its "
        "page's id, or not at all, and nothing moves or rolls back a "
        "state (ROADMAP B7)"),
    "pool_by_kind": (
        lambda f: f.pool_by_kind,
        "{what} is not supported for a model whose window layers keep a "
        "K/V pool of their own (kv_pool_by_kind: models/llama.py "
        "_forward_by_kind, WindowPagePool): {why}; the window layers' "
        "pages behind a row's window are given back while the row runs, "
        "and only JaxEngine's own steps on one device keep the books of "
        "both pools (ROADMAP B6)"),
}

# what the three classes that move KV pages between places are refused
_MOVES_PAGES = dict(
    state="it moves KV pages between places, and a sequence's pages "
    "without its state are not the sequence",
    by_blocks="it hands a sequence over as its pages and the first token "
    "that prefill sampled, and here prefill samples none and the pages "
    "hold whole blocks only",
    pool_by_kind="it moves a sequence as the pages of one pool, and the "
    "window layers' pages of the same positions are in another pool or "
    "already given back")

# feature -> (what it is called, why it is refused a capability); a
# capability a feature does not name is served
REFUSALS = {
    "host_pages": (
        "the host KV tier (host_pages > 0, with or without kv_compress)",
        dict(state="a page restored from the host comes without the state "
             "that goes with it",
             by_blocks="no test shows a page restored from the host against "
             "the block mask's invariant (a page holds whole blocks)",
             pool_by_kind="a page restored from the host is a page of the "
             "full layers' pool alone")),
    "spec_decode": (
        "spec_decode",
        dict(state="a rejected draft token has already advanced the state, "
             "which cannot be rolled back",
             by_blocks="the verify forward scores one drafted token a "
             "position under the causal mask, and a block is not drafted "
             "token by token",
             pool_by_kind="the verify forward writes drafted tokens' K/V "
             "through one page table")),
    "long_prefill_threshold": (
        "long_prefill_threshold (ring-attention prefill)",
        dict(by_blocks="the ring's position predicates are causal",
             pool_by_kind="the ring scatters a prompt's K/V into one pool")),
    "mesh": (
        "a mesh of more than one device",
        dict(state="no sharding rule places the state pools or the leaves "
             "of the layers that keep state",
             by_blocks="the block window folds a block's queries into the "
             "decode kernel's group axis and has no shard_map form",
             pool_by_kind="no sharding rule places the window layers' "
             "pools, and the shard_map wrappers take one table a row")),
    "disagg_prefill": ("a disaggregated prefill worker", _MOVES_PAGES),
    "disagg_decode": ("a disaggregated decode engine", _MOVES_PAGES),
    "kv_transfer": ("the KV transfer server", _MOVES_PAGES),
    # the one refusal made by the request, not at construction
    "sampling_penalty": (
        "a sampling penalty or logit_bias",
        dict(by_blocks="the counts of a row's tokens change inside a "
             "block, between the forwards that make its positions final, "
             "and the window keeps no such state")),
}
