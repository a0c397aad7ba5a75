"""Model configuration: the fields every family's code reads, and the
readers of models/llama.py's own kinds of ``config.json`` (incl.
DeepSeek-R1-Distill-Llama, the reference's flagship example model,
examples/llm/configs/agg.yaml). ``from_hf_config`` maps a HuggingFace
``config.json`` dict through the reader its ``model_type`` gets in
models/registry.py.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp


REMASKING_STRATEGIES = ("sequential", "low_confidence_static",
                        "low_confidence_dynamic")


@dataclass
class ModelConfig:
    model_type: str = "llama"
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    rope_theta: float = 500000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # MoE (Mixtral-style); num_experts=0 → dense
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # MLA (DeepSeek-V2/V3 multi-head latent attention); kv_lora_rank>0
    # switches the attention/KV-cache design (models/mla.py)
    q_lora_rank: int = 0           # 0 = full-rank q projection
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    attn_bias: bool = False        # qkv projection bias (Qwen2-style)
    qk_norm: bool = False          # per-head RMSNorm on q/k pre-RoPE (Qwen3)
    # DeepSeek-MoE (V2/V3): dense first-k layers, shared experts riding
    # beside the routed ones, and family-specific routing — "deepseek_v2"
    # (softmax scores, optional max-per-group limiting, scale) or
    # "deepseek_v3" (sigmoid scores + selection bias, top-2-sum groups,
    # optional renorm, scale). moe_intermediate_size is the EXPERT width;
    # intermediate_size stays the dense-layer width.
    moe_router: str = "mixtral"
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0
    moe_intermediate_size: Optional[int] = None
    routed_scaling_factor: float = 1.0
    n_group: int = 0               # 0 = no group-limited routing
    topk_group: int = 0
    norm_topk_prob: bool = False
    # what the renormalisation of the chosen sigmoid scores adds to their
    # sum (DeepSeek-V3's 1e-20; LFM2's 1e-6)
    moe_renorm_eps: float = 1e-20
    # real DeepSeek checkpoints store rope dims INTERLEAVED (pairs
    # (2i, 2i+1)); the loader permutes those weight columns to our
    # split-half rope convention (scores are permutation-invariant)
    rope_interleave: bool = False
    # Gemma-family knobs (model_type "gemma"/"gemma2"): scaled embeddings,
    # (1 + w) RMSNorm, GeGLU activation, explicit attention scale, and the
    # Gemma-2 final-logit softcap
    embed_scale: bool = False      # multiply embeddings by sqrt(hidden)
    norm_unit_offset: bool = False  # rms_norm weight is (1 + w)
    hidden_act: str = "silu"   # "silu" | "gelu_tanh" | "relu" | "relu2"
    query_pre_attn_scalar: Optional[float] = None  # attn scale override
    final_logit_softcap: Optional[float] = None
    # Gemma-2 only: sandwich norms (post-attention + pre/post-feedforward
    # norms around each residual add), tanh softcap on attention logits,
    # and sliding-window attention on even-indexed layers
    sandwich_norms: bool = False
    attn_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    # What each layer's attention may see and whether it rotates q and k,
    # a layer at a time: layer_window[l] is the window of layer l (position
    # j is visible from t iff t - window < j <= t) or None where the layer
    # sees every earlier position; layer_rope[l] is False where the layer
    # applies no positional embedding. () = every layer sees everything
    # and rotates. Gemma-2's even-layer rule is one such layout, filled
    # from sliding_window where none is given (__post_init__); model_type
    # "smallthinker" states both (sliding_window_layout, rope_layout).
    layer_window: tuple = ()
    layer_rope: tuple = ()
    # SmallThinker: layers of a kind share a K/V pool of their own
    # (models/llama.py init_kv_cache, engine/kv_manager.py WindowPagePool):
    # the window layers' pool keeps only the pages a row can still see.
    # Set by the configuration's family, not a switch: Gemma-2 keeps one
    # pool under a mask (its prefix cache, host tier and mesh are built
    # on one pool).
    kv_pool_by_kind: bool = False
    # SmallThinker: the router reads the layer's un-normed input, before
    # attention (its logits are made at the layer's entry)
    moe_early_router: bool = False
    # Cohere2-MoE (Command A+: models/cohere2_moe.py on llama.py's
    # by-kind path): a PARALLEL block (one norm a layer, attention and
    # the second half both read it and are added to the
    # residual stream once; no ln_mlp), a LayerNorm without bias (the
    # mean subtracted; its epsilon is kept in rms_norm_eps), the rotation
    # over interleaved pairs (rope_interleave: in llama.py's family the
    # pairs (2i, 2i + 1) are rotated as they lie; mla.py's loader
    # permutes its columns instead), and shared experts whose summed
    # output is multiplied by shared_expert_scale (1 / num_shared_experts
    # where they are AVERAGED).
    parallel_block: bool = False
    layer_norm: bool = False
    shared_expert_scale: float = 1.0
    # Jamba (models/jamba.py): Mamba-1 mixers in every layer but those
    # at attn_layer_offset + k * attn_layer_period, which attend (no
    # positional embedding of any kind).
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # LFM2 (models/lfm2.py): each layer's operator by name, "conv" (a
    # gated short convolution of conv_l_cache taps, whose per-sequence
    # state is the last conv_l_cache - 1 gated inputs) or
    # "full_attention"; the first num_dense_layers layers have a dense
    # MLP, the others routed experts.
    layer_types: tuple = ()
    conv_l_cache: int = 3
    num_dense_layers: int = 0
    # Granite 4.0-H (models/granite.py): layer_types names each layer
    # "mamba" or "attention" (no positional
    # embedding); a Mamba-2 mixer has mamba_n_heads heads of mamba_d_head
    # channels, each with a [mamba_d_head, mamba_d_state] matrix of state
    # and ONE scalar decay, and B and C shared by the heads of a group
    # (mamba_n_groups of them; granite-4.0-h-small has one);
    # mamba_chunk_size is the chunk of the matmul form a prompt runs.
    # Every layer's second half is routed experts (top-k of the router's
    # outputs, softmax over the chosen) plus one shared expert of
    # shared_intermediate_size. Four multipliers: the embedding's, the
    # attention scores' (in place of 1/sqrt(head_dim)), the one on what
    # every block adds to the residual stream, and the divisor of the
    # logits.
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    shared_intermediate_size: int = 0
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The chip's share of a layer's experts (expert parallelism on one
    # of its chips): num_experts is how many are HELD here, the router
    # keeps router_experts outputs (0 = num_experts: every expert is
    # here) and the held ones are [first_expert, first_expert +
    # num_experts). A pair routed elsewhere is not computed, and nothing
    # stands in for it.
    router_experts: int = 0
    first_expert: int = 0
    # Nemotron-H (models/nemotron_h.py): layer_types names each layer
    # "mamba" (granite.py's Mamba-2 mixer), "attention" or "moe", and a
    # layer is that ONE sub-block under its own norm. An expert is not
    # gated (hidden_act "relu2": relu(x W_up)^2 W_down, two matrices) and
    # the routed ones work at moe_latent_size: the layer projects its
    # input down once a token and the experts' weighted sum up again;
    # the router and the shared expert read the full width.
    moe_latent_size: int = 0
    # Kimi Linear (models/kimi_linear.py): layer_types names each layer
    # "kda" or "attention". A KDA mixer
    # (Kimi Delta Attention: a gated delta rule) has kda_n_heads heads,
    # each with a [kda_head_dim, kda_head_dim] float32 matrix of state
    # that decays A KEY CHANNEL and is corrected by a rank-1 delta a
    # token; q, k and v each pass a causal depthwise convolution of
    # mamba_d_conv taps (the conv tails' pool has jamba.py's ranks).
    # kda_chunk_size is the chunk of the XLA arm of the matmul form a
    # prompt runs (off the chip, and the chunk kernel's reference; the
    # program's choice, in no published file: where the kernels run a
    # prompt runs ops/kda.py kda_chunk, whose chunk is a constant of that
    # file). An attending layer is
    # MLA (the latent ranks above) and, with mla_nope, applies no
    # rotation to the qk_rope_head_dim shared columns.
    kda_n_heads: int = 0
    kda_head_dim: int = 128
    kda_chunk_size: int = 16
    mla_nope: bool = False
    # Solar Open 2 (models/solar_open2.py): the same KDA mixer beside
    # GQA layers without positions (K/V pages a KV
    # head, kv_lora_rank 0) whose output passes an elementwise sigmoid
    # gate of the layer's normed input (the leaf ``wg``, always there).
    # kda_beta_scale multiplies the delta rule's sigmoid beta: 2
    # where the configuration allows negative eigenvalues (beta in (0, 2):
    # the transition Diag(exp(g)) (I - beta k k^T) may have an eigenvalue
    # in (-1, 0)), 1 otherwise.
    kda_beta_scale: float = 1.0
    # LongCat-Flash (models/longcat_flash.py): a layer is TWO latent
    # attentions and two dense MLPs with ONE routed MoE that reads the
    # first sub-block's post-attention norm and is added after the
    # second sub-block's MLP (the pools have 2 x num_layers entries).
    # mla_q_scale multiplies the queries the query LoRA makes,
    # mla_kv_scale the normed latent (what the cache keeps): 1.0 = the
    # DeepSeek form as it is. The last zero_experts outputs of the router
    # are identity experts (no weights: a pair's "output" is the token
    # itself times its gate weight); router_experts counts them, the
    # real outputs are the identity_from before them.
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    zero_experts: int = 0
    # Generation by diffusion over blocks (model_type "sdar_moe"): the
    # attention mask is causal across blocks of block_length positions and
    # bidirectional inside one, the logits at a position are the
    # distribution of the token AT it (no shift), and text is generated a
    # block at a time: the block starts as mask_token_id, each denoising
    # forward makes some of its positions final (remasking_strategy:
    # "sequential", "low_confidence_static" or "low_confidence_dynamic"
    # with confidence_threshold), ceil(masked / denoising_steps) a
    # forward. block_length > 1 switches the decode window's program
    # (models/llama.py) and the engine's bookkeeping: a step then yields a
    # block a row, not a token.
    block_length: int = 1
    mask_token_id: int = 0
    denoising_steps: int = 1
    remasking_strategy: str = "sequential"
    confidence_threshold: float = 0.9
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.sliding_window is not None and not self.layer_window:
            # Gemma-2: the window on even-indexed layers (HF
            # Gemma2DecoderLayer ``is_sliding = not bool(layer_idx % 2)``)
            self.layer_window = tuple(
                None if l % 2 else self.sliding_window
                for l in range(self.num_layers))

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def window_layer_ids(self) -> tuple:
        """Layers whose attention is held to a window."""
        return tuple(l for l, w in enumerate(self.layer_window)
                     if w is not None)

    @property
    def full_layer_ids(self) -> tuple:
        """Layers whose attention sees every earlier position."""
        held = set(self.window_layer_ids)
        return tuple(l for l in range(self.num_layers) if l not in held)

    def rotates(self, l: int) -> bool:
        """Whether layer l applies the rotary embedding."""
        return not self.layer_rope or bool(self.layer_rope[l])

    @property
    def has_recurrent_state(self) -> bool:
        """Whether the family this configuration is of keeps per-sequence
        state beside its KV pages (its record declares ``init_state``)."""
        from .registry import family_of

        return family_of(self).init_state is not None

    def check_page_size(self, page_size: int) -> None:
        """Refuse a KV page that a block of the mask straddles: a
        position's K/V depends on every token up to the end of its block,
        so a page's content is a function of the prefix up to the page's
        end (what the prefix cache hashes) only where block_length
        divides page_size."""
        if page_size % self.block_length:
            raise ValueError(
                f"block_length ({self.block_length}) must divide page_size "
                f"({page_size}): a page whose last block runs into the "
                f"next page holds K/V that depends on tokens past the "
                f"page's end, and a prefix hit on it would not be exact")

    @property
    def router_width(self) -> int:
        """Outputs of the MoE router: the published count of experts,
        whatever share of them is held here."""
        return self.router_experts or self.num_experts

    @property
    def identity_from(self) -> int:
        """The router output from which on an index names an identity
        (zero-compute) expert; 0 where the router has none."""
        return (self.router_width - self.zero_experts
                if self.zero_experts else 0)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def attn_layer_ids(self) -> tuple:
        """Layers whose mixer is attention, for a model whose layers are
        of two kinds (every layer attends otherwise)."""
        if self.layer_types:
            return tuple(l for l, kind in enumerate(self.layer_types)
                         if kind in ("full_attention", "attention"))
        if not self.has_recurrent_state:
            return tuple(range(self.num_layers))
        return tuple(l for l in range(self.num_layers)
                     if (l - self.attn_layer_offset)
                     % self.attn_layer_period == 0)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def attn_scale(self) -> float:
        """Attention logit scale: 1/sqrt(head_dim) unless the config pins
        a different denominator (Gemma-2's query_pre_attn_scalar) or the
        scale itself (Granite's attention_multiplier)."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        denom = self.query_pre_attn_scalar or self.head_dim_
        return 1.0 / (denom ** 0.5)

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "float16": jnp.float16}[self.dtype]

    @classmethod
    def from_hf_config(cls, cfg: dict) -> "ModelConfig":
        """A ``config.json`` read by the family that claims its
        ``model_type`` (models/registry.py FAMILIES; absent: ``llama``).
        A type no family claims is refused, never read as Llama."""
        from .registry import reader_of

        return reader_of(cfg.get("model_type", "llama"))(cfg)

    @classmethod
    def from_local_path(cls, path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return cls.from_hf_config(json.load(f))

    @classmethod
    def tiny(cls, **overrides) -> "ModelConfig":
        """A CPU-testable configuration (vocab matches ByteTokenizer)."""
        base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                    rope_theta=10000.0, dtype="float32")
        base.update(overrides)
        return cls(**base)

    @classmethod
    def llama3_8b(cls) -> "ModelConfig":
        return cls()  # defaults above are Llama-3-8B


# ------------------------------------------------- reading a config.json
#
# What every reader starts from, what several share, and the readers of
# models/llama.py's own kinds, one function a kind. A family with a module
# of its own keeps its reader there (``read_config``), beside the code
# that reads its fields; models/registry.py FAMILIES says which reader a
# ``model_type`` gets.


def hf_base(cfg: dict, width_key: str = "intermediate_size") -> ModelConfig:
    """The keys a decoder's config.json has whatever its family."""
    return ModelConfig(
        model_type="llama",
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg[width_key],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg.get("num_key_value_heads",
                             cfg["num_attention_heads"]),
        head_dim=cfg.get("head_dim"),
        rope_theta=cfg.get("rope_theta", 10000.0),
        rope_scaling=cfg.get("rope_scaling"),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
    )


def refuser(model_type: str):
    """``refuse(what, why)`` of a reader: the one sentence a key this
    program does not compute is refused by."""
    def refuse(what: str, why: str):
        raise NotImplementedError(
            f"{model_type} with {what} is not supported ({why})")
    return refuse


def held_experts(cfg: dict, held_key: str, topk_key: str, refuse) -> tuple:
    """(held, router's width, first held) of a file that may be cut to a
    chip's share of its experts: ``held_key`` counts the experts HELD, and
    such a file names the published count (``router_num_experts``) and
    the first expert held (``first_local_expert``) beside it."""
    held = cfg[held_key]
    width = cfg.get("router_num_experts", held)
    first = cfg.get("first_local_expert", 0)
    if not 0 <= first <= width - held:
        refuse(f"first_local_expert {first}",
               f"the {held} experts held must lie inside the router's "
               f"{width}")
    if cfg[topk_key] > width:
        refuse(f"{topk_key} {cfg[topk_key]}",
               f"the router has {width} outputs")
    return held, width, first


def read_llama(cfg: dict) -> ModelConfig:
    """``llama``, and ``mistral``, whose decoder has the same shape."""
    return hf_base(cfg)


def read_mixtral(cfg: dict) -> ModelConfig:
    c = hf_base(cfg)
    c.model_type = "mixtral"
    c.num_experts = cfg.get("num_local_experts", 8)
    c.num_experts_per_tok = cfg.get("num_experts_per_tok", 2)
    return c


def read_qwen2(cfg: dict) -> ModelConfig:
    """Llama's decoder (GQA + SwiGLU) with a bias on q, k and v."""
    c = hf_base(cfg)
    c.attn_bias = True
    return c


def read_qwen3(cfg: dict) -> ModelConfig:
    """Llama GQA + per-head q/k RMSNorm (no qkv bias)."""
    c = hf_base(cfg)
    c.model_type = "qwen3"
    c.qk_norm = True
    return c


def _qwen3_experts(c: ModelConfig, cfg: dict, mt: str) -> ModelConfig:
    """Qwen3-MoE's experts: routed Mixtral-style (softmax-then-top-k ==
    top-k-then-softmax after renorm) with their own width."""
    if not cfg.get("norm_topk_prob", False):
        # our dense-over-experts MoE normalizes the top-k weights (softmax
        # over the selected logits); the un-renormalized variant would
        # silently diverge
        raise NotImplementedError(
            f"{mt} with norm_topk_prob=false is not supported (router "
            "weights are renormalized)")
    if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
        # every layer is treated as MoE; interleaved dense layers would
        # need per-layer MLP selection
        raise NotImplementedError(
            f"{mt} with dense layers interleaved (decoder_sparse_step != 1 "
            "or mlp_only_layers) is not supported")
    c.num_experts = cfg.get("num_experts", 128)
    c.num_experts_per_tok = cfg.get("num_experts_per_tok", 8)
    c.intermediate_size = cfg["moe_intermediate_size"]
    return c


def read_qwen3_moe(cfg: dict) -> ModelConfig:
    return _qwen3_experts(read_qwen3(cfg), cfg, "qwen3_moe")


def read_sdar_moe(cfg: dict) -> ModelConfig:
    """The Qwen3-MoE layer under a block mask, generated by diffusion over
    blocks; the generation keys are the family's published defaults where
    the file leaves them out."""
    c = read_qwen3(cfg)
    c.model_type = "sdar_moe"
    c.block_length = int(cfg.get("block_length", 4))
    c.mask_token_id = int(cfg.get("mask_token_id", 151669))
    c.denoising_steps = int(cfg.get("denoising_steps", c.block_length))
    c.remasking_strategy = cfg.get("remasking_strategy",
                                   "low_confidence_dynamic")
    c.confidence_threshold = float(cfg.get("confidence_threshold", 0.9))
    if c.remasking_strategy not in REMASKING_STRATEGIES:
        raise NotImplementedError(
            f"sdar_moe: remasking_strategy {c.remasking_strategy!r} is "
            f"not one of {REMASKING_STRATEGIES}")
    if c.block_length < 1 or c.denoising_steps < 1:
        raise ValueError(
            "sdar_moe: block_length and denoising_steps must be at least 1")
    return _qwen3_experts(c, cfg, "sdar_moe")


def read_gemma(cfg: dict) -> ModelConfig:
    """Gemma rides the Llama GQA stack with four semantic switches."""
    c = hf_base(cfg)
    c.model_type = "gemma"
    c.embed_scale = True
    c.norm_unit_offset = True
    c.hidden_act = "gelu_tanh"
    c.tie_word_embeddings = cfg.get("tie_word_embeddings", True)
    return c


def read_gemma2(cfg: dict) -> ModelConfig:
    """Gemma plus sandwich norms (post-attention norm on the attention
    output, pre/post-feedforward norms), sliding-window attention on even
    layers, logit softcaps, and an explicit attention-scale denominator."""
    c = read_gemma(cfg)
    c.model_type = "gemma2"
    c.sandwich_norms = True
    c.sliding_window = cfg.get("sliding_window", 4096)
    c.__post_init__()   # the even-layer rule
    c.attn_logit_softcap = cfg.get("attn_logit_softcapping")
    c.final_logit_softcap = cfg.get("final_logit_softcapping")
    c.query_pre_attn_scalar = cfg.get("query_pre_attn_scalar")
    return c


def read_smallthinker(cfg: dict) -> ModelConfig:
    """Primary experts only (the file names their width alone), a softmax
    over the chosen, ReGLU, and the two per-layer layouts, which must name
    the same layers (a window layer rotates, a full layer applies no
    positional embedding)."""
    refuse = refuser("smallthinker")
    c = hf_base(cfg, "moe_ffn_hidden_size")
    L = cfg["num_hidden_layers"]
    win = list(cfg["sliding_window_layout"][:L])
    rope = list(cfg["rope_layout"][:L])
    if len(win) != L or len(rope) != L:
        refuse(f"layouts of {len(win)} / {len(rope)} entries",
               f"sliding_window_layout and rope_layout must each "
               f"name num_hidden_layers = {L} layers")
    if [bool(w) for w in win] != [bool(r) for r in rope]:
        refuse("sliding_window_layout != rope_layout",
               "a window layer rotates q and k and a full layer "
               "applies no positional embedding: the two pools hold "
               "K by that rule, and no layer of a third kind exists")
    if not any(win) or all(win):
        refuse("layers of one kind only",
               "the K/V pools are one a kind of layer; a model whose "
               "layers all see the same is model_type llama")
    for key in ("moe_num_secondary_experts",
                "moe_num_active_secondary_experts",
                "moe_secondary_ffn_hidden_size"):
        if cfg.get(key):
            refuse(f"{key} = {cfg[key]}",
                   "secondary experts are not computed; the published "
                   "configuration has primary experts only")
    if not (cfg.get("moe_primary_router_apply_softmax", True)
            and cfg.get("norm_topk_prob", True)):
        refuse("moe_primary_router_apply_softmax or norm_topk_prob "
               "false", "the routing weights are a softmax over the "
               "chosen experts' logits")
    act = cfg.get("hidden_act", "relu")
    if act != "relu":
        refuse(f"hidden_act {act!r}", "its experts are ReGLU")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "the window layers rotate by rope_theta "
               "alone")
    window = int(cfg["sliding_window_size"])
    c.model_type = "smallthinker"
    c.sliding_window = window
    c.layer_window = tuple(window if w else None for w in win)
    c.layer_rope = tuple(bool(r) for r in rope)
    c.kv_pool_by_kind = True
    c.moe_early_router = True
    c.hidden_act = "relu"
    c.num_experts = cfg["moe_num_primary_experts"]
    c.num_experts_per_tok = cfg["moe_num_active_primary_experts"]
    return c
