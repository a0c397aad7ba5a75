"""Model configuration.

Covers the Llama family (incl. DeepSeek-R1-Distill-Llama — the reference's
flagship example model, examples/llm/configs/agg.yaml) and Mixtral-style MoE.
``from_hf_config`` maps a HuggingFace ``config.json`` dict.
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
    hidden_act: str = "silu"       # "silu" | "gelu_tanh" | "relu"
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
    # Cohere2-MoE (model_type "cohere2_moe", models/cohere2_moe.py on
    # llama.py's by-kind path): a PARALLEL block (one norm a layer,
    # attention and the second half both read it and are added to the
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
    # Jamba (model_type "jamba", models/jamba.py): Mamba-1 mixers in
    # every layer but those at attn_layer_offset + k * attn_layer_period,
    # which attend (no positional embedding of any kind). mamba_d_state
    # > 0 switches the model module and gives the engine a pool of
    # per-sequence recurrent state beside the KV pages.
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # LFM2 (model_type "lfm2_moe", models/lfm2.py): each layer's operator
    # by name, "conv" (a gated short convolution of conv_l_cache taps,
    # whose per-sequence state is the last conv_l_cache - 1 gated inputs)
    # or "full_attention"; the first num_dense_layers layers have a dense
    # MLP, the others routed experts. A non-empty layer_types switches the
    # model module.
    layer_types: tuple = ()
    conv_l_cache: int = 3
    num_dense_layers: int = 0
    # Granite 4.0-H (model_type "granitemoehybrid", models/granite.py):
    # layer_types names each layer "mamba" or "attention" (no positional
    # embedding); a Mamba-2 mixer has mamba_n_heads heads of mamba_d_head
    # channels, each with a [mamba_d_head, mamba_d_state] matrix of state
    # and ONE scalar decay, and B and C shared by all heads
    # (one group: more is refused); mamba_chunk_size is the chunk of the matmul
    # form a prompt runs. mamba_n_heads > 0 switches the model module.
    # Every layer's second half is routed experts (top-k of the router's
    # outputs, softmax over the chosen) plus one shared expert of
    # shared_intermediate_size. Four multipliers: the embedding's, the
    # attention scores' (in place of 1/sqrt(head_dim)), the one on what
    # every block adds to the residual stream, and the divisor of the
    # logits.
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
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
    # Kimi Linear (model_type "kimi_linear", models/kimi_linear.py):
    # layer_types names each layer "kda" or "attention". A KDA mixer
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
    # rotation to the qk_rope_head_dim shared columns. kda_n_heads > 0
    # switches the model module.
    kda_n_heads: int = 0
    kda_head_dim: int = 128
    kda_chunk_size: int = 16
    mla_nope: bool = False
    # Solar Open 2 (model_type "solar_open2", models/solar_open2.py): the
    # same KDA mixer beside GQA layers without positions (K/V pages a KV
    # head, kv_lora_rank 0) whose output passes an elementwise sigmoid
    # gate of the layer's normed input (the leaf ``wg``, always there).
    # kda_beta_scale multiplies the delta rule's sigmoid beta: 2
    # where the configuration allows negative eigenvalues (beta in (0, 2):
    # the transition Diag(exp(g)) (I - beta k k^T) may have an eigenvalue
    # in (-1, 0)), 1 otherwise.
    kda_beta_scale: float = 1.0
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
        return (self.mamba_d_state > 0 or "conv" in self.layer_types
                or self.kda_n_heads > 0)

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
        mt = cfg.get("model_type", "llama")
        if mt == "cohere2_moe":
            # nothing of this family may be read through the Llama
            # defaults below (sliding_window would take Gemma-2's rule)
            return cls._read_cohere2_moe(cfg)
        c = cls(
            model_type="mixtral" if mt == "mixtral" else "llama",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            # SmallThinker has experts only, and names their width alone
            intermediate_size=cfg["moe_ffn_hidden_size" if mt ==
                                  "smallthinker" else "intermediate_size"],
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
        if mt == "mixtral":
            c.num_experts = cfg.get("num_local_experts", 8)
            c.num_experts_per_tok = cfg.get("num_experts_per_tok", 2)
        if mt in ("deepseek_v2", "deepseek_v3"):
            c.model_type = mt
            c.q_lora_rank = cfg.get("q_lora_rank") or 0
            c.kv_lora_rank = cfg.get("kv_lora_rank", 512)
            c.qk_nope_head_dim = cfg.get("qk_nope_head_dim", 128)
            c.qk_rope_head_dim = cfg.get("qk_rope_head_dim", 64)
            c.v_head_dim = cfg.get("v_head_dim", 128)
            c.num_experts = cfg.get("n_routed_experts") or 0
            c.num_experts_per_tok = cfg.get("num_experts_per_tok", 2)
            c.rope_interleave = cfg.get("rope_interleave", True)
            if c.num_experts > 0:
                c.moe_router = mt
                c.n_shared_experts = cfg.get("n_shared_experts") or 0
                c.first_k_dense_replace = cfg.get("first_k_dense_replace",
                                                  0)
                c.moe_intermediate_size = cfg.get("moe_intermediate_size")
                c.routed_scaling_factor = cfg.get("routed_scaling_factor",
                                                  1.0)
                c.norm_topk_prob = cfg.get("norm_topk_prob", False)
                if mt == "deepseek_v2" and c.norm_topk_prob:
                    # The installed transformers DeepseekV2MoEGate ignores
                    # this flag (always scales, never renormalizes) while
                    # DeepSeek's remote-code gate renormalizes instead of
                    # scaling — two conflicting oracles, and no published
                    # V2 checkpoint sets it. Reject loudly rather than
                    # silently diverging from either.
                    raise NotImplementedError(
                        "deepseek_v2 with norm_topk_prob=true is not "
                        "supported (conflicting reference semantics)")
                if mt == "deepseek_v3" or cfg.get(
                        "topk_method", "greedy") != "greedy":
                    # v2 "greedy" routes without group limiting; v3 is
                    # always group-limited (noaux_tc)
                    c.n_group = cfg.get("n_group") or 0
                    c.topk_group = cfg.get("topk_group") or 0
        if mt == "qwen2":
            c.model_type = "llama"  # same decoder shape (GQA + SwiGLU)
            c.attn_bias = True      # qwen2 keeps bias on q/k/v projections
        if mt in ("qwen3", "qwen3_moe", "sdar_moe"):
            # Qwen3 = Llama GQA + per-head q/k RMSNorm (no qkv bias);
            # the MoE variant routes Mixtral-style (softmax-then-top-k ==
            # top-k-then-softmax after renorm) with its own expert width
            c.model_type = "qwen3"
            c.qk_norm = True
            if mt == "sdar_moe":
                # the Qwen3-MoE layer under a block mask, generated by
                # diffusion over blocks; the four generation keys are the
                # family's published defaults where the file leaves them
                # out
                c.model_type = "sdar_moe"
                c.block_length = int(cfg.get("block_length", 4))
                c.mask_token_id = int(cfg.get("mask_token_id", 151669))
                c.denoising_steps = int(cfg.get("denoising_steps",
                                                c.block_length))
                c.remasking_strategy = cfg.get("remasking_strategy",
                                               "low_confidence_dynamic")
                c.confidence_threshold = float(
                    cfg.get("confidence_threshold", 0.9))
                if c.remasking_strategy not in REMASKING_STRATEGIES:
                    raise NotImplementedError(
                        f"sdar_moe: remasking_strategy "
                        f"{c.remasking_strategy!r} is not one of "
                        f"{REMASKING_STRATEGIES}")
                if c.block_length < 1 or c.denoising_steps < 1:
                    raise ValueError(
                        "sdar_moe: block_length and denoising_steps must "
                        "be at least 1")
            if mt in ("qwen3_moe", "sdar_moe"):
                if not cfg.get("norm_topk_prob", False):
                    # our dense-over-experts MoE normalizes the top-k
                    # weights (softmax over the selected logits); the
                    # un-renormalized variant would silently diverge
                    raise NotImplementedError(
                        f"{mt} with norm_topk_prob=false is not "
                        "supported (router weights are renormalized)")
                if (cfg.get("decoder_sparse_step", 1) != 1
                        or cfg.get("mlp_only_layers")):
                    # every layer is treated as MoE; interleaved dense
                    # layers would need per-layer MLP selection
                    raise NotImplementedError(
                        f"{mt} with dense layers interleaved "
                        "(decoder_sparse_step != 1 or mlp_only_layers) "
                        "is not supported")
                c.num_experts = cfg.get("num_experts", 128)
                c.num_experts_per_tok = cfg.get("num_experts_per_tok", 8)
                c.intermediate_size = cfg["moe_intermediate_size"]
        if mt == "jamba":
            if (cfg.get("num_experts") or 1) > 1:
                # every layer's MLP is dense here; the expert_layer_*
                # keys would select routed layers
                raise NotImplementedError(
                    "jamba with num_experts > 1 is not supported (every "
                    "layer's MLP is computed dense)")
            if cfg.get("sliding_window"):
                raise NotImplementedError(
                    "jamba with sliding_window set is not supported (its "
                    "attention layers attend to the whole context)")
            c.model_type = "jamba"
            c.mamba_d_state = cfg.get("mamba_d_state", 16)
            c.mamba_d_conv = cfg.get("mamba_d_conv", 4)
            c.mamba_expand = cfg.get("mamba_expand", 2)
            c.mamba_dt_rank = (cfg.get("mamba_dt_rank")
                               or -(-cfg["hidden_size"] // 16))
            c.attn_layer_period = cfg.get("attn_layer_period", 8)
            c.attn_layer_offset = cfg.get("attn_layer_offset", 4)
            c.rms_norm_eps = cfg.get("rms_norm_eps", 1e-6)
        if mt == "lfm2_moe":
            kinds = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
            odd = sorted(set(kinds) - {"conv", "full_attention"})
            if odd or len(kinds) != cfg["num_hidden_layers"]:
                raise NotImplementedError(
                    "lfm2_moe: layer_types must name num_hidden_layers "
                    "layers, each conv or full_attention (got "
                    f"{odd or len(kinds)})")
            if cfg.get("conv_bias"):
                raise NotImplementedError(
                    "lfm2_moe with conv_bias true is not supported (the "
                    "short convolution is computed without a bias)")
            if not (cfg.get("use_expert_bias", True)
                    and cfg.get("norm_topk_prob", True)):
                raise NotImplementedError(
                    "lfm2_moe without use_expert_bias or norm_topk_prob "
                    "is not supported (the gate selects by score + bias "
                    "and renormalises the chosen scores)")
            rope = cfg.get("rope_parameters") or {}
            c.model_type = "lfm2_moe"
            # a cut in depth keeps the published list whole: the first
            # num_hidden_layers entries are the layers that run
            c.layer_types = kinds
            c.conv_l_cache = cfg.get("conv_L_cache", 3)
            c.num_dense_layers = cfg.get("num_dense_layers", 0)
            c.rms_norm_eps = cfg.get("norm_eps", 1e-5)
            c.rope_theta = rope.get("rope_theta",
                                    cfg.get("rope_theta", 1000000.0))
            c.qk_norm = True
            c.num_experts = cfg.get("num_experts", 0)
            c.num_experts_per_tok = cfg.get("num_experts_per_tok", 4)
            c.moe_intermediate_size = cfg.get("moe_intermediate_size")
            # sigmoid scores, selection by score + bias, the unbiased
            # scores of the chosen renormalised: DeepSeek-V3's gate
            # without groups (models/mla.py _deepseek_gate)
            c.moe_router = "deepseek_v3"
            c.norm_topk_prob = True
            c.moe_renorm_eps = 1e-6
            c.routed_scaling_factor = cfg.get("routed_scaling_factor", 1.0)
            c.tie_word_embeddings = cfg.get("tie_word_embeddings", True)
        if mt == "granitemoehybrid":
            c._read_granite(cfg)
        if mt == "smallthinker":
            c._read_smallthinker(cfg)
        if mt == "kimi_linear":
            c._read_kimi_linear(cfg)
        if mt == "solar_open2":
            c._read_solar_open2(cfg)
        if mt in ("gemma", "gemma2"):
            # Gemma rides the Llama GQA stack with four semantic switches
            c.model_type = "gemma"
            c.embed_scale = True
            c.norm_unit_offset = True
            c.hidden_act = "gelu_tanh"
            c.tie_word_embeddings = cfg.get("tie_word_embeddings", True)
            if mt == "gemma2":
                # Gemma-2 adds sandwich norms (post-attention norm on the
                # attention output, pre/post-feedforward norms), sliding-
                # window attention on even layers, logit softcaps, and an
                # explicit attention-scale denominator
                c.model_type = "gemma2"
                c.sandwich_norms = True
                c.sliding_window = cfg.get("sliding_window", 4096)
                c.layer_window = ()
                c.__post_init__()   # the even-layer rule
                c.attn_logit_softcap = cfg.get("attn_logit_softcapping")
                c.final_logit_softcap = cfg.get("final_logit_softcapping")
                c.query_pre_attn_scalar = cfg.get("query_pre_attn_scalar")
        return c

    @classmethod
    def _read_cohere2_moe(cls, cfg: dict) -> "ModelConfig":
        """The keys of a ``cohere2_moe`` config.json (Command A+): every
        layer a parallel block under one bias-free LayerNorm; window
        layers (``layer_types`` ``sliding_attention``) rotate q and k
        over interleaved pairs, full layers apply no positional
        embedding; sigmoid-routed experts of width ``intermediate_size``
        beside ``num_shared_experts`` shared ones that are averaged.
        ``layer_types`` is kept whole in a file cut in depth: the first
        ``num_hidden_layers`` entries are the layers that run.
        ``num_experts`` is the experts HELD; a file cut to a chip's share
        names the published count (``router_num_experts``) and the first
        expert held (``first_local_expert``) beside it, as granite's,
        kimi's and solar's do."""
        def refuse(what: str, why: str):
            raise NotImplementedError(
                f"cohere2_moe with {what} is not supported ({why})")

        L = cfg["num_hidden_layers"]
        kinds = list(cfg["layer_types"][:L])
        odd = sorted(set(kinds) - {"sliding_attention", "full_attention"})
        if odd or len(kinds) != L:
            refuse(f"layer_types {odd or len(kinds)}",
                   f"it must name num_hidden_layers = {L} layers, each "
                   f"sliding_attention or full_attention")
        if len(set(kinds)) != 2:
            refuse("layers of one kind only",
                   "the K/V pools are one a kind of layer; a model whose "
                   "layers all see the same is another module's")
        if not cfg.get("use_parallel_block", False):
            refuse("use_parallel_block false",
                   "attention and the experts read ONE LayerNorm and are "
                   "added once; the family's sequential form has a second "
                   "norm that no leaf of this module holds")
        if cfg.get("use_qk_norm", False):
            refuse("use_qk_norm true",
                   "q and k are rotated as projected; the family's q/k "
                   "norm is a LayerNorm a head that is not computed")
        if cfg.get("first_k_dense_replace", 0):
            refuse(f"first_k_dense_replace {cfg['first_k_dense_replace']}",
                   "every layer's second half is routed experts beside the "
                   "shared ones; the module builds no dense MLP "
                   "(prefix_dense_intermediate_size is read by no layer)")
        if cfg.get("rotary_pct", 1) != 1:
            refuse(f"rotary_pct {cfg['rotary_pct']}",
                   "the window layers rotate all head_dim columns")
        if cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj":
            refuse(f"position_embedding_type "
                   f"{cfg['position_embedding_type']!r}",
                   "the window layers rotate interleaved pairs (rope_gptj)")
        if (cfg.get("rope_parameters") or {}).get("rope_type",
                                                  "default") != "default" \
                or cfg.get("rope_scaling"):
            refuse("a rope_type other than default",
                   "the window layers rotate by rope_theta alone")
        shared = cfg.get("num_shared_experts", 0)
        strategy = cfg.get("shared_expert_combination_strategy", "average")
        if shared and strategy != "average":
            refuse(f"shared_expert_combination_strategy {strategy!r}",
                   "the shared experts' outputs are averaged and the mean "
                   "is added to the routed sum")
        if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
            refuse(f"expert_selection_fn {cfg['expert_selection_fn']!r}",
                   "the gate scores by a sigmoid")
        if not cfg.get("norm_topk_prob", True):
            refuse("norm_topk_prob false",
                   "the chosen sigmoid scores are renormalised")
        if not cfg.get("use_gated_activation", True) \
                or cfg.get("hidden_act", "silu") != "silu":
            refuse(f"hidden_act {cfg.get('hidden_act')!r} / "
                   f"use_gated_activation "
                   f"{cfg.get('use_gated_activation')}",
                   "its experts are SwiGLU")
        if cfg.get("attention_bias", False):
            refuse("attention_bias true",
                   "the projections are computed without a bias")
        held = cfg["num_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("first_local_expert", 0)
        if not 0 <= first <= width - held:
            refuse(f"first_local_expert {first}",
                   f"the {held} experts held must lie inside the "
                   f"router's {width}")
        if cfg["num_experts_per_tok"] > width:
            refuse(f"num_experts_per_tok {cfg['num_experts_per_tok']}",
                   f"the router has {width} outputs")
        window = int(cfg["sliding_window"])
        sliding = [k == "sliding_attention" for k in kinds]
        rope = cfg.get("rope_parameters") or {}
        return cls(
            model_type="cohere2_moe",
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            # the width of ONE expert (the file has no other key for it)
            intermediate_size=cfg["intermediate_size"],
            num_layers=L,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads",
                                 cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", rope.get("rope_theta", 50000.0)),
            rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
            tie_word_embeddings=cfg.get("tie_word_embeddings", True),
            num_experts=held, router_experts=width, first_expert=first,
            num_experts_per_tok=cfg["num_experts_per_tok"],
            # sigmoid scores, the chosen renormalised; no selection bias,
            # no groups, no scaling factor (the file has no key for any)
            moe_router="deepseek_v3", norm_topk_prob=True,
            n_shared_experts=shared,
            shared_expert_scale=1.0 / shared if shared else 1.0,
            sliding_window=window,
            layer_window=tuple(window if s else None for s in sliding),
            layer_rope=tuple(sliding),
            kv_pool_by_kind=True, rope_interleave=True,
            parallel_block=True, layer_norm=True,
            # logits = logit_scale * (h @ E^T): project_logits divides
            logits_scaling=1.0 / float(cfg.get("logit_scale", 1.0)),
        )

    def _read_smallthinker(self, cfg: dict) -> None:
        """The keys of a ``smallthinker`` config.json: primary experts
        only, a softmax over the chosen, ReGLU, and the two per-layer
        layouts, which must name the same layers (a window layer rotates,
        a full layer applies no positional embedding)."""
        def refuse(what: str, why: str):
            raise NotImplementedError(
                f"smallthinker with {what} is not supported ({why})")

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
        self.model_type = "smallthinker"
        self.sliding_window = window
        self.layer_window = tuple(window if w else None for w in win)
        self.layer_rope = tuple(bool(r) for r in rope)
        self.kv_pool_by_kind = True
        self.moe_early_router = True
        self.hidden_act = "relu"
        self.num_experts = cfg["moe_num_primary_experts"]
        self.num_experts_per_tok = cfg["moe_num_active_primary_experts"]

    def _read_kimi_linear(self, cfg: dict) -> None:
        """The keys of a ``kimi_linear`` config.json. The two lists of
        ``linear_attn_config`` count layers from 1 and are kept whole in
        a file cut in depth: the entries up to ``num_hidden_layers`` are
        the layers that run. ``num_experts`` is the experts HELD; a file
        cut to a chip's share names the published count
        (``router_num_experts``) and the first expert held
        (``first_local_expert``) beside it, as granite's does."""
        def refuse(what: str, why: str):
            raise NotImplementedError(
                f"kimi_linear with {what} is not supported ({why})")

        L = cfg["num_hidden_layers"]
        lin = cfg["linear_attn_config"]
        kda = [l for l in lin["kda_layers"] if l <= L]
        full = [l for l in lin["full_attn_layers"] if l <= L]
        if sorted(kda + full) != list(range(1, L + 1)):
            refuse(f"kda_layers {kda} and full_attn_layers {full}",
                   f"up to num_hidden_layers they must partition the "
                   f"layers 1..{L}: every layer is of exactly one kind")
        if not kda or not full:
            refuse("layers of one kind only",
                   "the state pool holds the KDA layers and the latent "
                   "pools the attending ones; a model of one kind is "
                   "another module's")
        if not cfg.get("mla_use_nope", False):
            refuse("mla_use_nope false",
                   "its attending layers apply no rotation to the shared "
                   "key columns, and no cell would run the rotated form")
        if cfg.get("q_lora_rank"):
            refuse(f"q_lora_rank {cfg['q_lora_rank']}",
                   "its attending layers project the queries at full "
                   "rank")
        if (cfg.get("num_expert_group") or 1) != 1 \
                or (cfg.get("topk_group") or 1) != 1:
            refuse(f"num_expert_group {cfg.get('num_expert_group')} / "
                   f"topk_group {cfg.get('topk_group')}",
                   "the gate chooses among all the router's outputs: "
                   "groups other than 1 are not computed")
        if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid":
            refuse(f"moe_router_activation_func "
                   f"{cfg['moe_router_activation_func']!r}",
                   "the gate scores by a sigmoid")
        if cfg.get("moe_layer_freq", 1) != 1:
            refuse(f"moe_layer_freq {cfg['moe_layer_freq']}",
                   "every layer after the first_k_dense_replace dense "
                   "ones has routed experts")
        if cfg.get("rope_scaling"):
            refuse("rope_scaling", "no layer rotates")
        held = cfg["num_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("first_local_expert", 0)
        if not 0 <= first <= width - held:
            refuse(f"first_local_expert {first}",
                   f"the {held} experts held must lie inside the "
                   f"router's {width}")
        if cfg["num_experts_per_token"] > width:
            refuse(f"num_experts_per_token {cfg['num_experts_per_token']}",
                   f"the router has {width} outputs")
        attending = set(full)
        self.model_type = "kimi_linear"
        self.layer_types = tuple("attention" if l in attending else "kda"
                                 for l in range(1, L + 1))
        self.kda_n_heads = lin["num_heads"]
        self.kda_head_dim = lin["head_dim"]
        self.mamba_d_conv = lin.get("short_conv_kernel_size", 4)
        self.mla_nope = True
        self.q_lora_rank = 0
        self.kv_lora_rank = cfg["kv_lora_rank"]
        self.qk_nope_head_dim = cfg["qk_nope_head_dim"]
        self.qk_rope_head_dim = cfg["qk_rope_head_dim"]
        self.v_head_dim = cfg["v_head_dim"]
        self.num_experts, self.router_experts = held, width
        self.first_expert = first
        self.num_experts_per_tok = cfg["num_experts_per_token"]
        self.moe_router = "deepseek_v3"
        self.norm_topk_prob = bool(cfg.get("moe_renormalize", True))
        self.routed_scaling_factor = cfg.get("routed_scaling_factor", 1.0)
        self.n_shared_experts = cfg.get("num_shared_experts", 0)
        self.first_k_dense_replace = cfg.get("first_k_dense_replace", 0)
        self.moe_intermediate_size = cfg["moe_intermediate_size"]

    def _read_solar_open2(self, cfg: dict) -> None:
        """The keys of a ``solar_open2`` config.json. ``gqa_layers``
        counts layers from 0 and is kept whole in a file cut in depth:
        the entries under ``num_hidden_layers`` are the layers that
        attend, every other layer is KDA. ``n_routed_experts`` is the
        experts HELD; a file cut to a chip's share names the published
        count (``router_num_experts``) and the first expert held
        (``first_local_expert``) beside it, as granite's and kimi's do."""
        def refuse(what: str, why: str):
            raise NotImplementedError(
                f"solar_open2 with {what} is not supported ({why})")

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
        held = cfg["n_routed_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("first_local_expert", 0)
        if not 0 <= first <= width - held:
            refuse(f"first_local_expert {first}",
                   f"the {held} experts held must lie inside the "
                   f"router's {width}")
        if cfg["num_experts_per_tok"] > width:
            refuse(f"num_experts_per_tok {cfg['num_experts_per_tok']}",
                   f"the router has {width} outputs")
        self.model_type = "solar_open2"
        self.layer_types = tuple("attention" if l in attending else "kda"
                                 for l in range(L))
        self.kda_n_heads = lin["num_heads"]
        self.kda_head_dim = lin["head_dim"]
        self.mamba_d_conv = lin.get("short_conv_kernel_size", 4)
        self.kda_beta_scale = 2.0 if cfg.get("kda_allow_neg_eigval") else 1.0
        self.num_experts, self.router_experts = held, width
        self.first_expert = first
        self.num_experts_per_tok = cfg["num_experts_per_tok"]
        # sigmoid scores, selection by score + bias, the unbiased scores
        # of the chosen renormalised and scaled: DeepSeek-V3's gate
        # without groups (models/mla.py _deepseek_gate)
        self.moe_router = "deepseek_v3"
        self.norm_topk_prob = bool(cfg.get("norm_topk_prob", True))
        self.routed_scaling_factor = float(
            cfg.get("routed_scaling_factor", 1.0))
        self.n_shared_experts = cfg.get("n_shared_experts", 0)
        self.first_k_dense_replace = 0
        self.moe_intermediate_size = cfg["moe_intermediate_size"]

    def _read_granite(self, cfg: dict) -> None:
        """The keys of a ``granitemoehybrid`` config.json. ``num_local_
        experts`` is the experts HELD; a file cut to a chip's share names
        the published count (``router_num_experts``) and the first expert
        held (``first_local_expert``) beside it."""
        def refuse(what: str, why: str):
            raise NotImplementedError(
                f"granitemoehybrid with {what} is not supported ({why})")

        L = cfg["num_hidden_layers"]
        kinds = tuple(cfg["layer_types"][:L])
        odd = sorted(set(kinds) - {"mamba", "attention"})
        if odd or len(kinds) != L:
            refuse(f"layer_types {odd or len(kinds)}",
                   "it must name num_hidden_layers layers, each mamba or "
                   "attention")
        if cfg.get("mamba_n_groups", 1) != 1:
            refuse("mamba_n_groups > 1",
                   "B and C are computed once for all heads, and the "
                   "chunked form's C.B product is one matrix a chunk")
        if cfg.get("position_embedding_type", "nope") != "nope":
            refuse(f"position_embedding_type "
                   f"{cfg['position_embedding_type']!r}",
                   "its attending layers apply no positional embedding")
        if cfg.get("mamba_proj_bias") or cfg.get("attention_bias"):
            refuse("mamba_proj_bias or attention_bias true",
                   "the mixers' projections are computed without a bias")
        if not cfg.get("mamba_conv_bias", True):
            refuse("mamba_conv_bias false",
                   "the causal convolution adds its bias leaf")
        heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        expand = cfg.get("mamba_expand", 2)
        if heads * d_head != expand * cfg["hidden_size"]:
            refuse(f"mamba_n_heads x mamba_d_head = {heads * d_head}",
                   f"mamba_expand x hidden_size is "
                   f"{expand * cfg['hidden_size']}, the mixer's one inner "
                   f"width")
        held = cfg["num_local_experts"]
        width = cfg.get("router_num_experts", held)
        first = cfg.get("first_local_expert", 0)
        if not 0 <= first <= width - held:
            refuse(f"first_local_expert {first}",
                   f"the {held} experts held must lie inside the "
                   f"router's {width}")
        if cfg["num_experts_per_tok"] > width:
            refuse(f"num_experts_per_tok {cfg['num_experts_per_tok']}",
                   f"the router has {width} outputs")
        self.model_type = "granitemoehybrid"
        self.layer_types = kinds
        self.mamba_n_heads, self.mamba_d_head = heads, d_head
        self.mamba_d_state = cfg["mamba_d_state"]
        self.mamba_d_conv = cfg.get("mamba_d_conv", 4)
        self.mamba_expand = expand
        self.mamba_chunk_size = cfg.get("mamba_chunk_size", 256)
        self.shared_intermediate_size = cfg.get("shared_intermediate_size",
                                                0)
        self.embedding_multiplier = float(cfg.get("embedding_multiplier", 1))
        self.attention_multiplier = cfg.get("attention_multiplier")
        self.residual_multiplier = float(cfg.get("residual_multiplier", 1))
        self.logits_scaling = float(cfg.get("logits_scaling", 1))
        self.num_experts, self.router_experts = held, width
        self.first_expert = first
        self.num_experts_per_tok = cfg["num_experts_per_tok"]
        self.tie_word_embeddings = cfg.get("tie_word_embeddings", True)

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

    @classmethod
    def llama3_70b(cls) -> "ModelConfig":
        return cls(hidden_size=8192, intermediate_size=28672, num_layers=80,
                   num_heads=64, num_kv_heads=8)

    @classmethod
    def mixtral_8x7b(cls) -> "ModelConfig":
        return cls(model_type="mixtral", vocab_size=32000, hidden_size=4096,
                   intermediate_size=14336, num_layers=32, num_heads=32,
                   num_kv_heads=8, rope_theta=1e6, num_experts=8,
                   num_experts_per_tok=2)
