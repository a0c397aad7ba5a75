"""Llama-family model in pure-functional JAX with a paged KV cache.

This is the worker data plane the reference delegates to patched vLLM
(container/deps/vllm/*-dynamo-kv-disagg-patch.patch) — re-designed TPU-first
instead of ported:

- layers are stacked on a leading axis and driven by ``lax.scan`` (one
  layer trace → fast XLA compiles at any depth);
- the KV cache is a preallocated page pool ``[L, num_pages, kv_heads,
  page_size, head_dim]`` living in HBM; sequences own pages via page tables
  (the vLLM paged-KV idea, expressed as JAX gather/scatter so XLA can fuse
  and shard it);
- prefill and decode share ONE attention path: write the new K/V into pages
  (scatter), gather the sequence's pages, masked GQA attention — so chunked
  prefill, prefix-cache continuation, and decode are the same program at
  different query lengths;
- shardings: heads over the "model" mesh axis, batch over "data"
  (tensor-parallel decode per SURVEY §2.4), applied via NamedSharding on
  params + cache (see dynamo_tpu/parallel/mesh.py).

All shapes are static under jit; batches/chunks are bucketed and padded by
the scheduler (dynamo_tpu/engine/scheduler.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.moe_grouped import i_tile, moe_grouped_mlp
from ..ops.paged_attention import (effective_window,
                                   paged_attention_decode,
                                   paged_attention_decode_sharded,
                                   paged_attention_prefill,
                                   paged_attention_prefill_sharded)
from ..engine.sampling import logprob_aux, sample_with_confidence, unmask
from ..runtime.config import env_flag
from .config import ModelConfig
from .window import (Family, WindowResults, block_carry_update, carry_active,
                     make_window)

Params = Dict[str, jax.Array]

# scatter sentinel for padded rows: guaranteed out-of-range so mode="drop"
# discards the write (negative indices would WRAP per numpy semantics)
DROP_SLOT = 1 << 30


# ---------------------------------------------------------------- KV cache


@dataclass
class KVCacheSpec:
    num_pages: int
    page_size: int

    def shape(self, cfg: ModelConfig,
              layers: Optional[int] = None) -> Tuple[int, ...]:
        # kv-head-major page layout [L, pages, KV, ps, hd]: the Pallas decode
        # kernel then consumes pages with NO in-kernel transpose (batched
        # MXU dots over the leading KV axis) and (ps, hd) is lane-aligned.
        # The price: a token's row lies UNDER the KV axis, and the TPU
        # compiler scatters only along a major axis, so a scatter of single
        # rows (_scatter_pages) relayouts the whole pool before and after
        # (KV-major, then one tile per token: four copies a pool, two
        # where [KV, hd] is one tile). Hot paths therefore write WHOLE
        # pages along (L, pages): _scatter_pages_paged in prefill,
        # commit_window at the end of the decode window.
        # The reference models this as KvLayout::{KvFirst,BlockFirst}
        # (lib/llm/src/kv/layer.rs:100-106) — layout chosen for the device.
        return (cfg.num_layers if layers is None else layers,
                self.num_pages, cfg.num_kv_heads, self.page_size,
                cfg.head_dim_)


def init_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                  dtype=None) -> Tuple[jax.Array, jax.Array]:
    """The K and V pools, [L, pages, ...]; for a configuration whose
    kinds of layer keep a pool each (``cfg.kv_pool_by_kind``) the pool of
    the layers that see the whole context, [L_full, pages, ...], layer
    ``cfg.full_layer_ids[a]`` at index a."""
    shape = spec.shape(cfg, len(cfg.full_layer_ids)
                       if cfg.kv_pool_by_kind else None)
    dtype = dtype or cfg.jax_dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_window_kv_cache(cfg: ModelConfig, spec: KVCacheSpec,
                         dtype=None) -> Tuple[jax.Array, jax.Array]:
    """The window layers' K and V pools, [L_win, pages, ...] with pages
    of their own count and their own ids: layer
    ``cfg.window_layer_ids[a]`` at index a. A row's table into it has a
    bounded number of slots (``window_table_slots``) and starts at the
    first page the row still holds, not at position 0."""
    shape = spec.shape(cfg, len(cfg.window_layer_ids))
    dtype = dtype or cfg.jax_dtype
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def window_table_slots(cfg: ModelConfig, page_size: int, ahead: int) -> int:
    """Slots of a row's table into the window layers' pool: the pages
    that intersect ``window`` positions behind a query and ``ahead``
    tokens written in one program (a prefill chunk, two decode windows)
    past it, whatever the row's context."""
    return -(-(cfg.sliding_window + ahead) // page_size) + 1


# ------------------------------------------------------------------ params


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init params (stacked layers on axis 0)."""
    dtype = dtype or cfg.jax_dtype
    D, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    V = cfg.vocab_size
    ks = jax.random.split(key, 10)

    def norm_init(k, *shape):
        return jnp.ones(shape, dtype)

    def w_init(k, *shape):
        scale = 1.0 / math.sqrt(shape[-2]) if len(shape) > 1 else 0.02
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p: Params = {
        "embed": w_init(ks[0], V, D),
        "wq": w_init(ks[1], L, D, H * hd),
        "wk": w_init(ks[2], L, D, KV * hd),
        "wv": w_init(ks[3], L, D, KV * hd),
        "wo": w_init(ks[4], L, H * hd, D),
        "w_gate": w_init(ks[5], L, D, I),
        "w_up": w_init(ks[6], L, D, I),
        "w_down": w_init(ks[7], L, I, D),
        "ln_attn": norm_init(ks[8], L, D),
        "ln_mlp": norm_init(ks[8], L, D),
        "ln_final": norm_init(ks[8], D),
    }
    if cfg.attn_bias:  # Qwen2-style q/k/v projection bias
        p["bq"] = jnp.zeros((L, H * hd), dtype)
        p["bk"] = jnp.zeros((L, KV * hd), dtype)
        p["bv"] = jnp.zeros((L, KV * hd), dtype)
    if cfg.sandwich_norms:  # Gemma-2 post-attention/feedforward norms
        p["ln_attn_post"] = norm_init(ks[8], L, D)
        p["ln_mlp_post"] = norm_init(ks[8], L, D)
    if cfg.qk_norm:  # Qwen3 per-head q/k norms
        p["q_norm"] = norm_init(ks[8], L, hd)
        p["k_norm"] = norm_init(ks[8], L, hd)
    if not cfg.tie_word_embeddings:
        p["lm_head"] = w_init(ks[9], D, V)
    if cfg.num_experts > 0:
        E = cfg.num_experts
        p["w_router"] = w_init(ks[5], L, D, E)
        p["w_gate"] = w_init(ks[5], L, E, D, I)
        p["w_up"] = w_init(ks[6], L, E, D, I)
        p["w_down"] = w_init(ks[7], L, E, I, D)
    return p


# -------------------------------------------------------------- primitives


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             unit_offset: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * lax.rsqrt(var + eps)
    if unit_offset:
        # Gemma: w is a delta around 1, applied in float32 before the
        # cast (matches HF GemmaRMSNorm exactly)
        return (normed * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    # Llama: cast first, then scale (matches HF LlamaRMSNorm)
    return normed.astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """LayerNorm without a bias: the mean subtracted, the variance about
    it, the weight applied in float32 before the cast (HF
    CohereLayerNorm)."""
    x32 = x.astype(jnp.float32)
    c = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return (c * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _norm(cfg: ModelConfig, x: jax.Array, w: jax.Array) -> jax.Array:
    """The configuration's norm of the residual stream: ``layer_norm``
    where it says so (``cfg.layer_norm``), else ``rms_norm``."""
    if cfg.layer_norm:
        return layer_norm(x, w, cfg.rms_norm_eps)
    return rms_norm(x, w, cfg.rms_norm_eps, cfg.norm_unit_offset)


def embed_tokens(params: Params, cfg: ModelConfig,
                 tokens: jax.Array) -> jax.Array:
    """Token embedding lookup; Gemma scales by sqrt(hidden), Granite by
    its embedding_multiplier."""
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.hidden_size), h.dtype)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    return h


def project_logits(params: Params, cfg: ModelConfig,
                   h: jax.Array) -> jax.Array:
    """LM head (tied to the embedding when absent) + the optional
    Gemma-2-style final-logit softcap — the single logit-path exit used
    by every forward variant."""
    with jax.named_scope("lm_head"):
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = (h @ head).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:       # Granite divides its logits
            logits = logits / cfg.logits_scaling
        if cfg.final_logit_softcap:
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


def relu2(x: jax.Array) -> jax.Array:
    """relu(x)^2: the activation of an expert that is not gated
    (models/nemotron_h.py)."""
    return jnp.square(jax.nn.relu(x))


def _act(cfg: ModelConfig):
    if cfg.hidden_act == "gelu_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if cfg.hidden_act == "relu":
        return jax.nn.relu
    if cfg.hidden_act == "relu2":
        return relu2
    return jax.nn.silu


def rope_freqs(cfg: ModelConfig, dim: Optional[int] = None) -> jax.Array:
    hd = dim or cfg.head_dim_
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    scaling = cfg.rope_scaling or {}
    if scaling.get("rope_type") == "llama3" or scaling.get("type") == "llama3":
        # Llama-3.1-style NTK-by-parts frequency rescaling: low frequencies
        # are divided by `factor`, high frequencies kept, mid smoothly mixed
        factor = scaling.get("factor", 8.0)
        low = scaling.get("low_freq_factor", 1.0)
        high = scaling.get("high_freq_factor", 4.0)
        orig = scaling.get("original_max_position_embeddings", 8192)
        wavelen = 2 * jnp.pi / inv
        smooth = jnp.clip((orig / wavelen - low) / (high - low), 0.0, 1.0)
        inv = jnp.where(wavelen > orig / low, inv / factor,
                        jnp.where(wavelen < orig / high, inv,
                                  (1 - smooth) * inv / factor + smooth * inv))
    return inv


def apply_rope(x: jax.Array, positions: jax.Array,
               inv_freq: jax.Array, interleave: bool = False) -> jax.Array:
    """x: [..., T, heads, head_dim]; positions: [..., T]. Pair i is the
    columns (i, i + head_dim / 2) (HF ``rotate_half``), or with
    ``interleave`` the columns (2i, 2i + 1) as they lie (GPT-J's).

    The interleaved arm works on whole heads: out = x cos2 + partner(x)
    sin2, where cos2 / sin2 hold each pair's angle twice, sin2 carries the
    sign (- on the even columns) and partner swaps the columns of a pair:
    the float32 products of (x1 cos - x2 sin, x2 cos + x1 sin). As arrays
    [..., hd / 2, 2] the pairs fill 2 of a TPU's 128 lanes; the swap is a
    product with a constant permutation, which moves each value once and
    adds zeros to it."""
    pos = positions[..., None].astype(jnp.float32)
    if interleave:
        hd = x.shape[-1]
        angles = (pos * jnp.repeat(inv_freq, 2))[..., None, :]  # [...,T,1,hd]
        sign = jnp.where(jnp.arange(hd) % 2 == 0, -1.0, 1.0)
        swap = jnp.eye(hd, dtype=x.dtype)[jnp.arange(hd) ^ 1]
        partner = jnp.einsum(
            "...d,de->...e", x, swap, preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST if x.dtype == jnp.float32
                       else None))
        out = (x.astype(jnp.float32) * jnp.cos(angles)
               + partner * (jnp.sin(angles) * sign))
        return out.astype(x.dtype)
    angles = pos * inv_freq  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _scatter_pages_paged(cache_layer: jax.Array, new: jax.Array,
                         page_slots: jax.Array) -> jax.Array:
    """Page-granular prefill commit: write WHOLE pages instead of
    scattering T individual rows (the row scatter costs ~110 ms per
    8x1024 prefill dispatch on v5e; this path is a reshape + block
    write). Requires chunk starts page-aligned (the engine guarantees it:
    prefix-cache hits are whole pages and chunk sizes are multiples of
    the page size). The tail page may carry junk K/V beyond the chunk —
    safe, because a position's K/V is always written before any query
    attends to it (causal masks exclude unwritten positions, and decode
    overwrites its slot before reading it).

    cache_layer: [num_pages, KV, ps, hd]; new: [B, T, KV, hd] (T % ps
    == 0); page_slots: [B, T // ps] destination page ids (>= num_pages →
    dropped padding).
    """
    np_, kv, ps, hd = cache_layer.shape
    B, T = new.shape[:2]
    blocks = new.reshape(B, T // ps, ps, kv, hd).transpose(0, 1, 3, 2, 4)
    blocks = blocks.reshape(B * (T // ps), kv, ps, hd)
    idx = page_slots.reshape(-1)
    return cache_layer.at[idx].set(blocks.astype(cache_layer.dtype),
                                   mode="drop")


def _scatter_pages(cache_layer: jax.Array, new: jax.Array,
                   flat_slots: jax.Array) -> jax.Array:
    """Write new K/V rows into the page pool.

    cache_layer: [num_pages, KV, page_size, hd]; new: [B, T, KV, hd];
    flat_slots: [B, T] flattened (page*page_size + slot) indices; indices
    >= num_pages*page_size (use DROP_SLOT) are dropped (negative indices
    would wrap, so padding must use the out-of-range sentinel).
    (TPU-native replacement for the reference's block_copy.cu CUDA kernel —
    an XLA scatter the compiler lays out on the VPU.)
    """
    np_, kv, ps, hd = cache_layer.shape
    idx = flat_slots.reshape(-1)
    pages = idx // ps   # DROP_SLOT → page >= num_pages → dropped
    offs = idx % ps
    rows = new.reshape(-1, kv, hd).astype(cache_layer.dtype)
    # advanced indices (pages, offs) separated by the KV slice put the
    # scatter axis first: target shape [B*T, KV, hd]
    return cache_layer.at[pages, :, offs].set(rows, mode="drop")


def commit_window(kv: jax.Array, w: jax.Array, page_table: jax.Array,
                  start: jax.Array, pos: jax.Array,
                  lo: Optional[jax.Array] = None) -> jax.Array:
    """Commit a decode window's K (or V) into the pool by WHOLE PAGES,
    along the pool's own major axis, in place.

    kv: [L, pages, KV, ps, hd] (donated by the caller); w: [L, B, k_steps,
    KV, hd], entry i the K/V of position start + i; page_table: [B, P];
    start: [B] position of the window's first token (-1: padding row);
    pos: [B] the carry's position after the window. Entry i commits iff
    start >= 0 and start + i < pos: a row that froze mid-window commits
    only what it produced, a padding row nothing. ``lo`` [B] (the block
    window: its buffer begins a block before what a row may write): the
    first position a row commits where that is not ``start``, which may
    then lie before 0; entry i commits iff lo >= 0 and lo <= start + i <
    pos.

    Why not a row scatter (`.at[page, :, offset]`, _scatter_pages): the
    TPU compiler scatters only along a major axis, and in this layout a
    token's row sits UNDER the KV axis. It therefore copied the pool to a
    KV-major layout, again to one tile per token, scattered, and copied
    back twice: eight pool-sized copies a window for K and V (four where
    a [KV, hd] update is one tile, KV 8), 14% of the device's time at
    qwen3-30b-a3b's pool (ledger, PR 29) to write 1,536 rows of 1 KB.
    Here the pool is viewed as [L * pages, KV, ps, hd] (a bitcast), the
    S pages a row's window can touch are gathered ([L, B, S] pages, tens
    of MB), the k_steps rows are put in with one select a step, and the
    whole pages are scattered back along axis 0; an untouched page's
    index is out of range and dropped. No op has a pool-sized output
    (tests/test_tpu_compile.py holds the compiled program to that).

    Why writing whole pages back is safe: a page a window writes belongs
    to exactly one running row. PageManager shares only FULL, published
    pages: a prefix hit is capped at (len - 1) // ps pages
    (allocate_sequence), a page a row fills while decoding is published
    only up to the tokens whose K/V are in the pool (commit_chain with
    extent filled - 1), and a window writes positions >= start, which lie
    past every full page of its row; fresh pages are handed to one row
    (refcount 1) and the engine forks no row. So no two written (row,
    page slot) entries name one page, and the bytes written back beside
    the new rows are the bytes just read: the pool afterwards is
    bit-identical to a row scatter's (tests/test_window_commit.py).
    """
    L, num_pages, KV, ps, hd = kv.shape
    B, k_steps = w.shape[1:3]
    S = 1 + (k_steps + ps - 2) // ps          # pages a window can touch
    P = page_table.shape[1]
    first = jnp.maximum(start, 0) // ps                          # [B]
    cols = first[:, None] + jnp.arange(S, dtype=jnp.int32)       # [B, S]
    page = jnp.take_along_axis(page_table, jnp.minimum(cols, P - 1), axis=1)
    # token i's row inside the row's S gathered pages, as s * ps + offset
    steps = jnp.arange(k_steps, dtype=jnp.int32)
    valid = jnp.logical_and((start if lo is None else lo)[:, None] >= 0,
                            start[:, None] + steps < pos[:, None])
    if lo is not None:
        valid = valid & (start[:, None] + steps >= lo[:, None])
    rel = jnp.where(valid, (start - first * ps)[:, None] + steps,
                    -1)                                          # [B, K]
    slot = jnp.arange(S * ps, dtype=jnp.int32).reshape(S, ps)
    hit = rel[:, :, None, None] == slot                          # [B,K,S,ps]
    # a page slot past the table's last column, or an id outside the pool
    # (a row scatter drops those too), is never written
    touched = (hit.any(axis=(1, 3)) & (cols < P)
               & (page >= 0) & (page < num_pages))               # [B, S]
    layer0 = jnp.arange(L, dtype=jnp.int32)[:, None, None] * num_pages
    src = layer0 + jnp.clip(page, 0, num_pages - 1)              # [L, B, S]
    dst = jnp.where(touched, layer0 + page, L * num_pages)
    flat = kv.reshape(L * num_pages, KV, ps, hd)
    pages = flat[src]                              # [L, B, S, KV, ps, hd]
    for i in range(k_steps):
        pages = jnp.where(hit[None, :, i, :, None, :, None],
                          w[:, :, i, None, :, None, :].astype(kv.dtype),
                          pages)
    flat = flat.at[dst.reshape(-1)].set(
        pages.reshape(-1, KV, ps, hd), mode="drop")
    return flat.reshape(kv.shape)


def _write_layer_pages(pool: jax.Array, new: jax.Array, slots: jax.Array,
                       off, pages: int, table: jax.Array, pos: jax.Array,
                       paged: bool) -> jax.Array:
    """``new`` [B, T, KV, hd] into ONE layer's pages of a flat pool
    [layers * pages, KV, ps, hd], the layer's first page at ``off``:
    whole pages of an aligned chunk (``paged``: ``slots`` [B, T // ps]
    page ids), one token a row by whole pages too (commit_window at
    ``table`` / ``pos``: a row scatter makes the compiler relayout the
    pool around it), else by rows (``slots`` [B, T] page * ps + offset).
    Slots count from the layer's own page 0. One outside the layer's
    pages, negative or past them, becomes the flat pool's end and is
    dropped: shifted by ``off`` alone, a padding slot of layer l would
    land in layer l + 1's page 0."""
    ps = pool.shape[2]
    if paged:
        dst = jnp.where((slots >= 0) & (slots < pages), slots + off,
                        pool.shape[0])
        return _scatter_pages_paged(pool, new, dst)
    if new.shape[1] == 1:
        at = pos[:, 0]
        return commit_window(pool[None], new[None], table + off, at,
                             at + 1)[0]
    dst = jnp.where((slots >= 0) & (slots < pages * ps),
                    slots + off * ps, pool.shape[0] * ps)
    return _scatter_pages(pool, new, dst)


def _flat_pool(pool: jax.Array) -> jax.Array:
    """[L, pages, KV, ps, hd] seen as [L * pages, KV, ps, hd]: a bitcast
    (the merged axes are major, and kv_cache_pspec shards KV alone)."""
    return pool.reshape(-1, *pool.shape[2:])


def _use_pallas() -> bool:
    """Route decode attention through the Pallas kernel on TPU backends
    (DYN_DISABLE_PALLAS=1 forces the XLA gather path everywhere)."""
    if env_flag("DYN_DISABLE_PALLAS"):
        return False
    # a backend that fails to initialise raises here: an unreachable chip
    # must stop the server, not turn into the XLA gather path
    return jax.default_backend() == "tpu"


def kernel_mode(allow_pallas: bool = True, pallas_interpret: bool = False,
                mesh=None) -> Optional[bool]:
    """How a site that has a Pallas kernel and an XLA arm runs: None the
    XLA arm, False the kernel on the chip, True the kernel interpreted.
    The one choice of every family's attention, scan and expert kernels,
    taken in Python before tracing: the kernel on a TPU backend
    (``_use_pallas``, read through this module when called, so a test or
    a tool that wants the chip's arm patches ``llama._use_pallas`` and
    reaches every family); off it, interpreted under the tests' hooks
    (a window maker's ``pallas_interpret``, or DYN_PALLAS_INTERPRET,
    which never interprets on a TPU backend: a lingering variable must
    not slow a hardware run, nor pass the DYN_DISABLE_PALLAS kill
    switch); else the XLA arm. ``mesh`` is given by a site whose kernel
    has no shard_map wrapper (mla.py's): it stays on the XLA arm under
    more than one device, where GSPMD shards its einsums."""
    if not allow_pallas or (mesh is not None and mesh.size > 1):
        return None
    if _use_pallas():
        return False
    if pallas_interpret or (env_flag("DYN_PALLAS_INTERPRET")
                            and not env_flag("DYN_DISABLE_PALLAS")):
        return True
    return None


def _softcap_mask(scores: jax.Array, visible: jax.Array,
                  softcap: Optional[float]) -> jax.Array:
    """Gemma-2 attention-score postprocess: tanh softcap (BEFORE masking —
    -1e30 through tanh would collapse to -softcap and unmask), then the
    visibility mask. ``visible`` broadcasts against ``scores``."""
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    return jnp.where(visible, scores, -1e30)


def _visible(kv_pos: jax.Array, q_pos: jax.Array,
             window: Optional[int], is_sliding, block: int = 1) -> jax.Array:
    """Causal visibility of kv position j to query position t, with the
    optional Gemma-2 sliding window: on sliding layers only the last
    ``window`` positions (j > t - window) are visible. ``is_sliding`` is
    a traced bool scalar (layer parity under lax.scan). With ``block``
    > 1 (cfg.block_length: generation by diffusion over blocks) the mask
    is causal across blocks and bidirectional inside one: t sees j iff
    j lies before the end of t's block."""
    if block > 1:
        vis = kv_pos < (q_pos // block + 1) * block
    else:
        vis = kv_pos <= q_pos
    if window is not None:
        in_win = kv_pos > q_pos - window
        vis = jnp.logical_and(vis, jnp.logical_or(
            jnp.logical_not(is_sliding), in_win))
    return vis


def _attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
               page_table: jax.Array, q_positions: jax.Array,
               scale: float, allow_pallas: bool = True,
               mesh=None, softcap: Optional[float] = None,
               window: Optional[int] = None,
               is_sliding=False, block: int = 1) -> jax.Array:
    """Dispatch: on a TPU backend decode (T == 1) and a chunk of queries
    (T > 1: prefill, speculative verify) each run their Pallas kernel
    over the row's own pages (ops/paged_attention.py); everything else
    runs the XLA gather path: other platforms, ``allow_pallas=False``, a
    mesh the shard_map wrappers cannot split (below), and a chunk of
    queries over heads that are no multiple of 128 lanes (run.py --model
    1b: the chip's compiler refuses the page copies, see
    _decode_kernel_narrow). With a >1-device ``mesh`` the kernels run per
    model-shard via shard_map (heads follow their kv heads: the *_sharded
    wrappers). ``block`` > 1 is the block mask of ``_visible``: the
    prefill kernel and the XLA arm take it (a decode step of one query
    does not exist under it; the block window below has its own
    attention)."""
    mode = kernel_mode(allow_pallas)
    pallas_ok, interp = mode is not None, mode is True
    B, T, H, hd = q.shape
    KV = k_pages.shape[1]
    sharded = mesh is not None and mesh.size > 1
    if sharded:
        # shard_map needs whole GQA groups and whole batch rows per shard;
        # shapes are static at trace time so this is a compile-time choice
        tp = mesh.shape.get("model", 1)
        dp = mesh.shape.get("data", 1)
        pallas_ok = pallas_ok and KV % tp == 0 and B % dp == 0
    # Gemma-2 knobs for the kernels: per-row effective window (huge on
    # global layers — is_sliding is traced layer parity) and the static
    # score softcap
    eff = None
    if window is not None:
        eff = effective_window(window, is_sliding, B)
    if T == 1 and pallas_ok and block == 1:
        lengths = q_positions[:, 0] + 1  # padding rows: -1 → 0 → zeros out
        lower = None
        if eff is not None:
            # first visible position; clamped so at least one position of
            # a live row stays in view (the index map indexes pt[lo//ps])
            lower = jnp.clip(lengths - eff, 0, jnp.maximum(lengths - 1, 0))
        if sharded:
            out = paged_attention_decode_sharded(
                q[:, 0], k_pages[None], v_pages[None], 0, page_table,
                lengths, mesh=mesh, scale=scale, interpret=interp,
                return_stats=False, softcap=softcap, lower=lower)
            return out[:, None]
        if not interp:  # unsharded K=1: hardware kernel only (no
            return paged_attention_decode(  # interpret hook needed here)
                q[:, 0], k_pages, v_pages, page_table,
                lengths, scale=scale, softcap=softcap,
                lower=lower)[:, None]
    if T > 1 and pallas_ok and (hd % 128 == 0 or interp):
        # pages stream through VMEM, the live rows' and no others,
        # instead of the XLA path's dense [B, P*ps, KV, hd] gather and
        # float32 scores over every slot of the table
        if sharded:
            if block > 1:
                raise NotImplementedError(
                    "the sharded prefill kernel has no block mask: a "
                    "mesh is not supported for a model that generates "
                    "by diffusion over blocks")
            return paged_attention_prefill_sharded(
                q, k_pages, v_pages, page_table, q_positions, mesh=mesh,
                scale=scale, interpret=interp, softcap=softcap,
                eff_win=eff)
        return paged_attention_prefill(q, k_pages, v_pages, page_table,
                                       q_positions, scale=scale,
                                       interpret=interp, softcap=softcap,
                                       eff_win=eff, block=block)
    return _paged_attention(q, k_pages, v_pages, page_table, q_positions,
                            scale, softcap=softcap, window=window,
                            is_sliding=is_sliding, block=block)


def _paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     page_table: jax.Array, q_positions: jax.Array,
                     scale: float, softcap: Optional[float] = None,
                     window: Optional[int] = None,
                     is_sliding=False, block: int = 1) -> jax.Array:
    """Gather-based paged GQA attention (XLA path; the Pallas kernel in
    dynamo_tpu/ops/paged_attention.py replaces this on TPU hot paths).

    q: [B, T, H, hd]; k_pages/v_pages: [num_pages, KV, ps, hd];
    page_table: [B, P]; q_positions: [B, T] (absolute, -1 for padding).
    Attends to logical positions j <= q_position (causal over the whole
    cached sequence, which includes the just-written chunk).
    """
    B, T, H, hd = q.shape
    _, KV, ps, _ = k_pages.shape
    P = page_table.shape[1]
    S = P * ps
    group = H // KV

    k = k_pages[page_table]  # [B, P, KV, ps, hd]
    v = v_pages[page_table]
    k = k.transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)
    v = v.transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)

    qg = q.reshape(B, T, KV, group, hd)
    # native-dtype operands + f32 accumulation: upcasting q/k to f32
    # BEFORE the matmul forces the MXU onto its f32 path (~8x slower than
    # bf16 x bf16 -> f32); preferred_element_type keeps the accumulator
    # exact. CPU test configs run f32 models, so this is identical there.
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    # mask [B, T, S]: slot j (logical position) visible iff j <= query pos
    # (and within the sliding window on Gemma-2 sliding layers)
    mask = _visible(jnp.arange(S)[None, None, :], q_positions[:, :, None],
                    window, is_sliding, block)
    scores = _softcap_mask(scores, mask[:, None, None, :, :], softcap)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, hd).astype(q.dtype)


# ------------------------------------------------------------ forward pass


def _mlp(h: jax.Array, w_gate, w_up, w_down, act=jax.nn.silu) -> jax.Array:
    with jax.named_scope("mlp"):
        return (act(h @ w_gate) * (h @ w_up)) @ w_down


def _layer_keys(cfg: ModelConfig) -> list:
    """Per-layer param names scanned over the stacked-layer axis — the
    single source for every forward variant (paged, fused window, full)."""
    keys = ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
            "ln_attn", "ln_mlp"]
    if cfg.parallel_block:      # ONE norm a layer
        keys.remove("ln_mlp")
    if cfg.num_experts > 0:
        keys.append("w_router")
    if cfg.n_shared_experts > 0:
        keys += list(SHARED_KEYS)
    if cfg.attn_bias:
        keys += ["bq", "bk", "bv"]
    if cfg.sandwich_norms:
        keys += ["ln_attn_post", "ln_mlp_post"]
    if cfg.qk_norm:
        keys += ["q_norm", "k_norm"]
    return keys


SHARED_KEYS = ("w_gate_s", "w_up_s", "w_down_s")


def _residual_add(h: jax.Array, out: jax.Array, lp, post_key: str,
                  cfg: ModelConfig) -> jax.Array:
    """Residual add, with the Gemma-2 sandwich norm on the branch output
    (post_attention_layernorm / post_feedforward_layernorm) when the
    config uses them."""
    if cfg.sandwich_norms:
        out = rms_norm(out, lp[post_key], cfg.rms_norm_eps,
                       cfg.norm_unit_offset)
    return h + out


def _qk_headnorm(q, k, lp, cfg: ModelConfig):
    """Qwen3 per-head RMSNorm on q/k before RoPE: weights [hd] broadcast
    over [..., H|KV, hd]. No-op unless cfg.qk_norm."""
    if not cfg.qk_norm:
        return q, k
    return (rms_norm(q, lp["q_norm"], cfg.rms_norm_eps),
            rms_norm(k, lp["k_norm"], cfg.rms_norm_eps))


def _window_flag(cfg: ModelConfig, l_idx):
    """Whether layer ``l_idx`` (traced under lax.scan) is held to the
    window, looked up in the configuration's per-layer layout
    (``cfg.layer_window``; Gemma-2's even-layer rule is one). One pool
    under a mask: a configuration whose kinds of layer keep a pool each
    runs ``_forward_by_kind``, where the kind is static."""
    if not cfg.window_layer_ids:
        return False
    return jnp.asarray([w is not None for w in cfg.layer_window])[l_idx]


def _dyn_expert(w, e, layer=None):
    """One expert's weight by traced index, as float32: from a layer's
    stacked [E, ...] tensor, or with ``layer`` from the whole
    [L, E, ...] parameter in ONE dynamic_slice, so nothing the size of a
    layer's expert stack is ever made. Dequantizes after the slice when
    quantized: the loop body only reads the ACTIVE expert's int8 bytes."""
    from .quant import QuantInt8

    def one(a):
        if layer is None:
            return lax.dynamic_index_in_dim(a, e, 0, False)
        tail = a.shape[2:]
        return lax.dynamic_slice(
            a, (layer, e) + (0,) * len(tail), (1, 1) + tail).reshape(tail)

    if isinstance(w, QuantInt8):
        return QuantInt8(one(w.q), one(w.s)).dequant(jnp.float32)
    return one(w).astype(jnp.float32)


def moe_block(n_tokens: int, top_k: int, w_shape,
              width: Optional[int] = None) -> int:
    """Rows of one block of the sorted dispatch, from the shapes at trace
    time (``w_shape``: the [..., E, D, I] of an expert stack; ``width``:
    the experts the gate scored where that is more than the E held here,
    see ``moe_experts``).

    The power of two at or above the pairs an expert gets from a full
    bucket (N*k/width), within 32..256: a sparse bucket then pads each
    live expert's one block with few dead rows, which cost their share
    of the block's arithmetic and of its rows in and out. Where an
    expert's matrices are too large to be held whole while the next
    one's stream in (ops/moe_grouped.py ``i_tile``, at bfloat16:
    Mixtral's 4,096 x 14,336) every block streams them again, padding rows are free and a
    second block for the same expert is not: the ridge's 256 whatever
    the bucket. On the v5e, one layer through the kernel (PERF.md, PR 42;
    the loop of small programs it replaced: PR 28): Mixtral-8x7B runs
    512 slots in 4.1 ms at 256; Qwen3-30B-A3B (2,048 x 768) 2,048 slots
    in 2.6-3.7 at 128; LFM2-24B-A2B (2,048 x 1,536) 1,024 slots in 1.9
    at 64, and on PR 42's first build 2.2 at 128 against 2.6 at 256;
    granite-4.0-h-small's 36 of 72 (4,096 x 768) 512 slots in 1.5 at
    128, on the first build 1.7 against 2.1 at 256."""
    E, D, I = w_shape[-3:]
    if i_tile(256, D, I, 2) < I:
        return 256
    mean = max(n_tokens * top_k // (width or E), 1)
    return min(max(1 << (mean - 1).bit_length(), 32), 256)


def moe_block_plan(counts: jax.Array, block: int, n_max: int):
    """Which blocks the sorted dispatch runs, from the live pairs per
    expert ``counts`` [E]: expert e owns ``ceil(counts[e] / block)``
    consecutive blocks over its run of the sorted pairs, and an expert
    with no pair owns none.

    Returns ``(n_blocks, block_e, row0, row_end)``: the number of blocks
    that hold a pair (``sum_e ceil(counts[e] / block)``), and for block
    j < n_blocks its expert, its first sorted pair and the end of its
    expert's run (its rows are ``row0[j] + i < row_end[j]``). The three
    vectors are ``n_max`` long; entries at or past ``n_blocks`` mean
    nothing."""
    E = counts.shape[0]
    per_e = (counts + block - 1) // block
    b_end = jnp.cumsum(per_e)
    g_end = jnp.cumsum(counts)
    j = jnp.arange(n_max, dtype=jnp.int32)
    block_e = jnp.minimum(
        jnp.sum(j[:, None] >= b_end[None, :], axis=1), E - 1)
    row0 = ((g_end - counts)[block_e]
            + (j - (b_end - per_e)[block_e]) * block)
    return b_end[-1], block_e, row0, g_end[block_e]


def moe_pair_slots(counts: jax.Array, block: int, pair_e: jax.Array,
                   place: jax.Array) -> jax.Array:
    """Where each pair's row lies when every block of ``moe_block_plan``
    has ``block`` rows of its own, block j's at ``j * block``: a pair of
    expert e that is the r-th of e's run sits in e's block ``r // block``
    at row ``r % block``, which is its place in sorted order moved up by
    the padding of the runs before e's. ``pair_e`` [NK] (E = not live:
    its slot is -1), ``place`` [NK] the pair's place in sorted order."""
    E = counts.shape[0]
    per_e = (counts + block - 1) // block
    ahead = ((jnp.cumsum(per_e) - per_e) * block
             - (jnp.cumsum(counts) - counts))             # [E]
    # chosen by comparison, not by index: NK lookups in a table of E are
    # NK scalar loads on the TPU (7-10 ns each)
    mine = pair_e[:, None] == jnp.arange(E, dtype=pair_e.dtype)
    return jnp.where(pair_e < E,
                     place + jnp.sum(jnp.where(mine, ahead, 0), axis=1), -1)


def _moe_kernel_interpret(w) -> Optional[bool]:
    """How the sorted dispatch runs its blocks. None: the loop of small
    XLA programs (off the TPU, and for int8 stacks, which ``_dyn_expert``
    dequantizes an expert at a time). Else ops/moe_grouped.py runs them
    as one kernel and this is its ``interpret`` flag; chosen as the
    attention kernels are (a TPU backend, or DYN_PALLAS_INTERPRET off
    it: the tests' hook)."""
    from .quant import QuantInt8

    return None if isinstance(w, QuantInt8) else kernel_mode()


def moe_kernel_takes(cfg: ModelConfig, params: Params, mesh,
                     n_tokens: int) -> bool:
    """Whether the expert layers of a program over ``n_tokens`` token
    rows run ops/moe_grouped.py: the sorted form by the shape rule, and
    stacks the kernel reads. (engine stats: moe_grouped_programs_total)"""
    if cfg.num_experts == 0 or not _moe_use_blocked(
            mesh, n_tokens, cfg.num_experts, cfg.num_experts_per_tok):
        return False
    # the routed experts' stack, under either name the modules give it
    # (the up matrix: an expert that is not gated has no other going in)
    stack = params["w_up_e"] if "w_up_e" in params else params["w_up"]
    return _moe_kernel_interpret(stack) is not None


# Rows the XLA side of the kernel's dispatch moves at a time. A prefill
# bucket is mostly padding where one short prompt rides in a batch of
# eight (cell 2: 12% of 2,048 rows live), so what is gathered in and read
# back follows the LIVE blocks and tokens in loops whose bounds the
# device knows after the sort, a few MB a turn (the bucket's worst case
# in one gather each cost 8 of cell 2's 28 ms prefill: PERF.md, PR 42).
_MOE_CHUNK_ROWS = 2048
_MOE_CHUNK_TOKENS = 256


def _moe_rows_in(x: jax.Array, tok: jax.Array, row0: jax.Array, n_blocks,
                 block: int) -> jax.Array:
    """[n_max * block, D]: block j's rows of ``x`` at ``j * block``, its
    pairs' tokens and past its run whatever pair follows (no row's
    result depends on another's, and only live pairs' rows are read
    back). Only the blocks below ``n_blocks`` are filled, ``per`` at a
    time; the rest of the buffer is never written and never read."""
    n_max, D = row0.shape[0], x.shape[1]
    per = max(_MOE_CHUNK_ROWS // block, 1)
    last = tok.shape[0] - 1

    def fill(i, xs):
        rows = (lax.dynamic_slice(row0, (i * per,), (per,))[:, None]
                + jnp.arange(block, dtype=jnp.int32)).reshape(-1)
        return lax.dynamic_update_slice(
            xs, x[tok[jnp.minimum(rows, last)]], (i * per * block, 0))

    return lax.fori_loop(0, (n_blocks + per - 1) // per, fill,
                         lax.empty((n_max * block, D), x.dtype))


def _moe_rows_out(ys: jax.Array, slot: jax.Array,
                  weights: jax.Array) -> jax.Array:
    """[N, D] float32: every token's pairs read back from their slots
    (``slot`` [N, k], -1 = not live: zeros, whatever the slot holds) and
    summed with their routing weights, a chunk of tokens at a time and
    only the chunks that hold a live pair."""
    N, k = slot.shape
    c = _MOE_CHUNK_TOKENS if N % _MOE_CHUNK_TOKENS == 0 else N
    # [chunks, k, c]: a token's pairs along the major axis, so that
    # their sum is k slabs added
    slot = slot.reshape(N // c, c, k).transpose(0, 2, 1)
    weights = weights.reshape(N // c, c, k).transpose(0, 2, 1)
    holds = jnp.any(slot >= 0, axis=(1, 2))
    live_first = jnp.argsort(~holds, stable=True)

    def add(i, out):
        at = live_first[i]
        s = lax.dynamic_index_in_dim(slot, at, 0, False)
        w = lax.dynamic_index_in_dim(weights, at, 0, False)
        y = jnp.where((s >= 0)[..., None], ys[jnp.maximum(s, 0)], 0.0)
        return lax.dynamic_update_slice(
            out, jnp.sum(y * w[..., None], axis=0), (at * c, 0))

    return lax.fori_loop(0, jnp.sum(holds), add,
                         jnp.zeros((N, ys.shape[1]), jnp.float32))


def moe_experts_blocked(x: jax.Array, weights: jax.Array, idx: jax.Array,
                        w_gate, w_up, w_down, block: int, live=None,
                        layer=None, act=jax.nn.silu,
                        first=None) -> jax.Array:
    """Sparse top-k expert dispatch with static shapes, NO token drops,
    and work in proportion to the LIVE (token, expert) pairs.

    x: [N, D] (f32) flattened tokens; weights/idx: [N, k] routing output;
    live: optional [N] bool, False on padding rows (None = all live);
    w_*: a layer's [E, ...] expert stacks, or with ``layer`` (traced
    index) the whole [L, E, ...] parameters, read in place; ``w_gate``
    None: an expert that is not gated (``moe_experts``). ``first``:
    see ``moe_experts``; a pair whose expert is not held is not live.

    Sort the N*k pairs by expert, a dead row's pairs behind every
    expert's run. Expert e's run is cut into ``ceil(count_e / block)``
    blocks and only those run (``moe_block_plan``): each takes its rows
    of ``x``, ONE expert's weights (HBM only streams the experts that
    hold a pair) and yields its [block, D] result. Every pair's result is
    then read back from where it lies and summed into its token with its
    routing weight; a dead row reads zeros. Exact same math as the
    dense-over-experts einsum: the per-row MLP does not depend on which
    block a row sits in.

    Two forms of running the blocks (``_moe_kernel_interpret``):
    - on a TPU ONE kernel whose grid is the plan (ops/moe_grouped.py):
      the live blocks' rows are gathered in the weights' dtype, block
      j's at ``j * block`` (``_moe_rows_in``), each block writes its own
      slot, and the live tokens' pairs are read back from theirs
      (``_moe_rows_out``);
    - off it a ``fori_loop`` whose bound is known on the device after
      the sort, one small XLA program a block, which writes its result
      at its place in sorted order into the N*k + block pairs' own
      buffer. A run's last block overhangs the next run with zero rows,
      which that run's own blocks, coming later, overwrite.
    Reference analog: vLLM's fused_moe dispatch.
    """
    N, D = x.shape
    k = idx.shape[-1]
    E = w_up.shape[-3]
    NK = N * k
    n_max = (NK + block - 1) // block + E
    interpret = _moe_kernel_interpret(w_up)
    if interpret is not None:       # whole chunks of blocks: _moe_rows_in
        per = max(_MOE_CHUNK_ROWS // block, 1)
        n_max = (n_max + per - 1) // per * per

    with jax.named_scope("moe.dispatch"):
        pair_e = idx.reshape(-1)                          # [NK]
        if first is not None:
            pair_e = pair_e - first
            pair_e = jnp.where((pair_e >= 0) & (pair_e < E), pair_e, E)
        if live is not None:
            pair_e = jnp.where(jnp.repeat(live, k), pair_e, E)
        order = jnp.argsort(pair_e, stable=True)          # sorted -> pair
        place = jnp.zeros((NK,), jnp.int32).at[order].set(
            jnp.arange(NK, dtype=jnp.int32), unique_indices=True)
        # token of each sorted pair, padded so a block's rows are one slice
        tok = jnp.pad(order // k, (0, block))
        counts = jnp.sum(jax.nn.one_hot(pair_e, E, dtype=jnp.int32), axis=0)
        n_blocks, block_e, row0, row_end = moe_block_plan(
            counts, block, n_max)

    if interpret is not None:
        with jax.named_scope("moe.dispatch"):
            xs = _moe_rows_in(x.astype(w_up.dtype), tok, row0, n_blocks,
                              block)
        with jax.named_scope("moe.experts"):
            stacks = (w_gate, w_up, w_down)
            if layer is None:
                stacks, layer = [w if w is None else w[None]
                                 for w in stacks], 0
            ys = moe_grouped_mlp(
                xs, *stacks, jnp.asarray(layer, jnp.int32), n_blocks,
                block_e, block=block, act=act, interpret=interpret)
        with jax.named_scope("moe.dispatch"):
            return _moe_rows_out(
                ys, moe_pair_slots(counts, block, pair_e, place).reshape(N, k),
                weights.astype(jnp.float32))

    def run_block(j, ys):
        r0 = row0[j]
        rows = r0 + jnp.arange(block, dtype=jnp.int32)
        t = lax.dynamic_slice(tok, (r0,), (block,))
        xb = jnp.where((rows < row_end[j])[:, None], x[t], 0.0)
        wg, wu, wd = (w if w is None else _dyn_expert(w, block_e[j], layer)
                      for w in (w_gate, w_up, w_down))
        hid = act(xb @ wu) if wg is None else act(xb @ wg) * (xb @ wu)
        return lax.dynamic_update_slice(ys, hid @ wd, (r0, 0))

    with jax.named_scope("moe.experts"):
        ys = jnp.zeros((NK + block, D), jnp.float32)
        ys = lax.fori_loop(0, n_blocks, run_block, ys)
    with jax.named_scope("moe.dispatch"):
        return jnp.sum(ys[place].reshape(N, k, D)
                       * weights.astype(jnp.float32)[..., None], axis=1)


# The v5e's ridge, FLOP a byte: 197 TFLOP/s over 819 GB/s. The experts'
# weights are read from HBM in either form, and N rows against one read
# of a bf16 [D, I] matrix are N FLOP a byte: under this many rows
# computing EVERY expert for every row hides under that read and is
# free; from here up the dense form is bound by its arithmetic, E / k
# times what the router chose (Qwen3-30B-A3B's 128 top-8 on 256 rows:
# 309 GFLOP a layer = 1.93 ms at 81% of the MXU's peak, against 1.48 ms
# to read every expert once and less where some hold no pair; PERF.md,
# PR 66). One read at every row count under it: the dense arm's down
# product contracts (e, i) at once, with the gate inside, on w_down
# [E, I, D] as stored (moe_experts' docstring: a product that keeps e
# has the whole stack relaid once an execution from 128 rows up)
_MOE_RIDGE_ROWS = 240


def _moe_use_blocked(mesh, n_tokens: int, n_experts: int,
                     top_k: int) -> bool:
    """The dense form while its arithmetic hides under one read of the
    weights, the sorted dispatch from the chip's ridge up, and only on
    UNSHARDED execution.

    Under ``_MOE_RIDGE_ROWS`` rows the dense-over-experts einsum is
    bound by the one read of the experts' weights that the sorted form
    pays as well (every decode window of a token a step, N = 1..128, and
    a PB 1 x T 128 prefill); from there up (a PB 1 x T 256 chunk, the
    block window's [64, 4] forward) the dense form computes E/k times
    the row-MLPs that were routed, and the sorted form only the blocks
    that hold a live pair, reading only the experts that own one.

    Under any >1-device mesh the tokens/experts are GSPMD-sharded and
    the sort/gather would turn into cross-device gathers — there the
    dense einsum (whose E axis shards cleanly over the "expert" mesh
    axis) stays the right program."""
    return (top_k < n_experts and n_tokens >= _MOE_RIDGE_ROWS
            and (mesh is None or mesh.size == 1))


def moe_experts(x: jax.Array, weights, idx, w_gate, w_up, w_down,
                blocked: bool, live=None, layer=None,
                out_dtype=jnp.float32, first=None,
                width: Optional[int] = None, act=jax.nn.silu,
                identity_from: int = 0) -> jax.Array:
    """The routed experts' MLPs on x [B, T, D], given a gate's output
    (weights, idx: [B, T, k]): the execution half of an MoE MLP, shared
    by every gate (the softmax top-k of ``_moe_mlp``, the sigmoid gate of
    mla.py, which lfm2.py uses too). Two forms; the CALLER picks
    (``_moe_use_blocked`` holds the rule by shape):
    - ``blocked``: ``moe_experts_blocked`` sorted dispatch: work follows
      the live (token, expert) pairs; ``live`` [B, T] bool marks the
      rows that are not padding (None = all), and with ``layer`` the w_*
      are the whole [L, E, ...] parameters, read in place.
    - dense einsum over ALL experts weighted by the routing mask:
      dispatches of fewer rows than the chip's ridge (one read of the
      weights bounds both forms) and expert-parallel meshes (GSPMD
      shards the E axis of the einsum; the sorted form's dynamic expert
      indexing would all-gather). The gate's weight multiplies the activation
      ``act(x w_gate) * (x w_up)`` [B, T, E, I] and the down product is
      ONE contraction over (e, i) with ``w_down`` [E, I, D]: the stack
      is read in the layout it is stored in at every row count, and no
      [B, T, E, D] intermediate exists. (A down product that keeps e,
      summed under the gate afterwards, wants I minor on ``w_down`` from
      128 rows up; a caller slices a layer's stack inside its loop over
      layers, so the compiler relays the WHOLE stack at the program's
      entry, once an execution: 6.5 ms of cell 10's 85 ms window, 5.0 of
      cell 11's 142. The flat ``[N, E*I] @ [E*I, D]`` relays ``w_gate``
      and ``w_up`` instead. PERF.md, PR 59.)
    float32 operands and accumulation in both; the result in
    ``out_dtype``.

    ``w_gate`` None: an expert that is NOT gated, ``act(x w_up) w_down``,
    two matrices, each read once in every form (models/nemotron_h.py:
    ``act`` = ``relu2``; handing ``w_up`` in as the gate too would give
    relu(a) a, the same number, and read and multiply every expert
    twice).

    ``first`` tells the layer WHICH experts it holds (the chip's share
    of a layer under expert parallelism): None = all of them, idx counts
    the w_* stacks' own E. An int: the gate routed over more experts than
    are here, idx counts the gate's outputs, the stacks hold experts
    ``[first, first + E)``, and only the pairs whose expert lies there
    are computed (dense: their one-hot over the held range, all zeros
    for an absent one; sorted: an absent pair is not live; ``width``,
    the experts the gate scored, sizes its blocks by the pairs that stay
    here). The result is this share's part of the sum; nothing stands in
    for the rest.

    ``identity_from`` > 0: the gate's outputs from that index on are
    identity (zero-compute) experts, which have no weights: such a pair
    adds the token itself times its gate weight (``_identity_part``,
    float32, in both forms and for every row handed in, whoever holds
    the real experts: it goes with the token). The real outputs are
    ``[0, identity_from)`` and the stacks hold ``[first, first + E)`` of
    THOSE (``first`` None is read as 0), so an identity pair is never a
    one-hot of the dense form and never a live pair of the sorted one.
    0 = the gate has none, and nothing here is traced differently."""
    B, T, D = x.shape
    E = w_up.shape[-3]
    k = idx.shape[-1]
    if identity_from:
        first = first or 0
        out = moe_experts(x, weights, idx, w_gate, w_up, w_down, blocked,
                          live, layer, jnp.float32, first, width, act)
        return (out + _identity_part(x, weights, idx, identity_from)
                ).astype(out_dtype)
    if blocked:
        out = moe_experts_blocked(
            x.reshape(B * T, D).astype(jnp.float32),
            weights.reshape(B * T, k), idx.reshape(B * T, k),
            w_gate, w_up, w_down, moe_block(B * T, k, w_up.shape, width),
            live=None if live is None else live.reshape(B * T),
            layer=layer, act=act, first=first)
        return out.reshape(B, T, D).astype(out_dtype)
    with jax.named_scope("moe.router"):
        held = idx if first is None else idx - first
        full_gate = jnp.sum(
            jax.nn.one_hot(held, E, dtype=jnp.float32) * weights[..., None],
            axis=2)
    # dense-over-experts: out = sum_e gate[...,e] * mlp_e(x), the gate
    # inside the down product: ONE contraction over (e, i)
    with jax.named_scope("moe.experts"):
        def into(w):
            return jnp.einsum("btd,edi->btei", x.astype(jnp.float32),
                              w.astype(jnp.float32))

        if w_gate is None:
            hid = act(into(w_up))
        else:
            ge, up = into(w_gate), into(w_up)
            hid = act(ge) * up
        out = jnp.einsum("btei,eid->btd", hid * full_gate[..., None],
                         w_down.astype(jnp.float32))
        return out.astype(out_dtype)


def _identity_part(x: jax.Array, weights: jax.Array, idx: jax.Array,
                   identity_from: int) -> jax.Array:
    """What a token's identity pairs add, [B, T, D] float32:
    ``x * sum_k w_k [idx_k >= identity_from]`` (``moe_experts``)."""
    with jax.named_scope("moe.zero"):
        w0 = jnp.sum(jnp.where(idx >= identity_from,
                               weights.astype(jnp.float32), 0.0), axis=-1)
        return x.astype(jnp.float32) * w0[..., None]


def held_first(cfg: ModelConfig):
    """``moe_experts``' ``first``: None where every expert the router
    scores is here, else the index of the first one held."""
    return None if cfg.router_width == cfg.num_experts else cfg.first_expert


def pairs_counted(cfg: ModelConfig, idx: jax.Array,
                  valid: jax.Array) -> jax.Array:
    """What a decode window counts of one layer's gate (granite.py
    ``WINDOW_COUNTS``): the (token, expert) pairs the router chose for
    the ``valid`` [B, T] rows, and those whose expert is held here."""
    here = ((idx >= cfg.first_expert)
            & (idx < cfg.first_expert + cfg.num_experts))
    counted = [cfg.num_experts_per_tok * jnp.sum(valid),
               jnp.sum(here & valid[..., None])]
    if cfg.zero_experts:
        # a third count where the router has identity outputs
        # (longcat_flash.py ``WINDOW_COUNTS``): the pairs that chose one
        counted.append(jnp.sum((idx >= cfg.identity_from)
                               & valid[..., None]))
    return jnp.stack(counted).astype(jnp.int32)


def deepseek_gate(x32, w_router, bias, cfg: ModelConfig, precision=None):
    """DeepSeek router → (weights [B, T, k], expert indices [B, T, k]).
    ``precision``: of the logits' product (None: the backend's default,
    which on a TPU is ONE bf16 pass over x32; models/nemotron_h.py asks
    for ``HIGHEST``).

    v2 (HF DeepseekV2MoEGate): softmax scores; optional group limiting by
    the MAX score per group; top-k; weights scaled (NOT renormalized).
    longcat_flash: v2's softmax scores, selection by scores + bias as
    v3's, no groups; weights scaled (NOT renormalized).
    v3 (HF DeepseekV3TopkRouter): sigmoid scores; selection by scores +
    e_score_correction_bias with groups ranked by their top-2 SUM; the
    applied weights are the ORIGINAL sigmoid scores of the selected
    experts, optionally renormalized, then scaled."""
    E = w_router.shape[-1]
    k = cfg.num_experts_per_tok
    logits = jnp.matmul(x32, w_router.astype(jnp.float32),
                        precision=precision)
    if cfg.moe_router == "deepseek_v3":
        scores = jax.nn.sigmoid(logits)
        # no selection bias where the family has none (cohere2_moe)
        choice = (scores if bias is None
                  else scores + bias.astype(jnp.float32))
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        # longcat_flash: the softmax scores WITH a selection bias (v2 has
        # none), no groups, the chosen scores scaled and not renormalised
        choice = (scores + bias.astype(jnp.float32)
                  if cfg.moe_router == "longcat_flash" else scores)
    if cfg.n_group > 0 and cfg.topk_group > 0:
        G = cfg.n_group
        cg = choice.reshape(*choice.shape[:-1], G, E // G)
        if cfg.moe_router == "deepseek_v3":
            g_scores = jnp.sum(lax.top_k(cg, 2)[0], axis=-1)
        else:
            g_scores = jnp.max(cg, axis=-1)
        _, g_idx = lax.top_k(g_scores, cfg.topk_group)
        g_mask = jnp.sum(jax.nn.one_hot(g_idx, G, dtype=jnp.float32),
                         axis=-2)
        choice = jnp.where(g_mask[..., :, None] > 0, cg,
                           0.0).reshape(choice.shape)
    _, topi = lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, topi, axis=-1)
    # v3 (HF DeepseekV3TopkRouter): optional renorm, then ALWAYS scaled.
    # v2: transformers' DeepseekV2MoEGate ignores norm_topk_prob (always
    # scales); configs setting it are rejected at ModelConfig load.
    if cfg.moe_router == "deepseek_v3" and cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.moe_renorm_eps)
    w = w * cfg.routed_scaling_factor
    return w, topi


def deepseek_moe_mlp(x: jax.Array, lp, cfg: ModelConfig, mesh=None,
                     live=None, layer=None, first=None,
                     gate=None) -> jax.Array:
    """Routed experts plus the always-on shared experts, on x [B, T, D].

    ``lp`` holds one layer's router, bias and shared-expert leaves. The
    routed experts run through ``moe_experts``, the execution
    every gate shares: either that layer's ``[E, ...]`` stacks (the
    dense einsum over every expert: dispatches under the chip's ridge,
    where one read of the weights bounds both forms, and expert-parallel
    meshes)
    or, with ``layer`` (a traced index into the expert segment), the
    whole ``[Lm, E, ...]`` parameters read in place by the sorted
    blocked dispatch, whose work follows the ``live`` (token, expert)
    pairs (_moe_use_blocked holds the rule; the callers apply it).
    ``first``: which experts the stacks hold of those the gate scored
    (``moe_experts``: a chip's share of the layer; None = all).
    ``gate``: the gate's (weights, indices) where the caller has made
    them already (models/kimi_linear.py counts the pairs held)."""
    x32 = x.astype(jnp.float32)
    if gate is None:
        with jax.named_scope("moe.router"):
            gate = deepseek_gate(x32, lp["w_router"],
                                 lp.get("router_bias"), cfg)
    w, topi = gate
    out = moe_experts(x32, w, topi, lp["w_gate_e"], lp["w_up_e"],
                      lp["w_down_e"], layer is not None, live=live,
                      layer=layer, first=first,
                      width=None if first is None else cfg.router_width,
                      identity_from=cfg.identity_from)
    if cfg.n_shared_experts > 0:
        with jax.named_scope("moe.shared"):
            shared = (jax.nn.silu(x @ lp["w_gate_s"])
                      * (x @ lp["w_up_s"])) @ lp["w_down_s"]
            if cfg.shared_expert_scale != 1.0:
                # shared experts that are AVERAGED: the stacks hold them
                # side by side, so their sum is one MLP and the mean a
                # multiple of it
                shared = shared * jnp.asarray(cfg.shared_expert_scale,
                                              shared.dtype)
            out = out + shared
    return out.astype(x.dtype)


def _moe_mlp(h: jax.Array, w_router, w_gate, w_up, w_down,
             top_k: int, mesh=None, live=None, layer=None,
             act=jax.nn.silu, logits=None) -> jax.Array:
    """Mixtral-style MoE MLP: token-choice top-k routing, softmax over
    the chosen logits, then ``moe_experts`` in the form the shape rule
    picks (sorted wherever ``layer`` says the parameters are whole).
    ``logits`` [B, T, E] float32: the router's, where they were made
    before this point (``router_logits``: a router that reads the
    layer's input); ``w_router`` is then not read."""
    B, T, _ = h.shape
    E = w_gate.shape[-3]
    with jax.named_scope("moe.router"):
        if logits is None:
            logits = (h @ w_router).astype(jnp.float32)  # [B, T, E]
        weights, idx = lax.top_k(logits, top_k)  # [B, T, k]
        weights = jax.nn.softmax(weights, axis=-1)
    return moe_experts(
        h, weights, idx, w_gate, w_up, w_down,
        layer is not None or _moe_use_blocked(mesh, B * T, E, top_k),
        live=live, layer=layer, out_dtype=h.dtype, act=act)


def router_logits(h: jax.Array, w_router) -> jax.Array:
    """The router's logits [B, T, E] float32 on ``h`` as it is handed in:
    for a router that reads the layer's un-normed input, before attention
    (``cfg.moe_early_router``), called at the layer's entry."""
    with jax.named_scope("moe"), jax.named_scope("moe.router"):
        return (h @ w_router).astype(jnp.float32)


def _ff_out(x, lp, cfg: ModelConfig, mesh, experts=None, live=None,
            l_idx=None, logits=None, valid=None):
    """What the second half of a layer adds, on the normed ``x``: the
    MLP, the Mixtral-style routed experts, or (``cfg.moe_router`` of the
    DeepSeek kind) the sigmoid gate's experts HELD here beside the shared
    ones (``deepseek_moe_mlp``). ``experts``: the stacked (gate, up,
    down) weights of every layer, read in place at ``l_idx`` by the
    sorted dispatch, with ``live`` the rows that make pairs (a prefill);
    else ``lp`` holds the layer's own. ``logits``: the router's, made at
    the layer's entry. Returns (out, counted): ``counted`` is
    ``pairs_counted`` of the ``valid`` rows where the gate is the
    DeepSeek kind and ``valid`` is given (a decode window), else None."""
    if cfg.num_experts == 0:
        return _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                    _act(cfg)), None
    if experts is None:     # the layer's own stacks: the dense form
        experts = (lp["w_gate"], lp["w_up"], lp["w_down"])
        live = l_idx = None
    with jax.named_scope("moe"):
        if cfg.moe_router == "mixtral":
            return _moe_mlp(x, lp["w_router"], *experts,
                            cfg.num_experts_per_tok, mesh=mesh, live=live,
                            layer=l_idx, act=_act(cfg), logits=logits), None
        with jax.named_scope("moe.router"):
            gate = deepseek_gate(x.astype(jnp.float32), lp["w_router"],
                                 lp.get("router_bias"), cfg)
            counted = (None if valid is None
                       else pairs_counted(cfg, gate[1], valid))
        mp = {**lp, **dict(zip(("w_gate_e", "w_up_e", "w_down_e"), experts))}
        return deepseek_moe_mlp(x, mp, cfg, mesh, live=live, layer=l_idx,
                                first=held_first(cfg), gate=gate), counted


def _layer_ff(h, lp, cfg: ModelConfig, mesh, experts=None, live=None,
              l_idx=None, logits=None):
    """The second half of a sequential layer: h + ``_ff_out`` of
    norm(h)."""
    x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    mlp_out, _ = _ff_out(x, lp, cfg, mesh, experts, live, l_idx, logits)
    return _residual_add(h, mlp_out, lp, "ln_mlp_post", cfg)


def _at(params: Params, keys, i):
    """One layer's leaves of the stacks named, by a (traced) index: the
    slice a scan over the stack would make, where the stacks are not cut
    into runs first (jamba.py, lfm2.py, mla.py)."""
    return {k: lax.dynamic_index_in_dim(params[k], i, 0, False)
            for k in keys}


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array, kv_k: jax.Array, kv_v: jax.Array,
            page_table: jax.Array, flat_slots: jax.Array,
            allow_pallas: bool = True, page_slots: Optional[jax.Array] = None,
            mesh=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared prefill/decode forward.

    tokens: [B, T] (T=1 for decode); positions: [B, T] absolute positions
    (-1 for padding rows); page_table: [B, P]; flat_slots: [B, T] cache
    write slots (page*page_size + offset of the position's own page in
    ``page_table``; DROP_SLOT or -1 drops a padding token);
    page_slots: optional [B, T // ps] page-granular write path for
    aligned prefill chunks (see _scatter_pages_paged).

    The pools ride the scan over the layers as its CARRY, each seen as
    [L * pages, KV, ps, hd]: layer l writes (_write_layer_pages) and
    reads (the table shifted by l * pages) its own pages of the donated
    buffer, so a program touches the pages of its chunk and no more. As
    scanned xs / ys every layer's pool was sliced out, updated and put
    into a stacked result that was then copied over the donated buffer:
    six pool-sized ops a program, whatever the chunk held (PERF.md,
    Findings PR 49).

    Returns (hidden [B, T, D], new_kv_k, new_kv_v).
    """
    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    B, T = tokens.shape

    h = embed_tokens(params, cfg, tokens)  # [B, T, D]
    safe_pos = jnp.maximum(positions, 0)

    layer_params = {k: params[k] for k in _layer_keys(cfg)}
    # the sorted MoE dispatch reads w[layer, expert] from the stacked
    # parameters: as scanned xs each layer's whole expert stack would be
    # sliced out (copied) before the block loop may index it
    moe_in_place = cfg.num_experts > 0 and _moe_use_blocked(
        mesh, B * T, cfg.num_experts, cfg.num_experts_per_tok)
    experts = live = None
    if moe_in_place:
        experts = [layer_params.pop(k) for k in ("w_gate", "w_up", "w_down")]
        live = positions >= 0   # padding rows make no (token, expert) pair

    pages = kv_k.shape[1]
    paged = page_slots is not None
    slots = page_slots if paged else flat_slots

    def layer(carry, xs):
        h, fk, fv = carry
        lp, l_idx = xs
        off = l_idx * pages
        with jax.named_scope("attn"):
            x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                         cfg.norm_unit_offset)
            xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
            if cfg.attn_bias:
                xq, xk, xv = xq + lp["bq"], xk + lp["bk"], xv + lp["bv"]
            q = xq.reshape(B, T, H, hd)
            k = xk.reshape(B, T, KV, hd)
            v = xv.reshape(B, T, KV, hd)
            q, k = _qk_headnorm(q, k, lp, cfg)
            q = apply_rope(q, safe_pos, inv_freq)
            k = apply_rope(k, safe_pos, inv_freq)
            if cfg.block_length > 1:
                k, v = _block_kv(k, v, fk.dtype)
            fk = _write_layer_pages(fk, k, slots, off, pages, page_table,
                                    positions, paged)
            fv = _write_layer_pages(fv, v, slots, off, pages, page_table,
                                    positions, paged)
            attn = _attention(q, fk, fv, page_table + off, positions,
                              scale, allow_pallas=allow_pallas, mesh=mesh,
                              softcap=cfg.attn_logit_softcap,
                              window=cfg.sliding_window,
                              is_sliding=_window_flag(cfg, l_idx),
                              block=cfg.block_length)
            h = _residual_add(h, attn.reshape(B, T, H * hd) @ lp["wo"], lp,
                              "ln_attn_post", cfg)
        h = _layer_ff(h, lp, cfg, mesh, experts, live, l_idx)
        return (h, fk, fv), None

    (h, fk, fv), _ = lax.scan(
        layer, (h, _flat_pool(kv_k), _flat_pool(kv_v)),
        (layer_params, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    return h, fk.reshape(kv_k.shape), fv.reshape(kv_v.shape)


def logits_at(params: Params, cfg: ModelConfig, hidden: jax.Array,
              gather_idx: jax.Array) -> jax.Array:
    """LM head at selected positions. hidden: [B, T, D];
    gather_idx: [B] position per row → logits [B, V] (float32)."""
    B = hidden.shape[0]
    with jax.named_scope("lm_head"):
        h_last = hidden[jnp.arange(B), gather_idx]  # [B, D]
    return project_logits(params, cfg, h_last)


def wanted(last_idx: jax.Array) -> jax.Array:
    """Whether any row of a prefill program wants its logits: a negative
    ``last_idx`` says the row's are read by nobody (a chunk that ends no
    prompt, a padding row: ``JaxEngine._dispatch_prefill`` writes them)."""
    return jnp.any(last_idx >= 0)


def no_logits(cfg: ModelConfig, B: int) -> jax.Array:
    """What a prefill program returns for logits nobody wanted."""
    with jax.named_scope("lm_head"):
        return jnp.zeros((B, cfg.vocab_size), jnp.float32)


def prefill_logits(params: Params, cfg: ModelConfig, hidden: jax.Array,
                   last_idx: jax.Array) -> jax.Array:
    """``logits_at`` as a PREFILL program ends: the head under one
    conditional on ``wanted(last_idx)``, so a program that ends no
    prompt does not read ``lm_head``; it returns zeros [B, V] float32.
    Where a row wants logits the arithmetic is ``logits_at``'s, and a
    row with a negative entry gets position 0's, which nobody reads.
    Both arms are in the one executable of the bucket. The decode steps,
    the windows and the verify forwards call ``logits_at``: every row of
    theirs is sampled from."""
    B = hidden.shape[0]
    with jax.named_scope("lm_head"):
        h_last = hidden[jnp.arange(B), jnp.maximum(last_idx, 0)]  # [B, D]
    return lax.cond(wanted(last_idx),
                    lambda: project_logits(params, cfg, h_last),
                    lambda: no_logits(cfg, B))


# ------------------------------------------- layers of two kinds, two pools


def layer_period(cfg: ModelConfig) -> int:
    """The shortest period of the per-layer layout (window, rotation)
    that divides the depth: the layers a scan step unrolls, so that each
    layer's kind is static."""
    L = cfg.num_layers
    kinds = [(cfg.layer_window[l], cfg.rotates(l)) for l in range(L)]
    return next(p for p in range(1, L + 1)
                if L % p == 0 and all(kinds[l] == kinds[l % p]
                                      for l in range(L)))


def _relative(positions: jax.Array, base: jax.Array) -> jax.Array:
    """Positions counted from a row's ``base`` (the first position of
    slot 0 of its window table); padding (-1) stays -1. The masks of
    attention and the commit compare positions with each other and with
    the table's slots, so they take these as they take absolute ones."""
    return jnp.where(positions >= 0,
                     positions - base.reshape((-1,) + (1,) * (positions.ndim
                                                              - 1)), -1)


def _qkv(cfg: ModelConfig, lp, x: jax.Array, pos: jax.Array, inv_freq,
         rotate: bool):
    """q [B, T, H, hd], k, v [B, T, KV, hd] of one layer on the normed
    ``x``; the rotary embedding only where the layer's layout has it."""
    B, T, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn.proj"):
        xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
        if cfg.attn_bias:
            xq, xk, xv = xq + lp["bq"], xk + lp["bk"], xv + lp["bv"]
        # the layout the heads' consumers want is paid on the activation:
        # without the fence the compiler carries it (and the interleaved
        # rotation's de-interleave) back through the product onto the
        # layer's weights, and relays them once a program at its entry
        xq, xk, xv = lax.optimization_barrier((xq, xk, xv))
    q, k = _qk_headnorm(xq.reshape(B, T, H, hd), xk.reshape(B, T, KV, hd),
                        lp, cfg)
    if rotate:
        q = apply_rope(q, pos, inv_freq, cfg.rope_interleave)
        k = apply_rope(k, pos, inv_freq, cfg.rope_interleave)
    return q, k, xv.reshape(B, T, KV, hd)


def _attn_out(cfg: ModelConfig, lp, h, attn):
    """Attention's heads ``attn`` [B, T, H * hd] through ``wo``, under
    the scope ``attn``: added to the stream ``h`` in a sequential layer;
    in a PARALLEL block (``cfg.parallel_block``) the product alone,
    which ``_second_half`` adds."""
    with jax.named_scope("attn.proj"):
        out = attn @ lp["wo"]
    return out if cfg.parallel_block else _residual_add(
        h, out, lp, "ln_attn_post", cfg)


def _second_half(cfg: ModelConfig, lp, h, x, a, mesh, experts=None,
                 live=None, l_idx=None, logits=None, valid=None):
    """(the layer's output, what ``_ff_out`` counted) from ``a`` =
    ``_attn_out``'s. A sequential layer hands the stream with attention
    added to ``_layer_ff`` (a norm of its own); a parallel block runs the
    second half on the layer's ONE normed input ``x`` and adds both
    branches to the stream once."""
    if not cfg.parallel_block:
        return _layer_ff(a, lp, cfg, mesh, experts, live, l_idx,
                         logits), None
    ff, counted = _ff_out(x, lp, cfg, mesh, experts, live, l_idx, logits,
                          valid)
    return h + a + ff, counted


def _forward_by_kind(params: Params, cfg: ModelConfig, tokens, positions,
                     kv_k, kv_v, page_table, flat_slots, wkv, wtab,
                     allow_pallas: bool = True, page_slots=None, mesh=None):
    """``forward`` for a configuration whose layers are of two kinds with
    a pool each (``cfg.kv_pool_by_kind``): layers that see the whole
    context write and read ``kv_k`` / ``kv_v`` [L_full, pages, ...] by
    ``page_table``, layers held to the window ``wkv`` = (K, V)
    [L_win, pages_w, ...] by the row's window table. ``wtab`` =
    (table [B, S_w], base [B], slots): slot s of a row's table is the
    page of positions ``base + s * ps ...``, so the table, the kernel's
    grid and what it copies follow the window and not the context;
    ``slots`` are the window pool's write slots in the form of
    ``flat_slots`` ([B, T]) or, where ``page_slots`` is given, of
    ``page_slots`` ([B, T // ps]).

    One scan over the periods of the layout, a period's layers unrolled:
    a layer's window, its rotation and its pool are static, and each
    attention call is handed its kind's pool, table and window. A kind's
    pool is seen as [layers * pages, ...], so that a layer's pages are
    written and read along the major axis and no layer is sliced out
    (lfm2.py's form). The router reads the layer's input
    where ``cfg.moe_early_router``. The layer's form follows what the
    configuration has, in Python at trace time: the norm (``_norm``), the
    rotation's pairs (``_qkv``), a sequential layer or a parallel block
    (``_attn_out`` / ``_second_half``) and the second half's kind
    (``_ff_out``); a configuration that has none of the newer forms
    lowers to the program it lowered to before they existed.
    Returns (hidden, kv_k, kv_v, wkv)."""
    wk, wv = wkv
    wtable, wbase, wslots = wtab
    inv_freq = rope_freqs(cfg)
    B, T = tokens.shape
    H, hd = cfg.num_heads, cfg.head_dim_
    p = layer_period(cfg)
    n_per = cfg.num_layers // p
    windows = cfg.layer_window[:p]
    n_win = sum(w is not None for w in windows)
    n_full = p - n_win
    NPf, NPw = kv_k.shape[1], wk.shape[1]
    h = embed_tokens(params, cfg, tokens)
    safe_pos = jnp.maximum(positions, 0)
    rel_pos = _relative(positions, wbase)
    keys = _layer_keys(cfg)
    experts = live = None
    if cfg.num_experts > 0 and _moe_use_blocked(
            mesh, B * T, cfg.num_experts, cfg.num_experts_per_tok):
        # the sorted dispatch reads w[layer, expert] in place (forward)
        keys = [k for k in keys if k not in ("w_gate", "w_up", "w_down")]
        experts = [params[k] for k in ("w_gate", "w_up", "w_down")]
        live = positions >= 0

    paged = page_slots is not None
    slots = page_slots if paged else flat_slots

    def period(carry, per):
        h, fk, fv, pk, pv = carry
        a_full = a_win = 0
        for j, window in enumerate(windows):
            l_idx = per * p + j
            lp = _at(params, keys, l_idx)
            logits = (router_logits(h, lp["w_router"])
                      if cfg.moe_early_router else None)
            with jax.named_scope("attn"):
                x = _norm(cfg, h, lp["ln_attn"])
                q, k, v = _qkv(cfg, lp, x, safe_pos, inv_freq, cfg.rotates(j))
                if window is None:
                    off = (per * n_full + a_full) * NPf
                    a_full += 1
                    fk = _write_layer_pages(fk, k, slots, off, NPf,
                                            page_table, positions, paged)
                    fv = _write_layer_pages(fv, v, slots, off, NPf,
                                            page_table, positions, paged)
                    with jax.named_scope("attn.full"):
                        attn = _attention(
                            q, fk, fv, page_table + off, positions,
                            cfg.attn_scale, allow_pallas=allow_pallas,
                            mesh=mesh, softcap=cfg.attn_logit_softcap)
                else:
                    off = (per * n_win + a_win) * NPw
                    a_win += 1
                    pk = _write_layer_pages(pk, k, wslots, off, NPw, wtable,
                                            rel_pos, paged)
                    pv = _write_layer_pages(pv, v, wslots, off, NPw, wtable,
                                            rel_pos, paged)
                    with jax.named_scope("attn.window"):
                        attn = _attention(
                            q, pk, pv, wtable + off, rel_pos,
                            cfg.attn_scale, allow_pallas=allow_pallas,
                            mesh=mesh, softcap=cfg.attn_logit_softcap,
                            window=window, is_sliding=True)
                a = _attn_out(cfg, lp, h, attn.reshape(B, T, H * hd))
            h, _ = _second_half(cfg, lp, h, x, a, mesh, experts, live, l_idx,
                                logits)
        return (h, fk, fv, pk, pv), None

    # the pools ride the scan as its CARRY, each seen as [layers * pages,
    # ...] (forward's form): as scanned xs / ys the results would be
    # buffers of their own, a second copy of every pool among the
    # temporaries
    (h, fk, fv, pk, pv), _ = lax.scan(
        period, (h, _flat_pool(kv_k), _flat_pool(kv_v), _flat_pool(wk),
                 _flat_pool(wv)),
        jnp.arange(n_per, dtype=jnp.int32))
    h = _norm(cfg, h, params["ln_final"])
    return (h, fk.reshape(kv_k.shape), fv.reshape(kv_v.shape),
            (pk.reshape(wk.shape), pv.reshape(wv.shape)))


def _make_step_fns_by_kind(cfg: ModelConfig, allow_pallas: bool, mesh):
    """``make_step_fns`` for a configuration with a pool a kind of layer:
    the same two programs under the same names, with the window layers'
    pools and tables as trailing operands (``wkv``, ``wtab``:
    ``_forward_by_kind``) and the pools returned last, as a model with
    state returns its state."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "wkv"))
    def prefill_step(params, tokens, positions, kv_k, kv_v, page_table,
                     flat_slots, last_idx, page_slots, wkv, wtab):
        h, kv_k2, kv_v2, wkv2 = _forward_by_kind(
            params, cfg, tokens, positions, kv_k, kv_v, page_table,
            flat_slots, wkv, wtab, allow_pallas=allow_pallas,
            page_slots=page_slots, mesh=mesh)
        return prefill_logits(params, cfg, h, last_idx), kv_k2, kv_v2, wkv2

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v", "wkv"))
    def decode_step(params, tokens, positions, kv_k, kv_v, page_table,
                    flat_slots, wkv, wtab):
        h, kv_k2, kv_v2, wkv2 = _forward_by_kind(
            params, cfg, tokens[:, None], positions[:, None], kv_k, kv_v,
            page_table, flat_slots[:, None], wkv, wtab,
            allow_pallas=allow_pallas, mesh=mesh)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)),
                kv_k2, kv_v2, wkv2)

    return prefill_step, decode_step


# ----------------------------------------------------- jitted entry points


def _one_pool_only(cfg: ModelConfig) -> None:
    """The programs over one pool rotate q and k in every layer."""
    assert all(cfg.rotates(l) for l in range(cfg.num_layers)), \
        "a layer without rotation runs _forward_by_kind (kv_pool_by_kind)"


def make_step_fns(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """Build the jitted (prefill_step, decode_step) pair for one config.

    Closures instead of static args because ModelConfig holds dicts
    (rope_scaling). The KV buffers are donated and ride ``forward``'s scan
    as its carry, so the pools a program returns ARE its operands, with
    the chunk's pages scattered in: no op has a pool-sized output of its
    own (tests/test_tpu_compile.py).
    With a >1-device ``mesh`` the Pallas attention kernels run per
    model-shard via shard_map (see _attention); ``allow_pallas=False``
    forces the XLA gather path everywhere.
    """
    if cfg.kv_pool_by_kind:
        return _make_step_fns_by_kind(cfg, allow_pallas, mesh)
    _one_pool_only(cfg)

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def prefill_step(params: Params, tokens: jax.Array, positions: jax.Array,
                     kv_k: jax.Array, kv_v: jax.Array, page_table: jax.Array,
                     flat_slots: jax.Array, last_idx: jax.Array,
                     page_slots: Optional[jax.Array] = None):
        """Process prompt chunks [B, T]; returns (logits [B, V], kv_k, kv_v).
        For a configuration that generates by blocks the logits are None:
        its prefill yields no token (the logits at a prompt's last
        position are of that position's own token), so the program has
        no head."""
        h, kv_k2, kv_v2 = forward(params, cfg, tokens, positions, kv_k, kv_v,
                                  page_table, flat_slots,
                                  allow_pallas=allow_pallas,
                                  page_slots=page_slots, mesh=mesh)
        if cfg.block_length > 1:
            return None, kv_k2, kv_v2
        return prefill_logits(params, cfg, h, last_idx), kv_k2, kv_v2

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def decode_step(params: Params, tokens: jax.Array, positions: jax.Array,
                    kv_k: jax.Array, kv_v: jax.Array, page_table: jax.Array,
                    flat_slots: jax.Array):
        """One decode step: tokens [B], positions [B] →
        (logits [B, V], kv_k, kv_v)."""
        h, kv_k2, kv_v2 = forward(params, cfg, tokens[:, None],
                                  positions[:, None], kv_k, kv_v,
                                  page_table, flat_slots[:, None], mesh=mesh,
                                  allow_pallas=allow_pallas)
        return (logits_at(params, cfg, h,
                          jnp.zeros(tokens.shape[0], jnp.int32)),
                kv_k2, kv_v2)

    return prefill_step, decode_step


def make_verify_fn(cfg: ModelConfig, allow_pallas: bool = True, mesh=None):
    """Speculative-verify forward: ONE [B, K+1] multi-token decode step
    against the paged pool, returning logits at EVERY position (unlike
    prefill_step's last-position gather — the accept mask needs the
    greedy target after each draft token).

    Reuses the chunked-prefill program shape exactly: the K+1 input
    tokens' K/V scatter into their page slots before attention, and the
    causal position mask lets draft token j attend to drafts 0..j-1 plus
    the whole cached sequence. K is static (one compile per batch/page
    bucket), so the verify grid stays as bounded as the decode grid.

    Rejected drafts leave their K/V in slots PAST the row's accepted
    extent — harmless by the same invariant that protects prefill tail
    pages: a position's K/V is always rewritten when its real token is
    the decode input, before any query can see it (causal masking hides
    positions beyond the current query, and pages only publish to the
    prefix cache once every slot holds accepted content)."""

    @partial(jax.jit, donate_argnames=("kv_k", "kv_v"))
    def verify_step(params: Params, tokens: jax.Array, positions: jax.Array,
                    kv_k: jax.Array, kv_v: jax.Array, page_table: jax.Array,
                    flat_slots: jax.Array):
        """tokens/positions/flat_slots: [B, K+1] (-1 / DROP_SLOT padding)
        → (logits [B, K+1, V] float32, kv_k, kv_v)."""
        h, kv_k2, kv_v2 = forward(params, cfg, tokens, positions, kv_k,
                                  kv_v, page_table, flat_slots,
                                  allow_pallas=allow_pallas, mesh=mesh)
        return project_logits(params, cfg, h), kv_k2, kv_v2

    return verify_step


# ------------------------------------------------- fused decode window


def make_decode_window_fn(cfg: ModelConfig, allow_pallas: bool = True,
                          max_top_k: int = 64, mesh=None,
                          pallas_interpret: bool = False):
    """Fused K-step decode with a READ-ONLY pool and a fully on-device
    sequence carry. The pool is gathered but never written inside the
    window; the K new tokens' K/V accumulate in a small per-layer window
    buffer that attention reads alongside the pool, and ONE commit at the
    end writes the window into the pool by whole pages, in place
    (commit_window: a gather of the few pages the rows' windows touch, a
    select per step, a scatter along the pool's major axis). No op of the
    program has an output of the pool's size: an unrolled chain of full
    forward() steps makes XLA hold several pool instances (each step's
    scatter output is a new buffer) and OOMs large pools, and a single
    scatter of token ROWS at the end, which this was until PR 30, made
    the compiler relayout the pool around it: eight pool-sized copies a
    window and one pool of temporaries (see commit_window).

    The program is models/window.py's (``make_window``: the loop, the
    sampler, the on-device carry and its stop rules, the results); what
    is here is this family's buffers, one step, and the commit.

    A configuration that generates by diffusion over blocks
    (``cfg.block_length`` > 1) gets ``_make_block_window_fn``'s program
    under the same name and call form: a window of whole blocks."""
    if cfg.block_length > 1:
        return _make_block_window_fn(cfg, allow_pallas, max_top_k, mesh,
                                     pallas_interpret)
    if cfg.kv_pool_by_kind:
        return make_window(
            _window_family_by_kind(cfg, allow_pallas, mesh,
                                   pallas_interpret), max_top_k)
    _one_pool_only(cfg)
    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    # pool attention: Pallas flash kernel on TPU (streams only each row's
    # live pages HBM→VMEM, returns online-softmax stats merged with the
    # in-flight window buffer) — the XLA gather fallback re-materializes
    # the gathered pool EVERY unrolled step (the gather fuses into its
    # per-step consumer instead of hoisting), ~4.3 GB of HBM traffic per
    # step at B=32/P=32: measured 54 ms/step vs ~2 ms for the kernel.
    # Under a mesh the kernel runs per model-shard via shard_map (heads
    # follow their kv heads — ops/paged_attention.py
    # paged_attention_decode_sharded); pallas_interpret forces the kernel
    # path in interpret mode for CPU parity tests.
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    sharded = mesh is not None and mesh.size > 1
    mode = kernel_mode(allow_pallas, pallas_interpret)
    if cfg.num_kv_heads % max(tp, 1):
        mode = None
    L = cfg.num_layers

    def begin(w):
        B = w.start.shape[0]
        wdt = w.kv_k.dtype
        return (jnp.zeros((L, B, w.k_steps, KV, hd), wdt),
                jnp.zeros((L, B, w.k_steps, KV, hd), wdt))

    def step(w, bufs, tok, pos, active, i):
        # frozen (done/pad) rows still flow through the matmuls: their
        # outputs are discarded and their KV never commit (commit's mask),
        # so correctness needs no per-row control flow
        params, kv_k, kv_v = w.params, w.kv_k, w.kv_v
        page_table, start = w.page_table, w.start
        wk, wv = bufs
        B = tok.shape[0]
        wdt = kv_k.dtype
        layer_params = {k: params[k] for k in _layer_keys(cfg)}
        h = embed_tokens(params, cfg, tok)[:, None]  # [B, 1, D]
        safe_pos = jnp.maximum(pos, 0)[:, None]

        def layer(h, xs):
            # NOTE: the pools are closure-captured, NOT scanned xs —
            # scanning them makes XLA materialize a fresh per-layer
            # slice copy for each unrolled step's pallas operand
            # (≈6.4 GB/step of copy traffic at serving sizes)
            lp, l_idx, wk_l, wv_l = xs
            with jax.named_scope("attn"):
                x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                             cfg.norm_unit_offset)
                xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
                if cfg.attn_bias:
                    xq, xk, xv = (xq + lp["bq"], xk + lp["bk"],
                                  xv + lp["bv"])
                q, k = _qk_headnorm(xq.reshape(B, 1, H, hd),
                                    xk.reshape(B, 1, KV, hd), lp, cfg)
                q = apply_rope(q, safe_pos, inv_freq)
                k = apply_rope(k, safe_pos, inv_freq)
                v = xv.reshape(B, 1, KV, hd)
                wk_l = wk_l.at[:, i].set(k[:, 0].astype(wdt))
                wv_l = wv_l.at[:, i].set(v[:, 0].astype(wdt))
                attn = window_attention(
                    q, kv_k, kv_v, l_idx, page_table, start, wk_l, wv_l, i,
                    scale, mode, mesh=mesh if sharded else None,
                    softcap=cfg.attn_logit_softcap,
                    window=cfg.sliding_window,
                    is_sliding=_window_flag(cfg, l_idx),
                    q_pos=safe_pos[:, 0])
                h = _residual_add(
                    h, attn.reshape(B, 1, H * hd) @ lp["wo"], lp,
                    "ln_attn_post", cfg)
            return _layer_ff(h, lp, cfg, mesh), (wk_l, wv_l)

        h, (wk, wv) = lax.scan(
            layer, h,
            (layer_params, jnp.arange(L, dtype=jnp.int32), wk, wv))
        h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps, cfg.norm_unit_offset)
        logits = logits_at(params, cfg, h, jnp.zeros(B, jnp.int32))
        return logits, (wk, wv), None

    def commit(w, bufs, pos):
        # the window into the pool by whole pages (commit_window): entry
        # i holds the K/V of position start+i, valid only if the row was
        # still active at step i (start+i < final pos)
        wk, wv = bufs
        return (commit_window(w.kv_k, wk, w.page_table, w.start, pos),
                commit_window(w.kv_v, wv, w.page_table, w.start, pos), None)

    return make_window(Family(begin, step, commit), max_top_k)


def _window_family_by_kind(cfg: ModelConfig, allow_pallas: bool, mesh,
                           pallas_interpret: bool) -> Family:
    """The decode window's ``begin`` / ``step`` / ``commit`` for a
    configuration with a pool a kind of layer. The window layers' pools
    are the program's ``state`` operand (K, V) and ``state_slots`` the
    rows' (table [B, S_w], base [B]) into them (``_forward_by_kind``);
    both pools are read-only in the steps, the steps' K/V of every layer
    go to one buffer [L, B, K, KV, hd], and the commit writes each kind's
    layers to its own pool by whole pages, the window layers' at the
    positions counted from the row's base. Where the second half is the
    DeepSeek kind's (``_ff_out``) a step also returns what its layers
    counted of the live rows' pairs (``pairs_counted``: the module that
    runs this family declares ``WINDOW_COUNTS``, models/cohere2_moe.py)."""
    inv_freq = rope_freqs(cfg)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    mode = kernel_mode(allow_pallas, pallas_interpret, mesh)
    L = cfg.num_layers
    p = layer_period(cfg)
    windows = cfg.layer_window[:p]
    n_win = sum(w is not None for w in windows)
    n_full = p - n_win
    keys = _layer_keys(cfg)
    full_ids = jnp.asarray(cfg.full_layer_ids, jnp.int32)
    win_ids = jnp.asarray(cfg.window_layer_ids, jnp.int32)

    def begin(w):
        B = w.start.shape[0]
        wdt = w.kv_k.dtype
        return (jnp.zeros((L, B, w.k_steps, KV, hd), wdt),
                jnp.zeros((L, B, w.k_steps, KV, hd), wdt))

    def step(w, bufs, tok, pos, active, i):
        params = w.params
        (pk, pv), (wtable, wbase) = w.state, w.state_slots
        wk, wv = bufs
        B = tok.shape[0]
        wdt = w.kv_k.dtype
        h = embed_tokens(params, cfg, tok)[:, None]
        safe_pos = jnp.maximum(pos, 0)[:, None]
        rel_start = _relative(w.start, wbase)
        rel_pos = safe_pos[:, 0] - wbase

        def period(h, xs):
            per, wk_p, wv_p = xs
            a_full = a_win = 0
            ks, vs, tally = [], [], []
            for j, window in enumerate(windows):
                lp = _at(params, keys, per * p + j)
                logits = (router_logits(h, lp["w_router"])
                          if cfg.moe_early_router else None)
                with jax.named_scope("attn"):
                    x = _norm(cfg, h, lp["ln_attn"])
                    q, k, v = _qkv(cfg, lp, x, safe_pos, inv_freq,
                                   cfg.rotates(j))
                    wk_l = wk_p[j].at[:, i].set(k[:, 0].astype(wdt))
                    wv_l = wv_p[j].at[:, i].set(v[:, 0].astype(wdt))
                    if window is None:
                        a, a_full = per * n_full + a_full, a_full + 1
                        with jax.named_scope("attn.full"):
                            attn = window_attention(
                                q, w.kv_k, w.kv_v, a, w.page_table, w.start,
                                wk_l, wv_l, i, cfg.attn_scale, mode,
                                softcap=cfg.attn_logit_softcap)
                    else:
                        a, a_win = per * n_win + a_win, a_win + 1
                        with jax.named_scope("attn.window"):
                            attn = window_attention(
                                q, pk, pv, a, wtable, rel_start, wk_l, wv_l,
                                i, cfg.attn_scale, mode,
                                softcap=cfg.attn_logit_softcap,
                                window=window, is_sliding=True,
                                q_pos=rel_pos)
                    a = _attn_out(cfg, lp, h, attn.reshape(B, 1, H * hd))
                h, counted = _second_half(cfg, lp, h, x, a, mesh,
                                          logits=logits,
                                          valid=active[:, None])
                ks.append(wk_l)
                vs.append(wv_l)
                if counted is not None:
                    tally.append(counted)
            return h, (jnp.stack(ks), jnp.stack(vs),
                       sum(tally) if tally else None)

        h, (wk, wv, counted) = lax.scan(
            period, h, (jnp.arange(L // p, dtype=jnp.int32),
                        wk.reshape(L // p, p, *wk.shape[1:]),
                        wv.reshape(L // p, p, *wv.shape[1:])))
        h = _norm(cfg, h, params["ln_final"])
        logits = logits_at(params, cfg, h, jnp.zeros(B, jnp.int32))
        return logits, (wk.reshape(bufs[0].shape),
                        wv.reshape(bufs[1].shape)), (
            None if counted is None else jnp.sum(counted, axis=0))

    def commit(w, bufs, pos):
        wk, wv = bufs
        (pk, pv), (wtable, wbase) = w.state, w.state_slots
        start_w, pos_w = _relative(w.start, wbase), _relative(pos, wbase)
        return (commit_window(w.kv_k, wk[full_ids], w.page_table, w.start,
                              pos),
                commit_window(w.kv_v, wv[full_ids], w.page_table, w.start,
                              pos),
                (commit_window(pk, wk[win_ids], wtable, start_w, pos_w),
                 commit_window(pv, wv[win_ids], wtable, start_w, pos_w)))

    return Family(begin, step, commit)


# ------------------------------- block window (generation by diffusion)


def _block_kv(k, v, dtype):
    """A token's K and V in the type they are kept in, for a
    configuration that generates by blocks: the one place both of its
    programs (block-causal prefill, block window) make them, so that
    tools/diffusion_block_check.py can round them to 8 bits there and
    show that the cell's agreement check sees the cache's precision."""
    return k.astype(dtype), v.astype(dtype)


def _make_block_window_fn(cfg: ModelConfig, allow_pallas: bool,
                          max_top_k: int, mesh, pallas_interpret: bool):
    """The fused decode window of a configuration that generates by
    diffusion over blocks of L = ``cfg.block_length`` positions: a window
    of W = k_steps / L BLOCKS a row, under the name and the call form of
    ``make_decode_window_fn``'s program.

    One block of a row, at positions s .. s + L - 1 (s a multiple of L):
    the input is the block's final tokens (the prompt's tail, in a row's
    first block) and ``mask_token_id`` elsewhere. While any live row has
    a masked position (the batch takes as many forwards as its slowest
    row, at most ``cfg.denoising_steps``): one forward (scope
    ``diffusion.denoise``), then at every masked position a draw and its
    probability, and ``sampling.unmask`` makes n = ceil(masked at block
    start / denoising_steps) of them final by ``cfg.remasking_strategy``
    (scope ``diffusion.unmask``); a final position never changes again.

    The K/V of a position depends on the tokens of its whole block, so
    only a forward on the block's FINAL tokens makes K/V that may be
    kept. That forward is no forward of its own: the final tokens of
    block b (the row's PENDING block) ride as the first L queries of the
    first denoising forward of block b + 1, a [B, 2L] forward under the
    mask that is causal across blocks and bidirectional inside one. Every
    layer computes b's final K/V there and b + 1's queries attend to them
    in that same layer: the K/V and the logits of a commit forward
    followed by a denoising forward, in one read of the weights. Only the
    second L rows go through the head. Turns 2 .. S of a block are [B, L]
    forwards in a ``lax.while_loop``, and the window's W blocks one
    ``lax.fori_loop`` around both, so the program holds each forward
    once whatever W is. So a block of L = 4 under a
    strategy that makes one position final a forward costs FOUR forwards,
    the first of them 2L rows wide (the routed experts of either kind
    of forward take the sorted form where its rows, B * 2L or B * L,
    reach ``_MOE_RIDGE_ROWS``, by the rule of every other program: at
    B 64 both do, at B 8 neither). The pending block crosses the
    window's boundary in the carry: a window's last block is committed
    by the next window's first forward, and a row that finishes leaves
    its last block uncommitted (nothing reads it).

    K/V of the pending block and of the window's blocks live in the
    window buffer [Lyr, B, (W + 1) * L, KV, hd] only (slot j holds
    position start - L + j; every forward overwrites its blocks' slots)
    and the pool is read-only; ``commit_window`` writes at the end the
    blocks whose K/V a two-block forward of this window made final: the
    pending one and all but the last of the window's own. A block the
    pool already holds (prefilled, a prefix hit's page) is never pending,
    so never run or written again. Attention of a block's queries: every
    pooled position and every buffer slot up to the block's end is
    visible to EVERY query of the block, so no per-query mask exists
    (``_block_window_attention``).

    Carry: ``tokens`` is [B, 2L]: the pending block (final ids; all -1
    where the row has none: fresh from prefill, whose whole blocks the
    block-causal prefill wrote) beside the open block, a final token's
    id or -1 for a masked position (masked-ness is this flag, never ``id
    == mask_token_id``: a prompt may contain that id); ``positions`` the
    open block's start (-1 padding). After a window a continuing row's
    last block is pending and it starts a fresh block (all -1). Returns
    (toks [B, W * L] by position, emitted [B], [aux,] carry, kv_k, kv_v,
    info [B, 5]): a row's new tokens are ``toks[i, off : off +
    emitted[i]]`` with ``off`` the final positions its first block came
    with; ``info`` counts, a live row, blocks, forwards (a two-block
    forward is ONE), blocks whose K/V a two-block forward made final,
    blocks that took fewer denoising forwards than their schedule (an
    early exit of the dynamic strategy) and tokens generated but
    dropped."""
    L, S = cfg.block_length, cfg.denoising_steps
    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    mode = kernel_mode(allow_pallas, pallas_interpret)
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the block window has no sharded form (engine/jax_engine.py "
            "refuses a mesh for a configuration with block_length > 1)")

    @partial(jax.jit, static_argnames=("k_steps", "logprobs_topn"),
             donate_argnames=("kv_k", "kv_v"))
    def decode_window(params, tokens, positions, done, steps, remaining,
                      kv_k, kv_v, page_table, temperature, top_k, top_p,
                      seeds, eos_table, penalties=None, *, k_steps: int,
                      logprobs_topn: int = 0):
        assert penalties is None, "no penalty state inside a block"
        assert k_steps % L == 0, (k_steps, L)
        assert tokens.shape[1] == 2 * L, (tokens.shape, L)
        B = tokens.shape[0]
        Lyr = cfg.num_layers
        W = k_steps // L
        start = positions
        wdt = kv_k.dtype
        wk = jnp.zeros((Lyr, B, k_steps + L, KV, hd), wdt)
        wv = jnp.zeros((Lyr, B, k_steps + L, KV, hd), wdt)
        layer_params = {k: params[k] for k in _layer_keys(cfg)}
        offs = jnp.arange(L, dtype=jnp.int32)
        # what the pool holds of a row, and the slots of the buffer it
        # does not use: its pending block lies in slots [0, L), or in
        # the pool with everything before it
        held = tokens[:, 0] >= 0
        pooled = jnp.where(held, start - L, start)
        skip = jnp.where(held, 0, L)

        def block_forward(x_tok, live, wk, wv, w):
            """x_tok [B, n * L]: the open block of (traced) index w (n =
            1), at positions start + w * L + (0 .. L-1), or the pending
            block before it as well (n = 2); their K/V overwrite the
            buffer's slots from (w + 2 - n) * L on. ``live`` [B, n * L]:
            the rows that make (token, expert) pairs where the experts
            take the sorted form. Returns the OPEN block's logits
            [B * L, V]."""
            n = x_tok.shape[1] // L
            T, at = n * L, (w + 2 - n) * L
            h = embed_tokens(params, cfg, x_tok)            # [B, T, D]
            q_pos = jnp.maximum(
                start[:, None] + (at - L + jnp.arange(T, dtype=jnp.int32)),
                0)
            xs = dict(layer_params)
            # as in forward: the sorted dispatch reads w[layer, expert]
            # where the parameters lie; as scanned xs the layer's whole
            # expert stack would be sliced out before a block may index it
            experts = None
            if cfg.num_experts > 0 and _moe_use_blocked(
                    mesh, B * T, cfg.num_experts, cfg.num_experts_per_tok):
                experts = [xs.pop(k) for k in ("w_gate", "w_up", "w_down")]

            def layer(h, xs):
                lp, l_idx, wk_l, wv_l = xs
                with jax.named_scope("attn"):
                    x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                                 cfg.norm_unit_offset)
                    xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
                    if cfg.attn_bias:
                        xq, xk, xv = (xq + lp["bq"], xk + lp["bk"],
                                      xv + lp["bv"])
                    q, k = _qk_headnorm(xq.reshape(B, T, H, hd),
                                        xk.reshape(B, T, KV, hd), lp, cfg)
                    q = apply_rope(q, q_pos, inv_freq)
                    k = apply_rope(k, q_pos, inv_freq)
                    k, v = _block_kv(k, xv.reshape(B, T, KV, hd), wdt)
                    wk_l = lax.dynamic_update_slice_in_dim(wk_l, k, at, 1)
                    wv_l = lax.dynamic_update_slice_in_dim(wv_l, v, at, 1)
                    attn = _block_window_attention(
                        q, kv_k, kv_v, l_idx, page_table, pooled, wk_l,
                        wv_l, skip, at + T, L, scale,
                        use_pallas=mode is not None, interpret=bool(mode))
                    h = _residual_add(
                        h, attn.reshape(B, T, H * hd) @ lp["wo"], lp,
                        "ln_attn_post", cfg)
                return (_layer_ff(h, lp, cfg, mesh, experts, live, l_idx),
                        (wk_l, wv_l))

            h, (wk, wv) = lax.scan(
                layer, h,
                (xs, jnp.arange(Lyr, dtype=jnp.int32), wk, wv))
            h = rms_norm(h[:, T - L:], params["ln_final"], cfg.rms_norm_eps,
                         cfg.norm_unit_offset)
            # [B * L, V], a row's positions one after another: every
            # consumer wants rows, and a reshape of [B, L, V] is a copy
            return project_logits(params, cfg, h.reshape(B * L, -1)), wk, wv

        def open_block(tok):
            return jnp.where(tok < 0, cfg.mask_token_id, tok)

        N = logprobs_topn

        def block(w, c):
            """Block w of every row. The window's blocks are ONE loop,
            so that the program holds each forward and each draw once
            whatever W is: unrolled, every block's were traced, lowered
            and read back from the compile cache again (``setup_s`` +16%
            at W = 2: PERF.md, PR 62)."""
            (pend, tok, pos, done, steps, remaining, wk, wv, final,
             emitted, info, toks, aux_w) = c
            active = carry_active(done, pos)
            masked0 = jnp.logical_and(tok < 0, active[:, None])
            n0 = jnp.sum(masked0.astype(jnp.int32), axis=1)
            n_step = (n0 + S - 1) // S
            aux0 = ((jnp.zeros((B, L), jnp.float32),
                     jnp.zeros((B, L, N), jnp.float32),
                     jnp.zeros((B, L, N), jnp.int32)) if N else ())
            rng_step = pos[:, None] + offs[None, :]
            folded = active & (pend[:, 0] >= 0)

            def unmask_turn(c, logits):
                i, tok, masked, fwd, aux = c
                with jax.named_scope("diffusion.unmask"):
                    ids, conf = sample_with_confidence(
                        logits, temperature, top_k, top_p, seeds, rng_step,
                        max_top_k=max_top_k)
                    pick = unmask(cfg.remasking_strategy, masked, conf,
                                  n_step, cfg.confidence_threshold,
                                  i >= S - 1)
                    if N:
                        lp, tv, ti = logprob_aux(logits, ids.reshape(B * L),
                                                 N)
                        aux = (jnp.where(pick, lp.reshape(B, L), aux[0]),
                               jnp.where(pick[..., None],
                                         tv.reshape(B, L, N), aux[1]),
                               jnp.where(pick[..., None],
                                         ti.reshape(B, L, N), aux[2]))
                    fwd = fwd + jnp.any(masked, axis=1).astype(jnp.int32)
                    tok = jnp.where(pick, ids, tok)
                    masked = masked & ~pick
                return i + 1, tok, masked, fwd, aux

            def denoise(c, live=jnp.repeat(active[:, None], L, axis=1)):
                *c, wk, wv = c
                with jax.named_scope("diffusion.denoise"):
                    logits, wk, wv = block_forward(open_block(c[1]), live,
                                                   wk, wv, w)
                return (*unmask_turn(c, logits), wk, wv)

            with jax.named_scope("diffusion.denoise"):
                # the block's first forward, whatever the batch holds: the
                # pending block rides before the open one, and this
                # forward's K/V of it are the ones kept
                logits, wk, wv = block_forward(
                    jnp.concatenate([jnp.maximum(pend, 0), open_block(tok)],
                                    axis=1),
                    jnp.repeat(jnp.stack([folded, active], axis=1), L,
                               axis=1), wk, wv, w)
            final = jnp.where(folded, pos, final)
            turn = unmask_turn((jnp.int32(0), tok, masked0,
                                jnp.zeros((B,), jnp.int32), aux0), logits)
            _, tok, _, fwd, aux, wk, wv = lax.while_loop(
                lambda c: jnp.any(c[2]), denoise, (*turn, wk, wv))
            with jax.named_scope("diffusion.unmask"):
                emit, new_pos, done, steps, remaining = block_carry_update(
                    tok, masked0, pos, done, steps, remaining, eos_table, L)
                n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)
                emitted = emitted + n_emit
                live = active.astype(jnp.int32)
                scheduled = (n0 + jnp.maximum(n_step, 1) - 1) \
                    // jnp.maximum(n_step, 1)
                info = info + jnp.stack(
                    [live, fwd, folded.astype(jnp.int32),
                     (active & (fwd < scheduled)).astype(jnp.int32),
                     n0 - n_emit], axis=1)
            toks = lax.dynamic_update_slice_in_dim(toks, tok, w * L, 1)
            aux_w = tuple(lax.dynamic_update_slice_in_dim(a, x, w * L, 1)
                          for a, x in zip(aux_w, aux))
            # a row that went on: the block it finished is pending, the
            # next one fresh
            return (jnp.where((new_pos > pos)[:, None], tok, -1),
                    jnp.full((B, L), -1, jnp.int32), new_pos, done, steps,
                    remaining, wk, wv, final, emitted, info, toks, aux_w)

        aux_w = ((jnp.zeros((B, k_steps), jnp.float32),
                  jnp.zeros((B, k_steps, N), jnp.float32),
                  jnp.zeros((B, k_steps, N), jnp.int32)) if N else ())
        (pend, tok, pos, done, steps, remaining, wk, wv, final, emitted,
         info, toks, aux_w) = lax.fori_loop(0, W, block, (
             tokens[:, :L], tokens[:, L:], positions, done, steps,
             remaining, wk, wv,
             pooled,        # a row's positions before it have final K/V
             jnp.zeros((B,), jnp.int32), jnp.zeros((B, 5), jnp.int32),
             jnp.zeros((B, k_steps), jnp.int32), aux_w))

        with jax.named_scope("kv_carry"):
            kv_k = commit_window(kv_k, wk, page_table, start - L, final,
                                 lo=pooled)
            kv_v = commit_window(kv_v, wv, page_table, start - L, final,
                                 lo=pooled)
        return WindowResults(toks, emitted, aux_w or None,
                             (jnp.concatenate([pend, tok], axis=1), pos,
                              done, steps, remaining), kv_k, kv_v,
                             info, None).pack()

    return decode_window


def _block_window_attention(q, k_pools, v_pools, l_idx, page_table, pooled,
                            wk_l, wv_l, skip, end, block: int, scale,
                            use_pallas: bool, interpret: bool):
    """Attention of one or two blocks' queries in the block window: the
    (frozen) pool for positions < ``pooled``, and the window buffer's
    slots from ``skip`` up to the end of the queries' own block: the
    last block of queries ends at slot ``end`` (a scalar, traced or
    not), the one before it ``block`` slots earlier, so the second of
    two sees one block further than the first. Every key on either side
    is visible to every query of a block, so the only masks are the
    pool's extent and the buffer's.

    q: [B, n * block, H, hd]; *_pools: [Lyr, pages, KV, ps, hd]; l_idx:
    traced scalar; wk_l / wv_l: [B, K, KV, hd]; pooled: [B] (-1: padding
    row, sees nothing); skip: [B]. On the chip the pool side is ONE call
    of the decode kernel with the n * block queries folded into its
    group axis (q as [B, KV * n * block * G, hd], head-major under each
    KV head: the row's pages are read once for both blocks), its (m, l)
    statistics merged with the buffer side's as
    ``_pool_window_attention_pallas`` merges them; off it,
    ``_pool_window_attention``'s gather and one softmax."""
    from ..ops.paged_attention import (NEG_INF,
                                       paged_attention_decode_layered)

    B, T, H, hd = q.shape
    KV = wk_l.shape[2]
    G = H // KV
    K = wk_l.shape[1]
    qg = q.reshape(B, T, KV, G, hd).transpose(0, 2, 1, 3, 4)  # [B,KV,T,G,hd]
    q32 = qg.reshape(B, KV, T * G, hd).astype(jnp.float32)
    slot = jnp.arange(K)
    # the end of each query row's block, rows being (query, head in group)
    ends = end - (T - 1 - jnp.arange(T * G) // G) // block * block
    mask_w = ((slot[None, None, :] < ends[None, :, None])
              & (slot[None, None, :] >= skip[:, None, None])
              & (pooled[:, None, None] >= 0))                   # [B,T*G,K]
    sw = jnp.einsum("bkrh,bwkh->bkrw", q32,
                    wk_l.astype(jnp.float32)) * scale      # [B,KV,T*G,K]
    sw = jnp.where(mask_w[:, None], sw, NEG_INF)
    if use_pallas:
        out_p, m_p, l_p = paged_attention_decode_layered(
            qg.reshape(B, KV * T * G, hd), k_pools, v_pools, l_idx,
            page_table, jnp.maximum(pooled, 0), scale=scale,
            return_stats=True, interpret=interpret)
        m_w = jnp.max(sw, axis=-1)
        p_w = jnp.exp(sw - m_w[..., None])
        l_w = jnp.sum(p_w, axis=-1)
        out_w = jnp.einsum("bkrw,bwkh->bkrh", p_w, wv_l.astype(jnp.float32))
        m_p = m_p.reshape(B, KV, T * G)
        l_p = l_p.reshape(B, KV, T * G)
        m_t = jnp.maximum(m_p, m_w)
        a_p = jnp.exp(m_p - m_t) * l_p
        a_w = jnp.exp(m_w - m_t)
        l_t = jnp.maximum(a_p + a_w * l_w, 1e-9)
        out = (out_p.reshape(B, KV, T * G, hd).astype(jnp.float32)
               * a_p[..., None] + out_w * a_w[..., None]) / l_t[..., None]
    else:
        ps = k_pools.shape[3]
        S = page_table.shape[1] * ps
        kp = k_pools[l_idx][page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, S, KV, hd)
        vp = v_pools[l_idx][page_table].transpose(0, 1, 3, 2, 4).reshape(
            B, S, KV, hd)
        sp = jnp.einsum("bkrh,bskh->bkrs", q32,
                        kp.astype(jnp.float32)) * scale
        mask_p = jnp.arange(S)[None, :] < pooled[:, None]
        sp = jnp.where(mask_p[:, None, None, :], sp, NEG_INF)
        p = jax.nn.softmax(jnp.concatenate([sp, sw], axis=-1), axis=-1)
        out = (jnp.einsum("bkrs,bskh->bkrh", p[..., :S],
                          vp.astype(jnp.float32))
               + jnp.einsum("bkrw,bwkh->bkrh", p[..., S:],
                            wv_l.astype(jnp.float32)))
    out = out.reshape(B, KV, T, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, hd).astype(q.dtype)


def window_attention(q, k_pools, v_pools, l_idx, page_table, start, wk_l,
                     wv_l, i: int, scale, mode: Optional[bool], mesh=None,
                     **knobs):
    """One window step's attention of layer ``l_idx`` over the read-only
    pools and the window buffer, as ``mode`` (kernel_mode) says: the
    Pallas decode kernel on the pools where they lie, or the XLA gather
    arm on the layer's slice. ``knobs``: softcap, window, is_sliding,
    q_pos (Gemma-2)."""
    if mode is None:
        return _pool_window_attention(q, k_pools[l_idx], v_pools[l_idx],
                                      page_table, start, wk_l, wv_l, i,
                                      scale, **knobs)
    return _pool_window_attention_pallas(
        q, k_pools, v_pools, jnp.asarray(l_idx, jnp.int32), page_table,
        start, wk_l, wv_l, i, scale, interpret=mode, mesh=mesh, **knobs)


def _pool_window_attention_pallas(q, k_pools, v_pools, l_idx, page_table,
                                  start, wk_l, wv_l, i: int, scale,
                                  interpret: bool = False, mesh=None,
                                  softcap=None, window=None,
                                  is_sliding=False, q_pos=None):
    """Decode attention for one fused-window step: the (frozen) paged pool
    via the Pallas flash kernel (stats returned, layer selected by index
    map — no layer-slice materialization), merged with the in-flight
    window buffer by online-softmax combination. Positions < start live in
    the pool; positions start..start+i in the buffer.

    q: [B, 1, H, hd]; *_pools: [L, pages, KV, ps, hd]; l_idx: scalar;
    wk_l/wv_l: [B, K, KV, hd]; start: [B]; i: static step index. The
    Gemma-2 knobs (score softcap; sliding window on is_sliding layers
    with ``q_pos`` [B] the current query position) apply to BOTH sides:
    the kernel takes a per-row lower bound — and skips pages the window
    already slid past — while the buffer side masks in XLA."""
    from ..ops.paged_attention import (NEG_INF,
                                       paged_attention_decode_layered,
                                       paged_attention_decode_sharded)

    B, _, H, hd = q.shape
    KV = wk_l.shape[2]
    G = H // KV
    K = wk_l.shape[1]
    lengths = jnp.maximum(start, 0)  # pool extent; padding rows (-1) → 0
    lower = None
    eff = None
    if window is not None:
        eff = effective_window(window, is_sliding, B)
        # pool side sees [lower, start); a window that slid past the whole
        # pool (q_pos + 1 - eff >= start) leaves an empty view, which the
        # kernel's valid-masking returns as (m=NEG_INF, l=0) — the merge
        # below weights that side by l_p = 0
        lower = jnp.clip(q_pos + 1 - eff, 0, lengths)
    if mesh is not None:
        out_p, m_p, l_p = paged_attention_decode_sharded(
            q[:, 0], k_pools, v_pools, l_idx, page_table, lengths,
            mesh=mesh, scale=scale, interpret=interpret,
            softcap=softcap, lower=lower)
    else:
        out_p, m_p, l_p = paged_attention_decode_layered(
            q[:, 0], k_pools, v_pools, l_idx, page_table, lengths,
            scale=scale, return_stats=True, interpret=interpret,
            softcap=softcap, lower=lower)
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    sw = jnp.einsum("bkgh,bwkh->bkgw", qg,
                    wk_l.astype(jnp.float32)) * scale  # [B, KV, G, K]
    if softcap:
        sw = softcap * jnp.tanh(sw / softcap)
    mask_w = (jnp.arange(K)[None, :] <= i) & (start[:, None] >= 0)
    if eff is not None:
        # buffer slot w holds position start + w; slot i (the current
        # token) always stays visible since eff >= 1
        mask_w &= (start[:, None] + jnp.arange(K)[None, :]
                   > (q_pos - eff)[:, None])
    sw = jnp.where(mask_w[:, None, None, :], sw, NEG_INF)
    m_w = jnp.max(sw, axis=-1)                         # [B, KV, G]
    p_w = jnp.exp(sw - m_w[..., None])
    l_w = jnp.sum(p_w, axis=-1)
    out_w = jnp.einsum("bkgw,bwkh->bkgh", p_w, wv_l.astype(jnp.float32))
    # merge: rescale each side to the joint max, renormalize once
    m_p = m_p.reshape(B, KV, G)
    l_p = l_p.reshape(B, KV, G)
    m_t = jnp.maximum(m_p, m_w)
    a_p = jnp.exp(m_p - m_t) * l_p   # pool side un-normalized weight
    a_w = jnp.exp(m_w - m_t)
    l_t = jnp.maximum(a_p + a_w * l_w, 1e-9)
    out = (out_p.reshape(B, KV, G, hd).astype(jnp.float32) * a_p[..., None]
           + out_w * a_w[..., None]) / l_t[..., None]
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def _pool_window_attention(q, k_pool_l, v_pool_l, page_table, start,
                           wk_l, wv_l, i: int, scale,
                           softcap=None, window=None, is_sliding=False,
                           q_pos=None):
    """Decode attention reading the (frozen) paged pool for positions
    < start plus the in-flight window for positions start..start+i.

    q: [B, 1, H, hd]; *_pool_l: [pages, KV, ps, hd]; wk_l/wv_l:
    [B, K, KV, hd]; start: [B]; i: static step index. The Gemma-2 knobs
    (score softcap, sliding window on is_sliding layers, with ``q_pos``
    [B] the current query position) ride this XLA path — the Pallas
    window kernel doesn't implement them."""
    B, _, H, hd = q.shape
    _, KV, ps, _ = k_pool_l.shape
    K = wk_l.shape[1]
    P = page_table.shape[1]
    S = P * ps
    G = H // KV

    kp = k_pool_l[page_table].transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)
    vp = v_pool_l[page_table].transpose(0, 1, 3, 2, 4).reshape(B, S, KV, hd)
    qg = q.reshape(B, 1, KV, G, hd).astype(jnp.float32)
    sp = jnp.einsum("btkgh,bskh->bkgts", qg,
                    kp.astype(jnp.float32)) * scale  # [B,KV,G,1,S]
    sw = jnp.einsum("btkgh,bwkh->bkgtw", qg,
                    wk_l.astype(jnp.float32)) * scale  # [B,KV,G,1,K]
    mask_p = (jnp.arange(S)[None, :] < start[:, None])  # start<0 → all off
    mask_w = (jnp.arange(K)[None, :] <= i) & (start[:, None] >= 0)
    if window is not None:
        # sliding layers see only kv positions > q_pos - window; pool
        # slot j holds logical position j, window slot w holds start + w
        keep = jnp.logical_not(is_sliding)
        mask_p &= keep | (jnp.arange(S)[None, :]
                          > (q_pos - window)[:, None])
        mask_w &= keep | ((start[:, None] + jnp.arange(K)[None, :])
                          > (q_pos - window)[:, None])
    sp = _softcap_mask(sp, mask_p[:, None, None, None, :], softcap)
    sw = _softcap_mask(sw, mask_w[:, None, None, None, :], softcap)
    s = jnp.concatenate([sp, sw], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    pp, pw = p[..., :S], p[..., S:]
    out = (jnp.einsum("bkgts,bskh->btkgh", pp, vp.astype(jnp.float32))
           + jnp.einsum("bkgtw,bwkh->btkgh", pw,
                        wv_l.astype(jnp.float32)))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# -------------------------------------------------- full-attention reference


def full_attention_layer(cfg: ModelConfig, h: jax.Array, lp: Params,
                         pos: jax.Array, inv_freq: jax.Array,
                         scale: float, is_sliding=False,
                         mesh=None) -> jax.Array:
    """One transformer layer with plain causal full attention (no paged
    cache). The single source of the layer math for every non-paged
    consumer: ``reference_forward`` (test oracle) and the
    pipeline-parallel stage body (parallel/pipeline_parallel.py) —
    inside the latter's shard_map all values are device-local, so the
    default mesh=None (which may pick the blocked MoE dispatch) is
    correct there too.
    ``is_sliding`` is the traced Gemma-2 per-layer window flag (the
    caller owns the per-layer bookkeeping — see _window_flag)."""
    B, T = h.shape[:2]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    x = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    xq, xk, xv = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    if cfg.attn_bias:
        xq, xk, xv = xq + lp["bq"], xk + lp["bk"], xv + lp["bv"]
    q, k = _qk_headnorm(xq.reshape(B, T, H, hd),
                        xk.reshape(B, T, KV, hd), lp, cfg)
    q = apply_rope(q, pos, inv_freq)
    k = apply_rope(k, pos, inv_freq)
    v = xv.reshape(B, T, KV, hd)
    qg = q.reshape(B, T, KV, H // KV, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = _visible(jnp.arange(T)[None, None, :],
                    jnp.arange(T)[None, :, None],
                    cfg.sliding_window, is_sliding,
                    cfg.block_length)  # [1, T, T]
    scores = _softcap_mask(scores, mask[:, None, None],
                           cfg.attn_logit_softcap)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    attn = attn.reshape(B, T, H * hd).astype(h.dtype)
    h = _residual_add(h, attn @ lp["wo"], lp, "ln_attn_post", cfg)
    x = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    if cfg.num_experts > 0:
        mlp_out = _moe_mlp(x, lp["w_router"], lp["w_gate"], lp["w_up"],
                           lp["w_down"], cfg.num_experts_per_tok, mesh=mesh,
                           act=_act(cfg))
    else:
        mlp_out = _mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"], _act(cfg))
    return _residual_add(h, mlp_out, lp, "ln_mlp_post", cfg)


def reference_forward(params: Params, cfg: ModelConfig,
                      tokens: jax.Array) -> jax.Array:
    """Plain full-attention forward (no paging) used to validate the paged
    path in tests; returns logits for every position [B, T, V]."""
    B, T = tokens.shape
    inv_freq = rope_freqs(cfg)
    scale = cfg.attn_scale
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    h = embed_tokens(params, cfg, tokens)

    layer_params = {k: params[k] for k in _layer_keys(cfg)}

    def layer(h, xs):
        lp, l_idx = xs
        return full_attention_layer(cfg, h, lp, pos, inv_freq, scale,
                                    is_sliding=_window_flag(cfg, l_idx)), \
            None

    h, _ = lax.scan(layer, h,
                    (layer_params, jnp.arange(cfg.num_layers)))
    h = rms_norm(h, params["ln_final"], cfg.rms_norm_eps, cfg.norm_unit_offset)
    return project_logits(params, cfg, h)
