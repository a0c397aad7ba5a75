"""The JAX serving engine: continuous batching over a paged KV cache.

This replaces the reference's engine integrations (patched vLLM/SGLang
subprocesses over ZMQ, lib/llm/src/engines/) with an in-process TPU-native
engine — the idiomatic choice on TPU where the engine IS the Python process
(SURVEY §5 "Distributed communication backend").

Design:

- one asyncio scheduler loop owns the device: it alternates chunked
  prefill steps and batched decode steps over static-shaped, bucketed
  programs (no data-dependent shapes under jit);
- per-request state is host-side (token lists, page tables from
  ``PageManager``); the device sees only padded arrays;
- device→host sync (sampled tokens) happens via ``run_in_executor`` so the
  event loop keeps serving other requests during a TPU step;
- sequences preempt (release pages, requeue) when the pool runs dry —
  prefix caching makes re-prefill cheap;
- the engine speaks the internal token-level protocol
  (``PreprocessedRequest`` in, ``EngineOutput`` chunks out) so it slots
  behind ``Backend`` exactly like the reference's ExecutionContext.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import math
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
import functools
from functools import partial
from typing import AsyncIterator, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..llm.protocols.common import (FINISH_CANCELLED, FINISH_EOS,
                                    FINISH_LENGTH, FINISH_TIMEOUT,
                                    EngineOutput, PreprocessedRequest)
from ..models.config import ModelConfig
from ..models.llama import DROP_SLOT, KVCacheSpec, moe_kernel_takes
from ..models.registry import family_of
from ..models.window import WindowResults, unpack as unpack_window
from ..runtime import blackbox, guard, profiling, slo, tracing
from ..runtime.config import env_bool, env_int, env_str
from ..runtime.engine import Context
from .jit_fence import CompileFence
from .kv_manager import ChainHashCache, PageManager, WindowPagePool
from .profiler import EngineProfiler, memory_snapshot
from .sampling import (SamplingBatch, logprob_aux, sample_tokens,
                       verify_greedy_draft)
from .spec_decode import propose_ngram_draft

log = logging.getLogger("dynamo_tpu.engine")


def _cancel_reason(ctx: Context) -> str:
    """Why a stopped sequence is ending: the request deadline expired
    (client-visible "timeout", HTTP 504) vs. the caller cancelled
    ("cancelled"). Either way the sequence is terminated on the cancel
    path and its pages free immediately."""
    return FINISH_TIMEOUT if ctx.expired else FINISH_CANCELLED


def _phased(name: str):
    """Run the method inside the step-thread phase ``name``
    (engine/profiler.py: the phase ledger + a ``dyn.<name>`` trace
    annotation; phases entered further in take the clock over)."""

    def deco(fn):
        @functools.wraps(fn)
        def run(self, *args, **kwargs):
            with self.profiler.phase(name):
                return fn(self, *args, **kwargs)

        return run

    return deco


def _stamp_dispatch(fence: CompileFence, name: str, fn):
    """Wrap a jitted step fn so every dispatch notes its call form on
    the engine's compile fence. The note is raw refs (one attribute
    store); jit_fence renders it into a dtype[shape] call-form key only
    when a post-warmup compile actually trips the fence."""
    def call(*args, **kwargs):
        fence.note_dispatch(name, args, kwargs)
        return fn(*args, **kwargs)
    call.__wrapped__ = fn  # the jitted fn itself (AOT .lower() for checks)
    return call


@dataclass
class EngineConfig:
    page_size: int = 64
    num_pages: int = 512
    max_batch: int = 64
    prefill_chunk: int = 512
    max_top_k: int = 64
    # host-DRAM offload tier: blocks evicted from HBM spill here and
    # restore on prefix hits (reference kv/ V2 multi-tier storage +
    # docs/kv_cache_manager.md "+40% TTFT"); 0 disables the tier
    host_pages: int = 0
    # tiered-KV restore chunking: at most this many host→HBM page
    # restores dispatch per scheduler iteration, so one request with a
    # huge host-tier prefix hit cannot block every other request's step
    # behind a bulk synchronous copy. The default is unmeasured on a
    # directly attached chip. Gated sequences wait in `prefilling` while
    # their restores drain across iterations; 0 = unlimited (the old
    # single-shot behavior)
    tier_restore_chunk: int = 32
    # top-N alternatives returned per token when a request asks for
    # logprobs; matches OpenAI's top_logprobs cap of 20 so no valid
    # request is silently truncated. ONE static value so all logprob
    # requests share a compiled window variant (the per-row requested
    # count is sliced host-side)
    max_top_logprobs: int = 20
    # pre-compile the logprobs decode-window variants (logprobs_topn is
    # a STATIC argname: serving flips it from 0 to max_top_logprobs on
    # the first request that asks for logprobs, and each value is its
    # own program per bucket). On by default — logprobs is a stock
    # OpenAI-API field any client can send, so unlike penalties the
    # unwarmed form is routinely reachable (DL026 warmup-form-drift
    # finding, previously a runtime compile-fence trip class)
    warmup_logprobs: bool = True
    # pre-compile the penalized decode-window variants too (doubles the
    # decode programs in warmup). Off by default: most deployments never
    # send sampling penalties, and a first penalty request merely pays
    # one compile per bucket
    warmup_penalties: bool = False
    # int8-compress the host tier (engine/kv_compress.py): pages are
    # quantized ON DEVICE before D2H and dequantized ON DEVICE after
    # H2D, so the slow host link moves ~half the bytes and the host
    # pool holds ~2x the pages per GB. LOSSY (restored pages round-trip
    # through int8). None (default) = ON whenever the tier is enabled,
    # unless DYN_HOST_TIER_FP16 asks for the lossless fallback;
    # explicit True/False wins over both
    host_tier_int8: Optional[bool] = None
    # dynaheat eviction policy for BOTH cache tiers: "cost" (GreedyDual
    # over the dynacache hot-prefix hit table — hot shared prefixes
    # outlive cold one-shot churn) or "lru" (the original least-recently-
    # freed order, kept as the A/B control). None reads DYN_EVICT_POLICY.
    evict_policy: Optional[str] = None
    # dynaheat overlapped restores: a drained restore batch's H2D +
    # dequantize dispatches on one drain and its page inject lands on
    # the NEXT, overlapping the intervening device step. False = the
    # serial same-drain inject (A/B control). None reads
    # DYN_RESTORE_OVERLAP.
    restore_overlap: Optional[bool] = None
    max_prefill_batch: int = 8  # prompts packed per prefill dispatch
    # fused decode window: run K decode+sample steps inside ONE jitted
    # program (sampling stays on device; tokens cross to the host once per
    # window). The serving loop is dispatch-latency-bound — per-step host
    # round-trips dwarf the ~ms device compute — so K amortizes dispatch
    # K-fold. EOS/stop/budget masking runs ON DEVICE (rows freeze), so K
    # can grow without dead compute past a stop. One token a row a step
    # is a property of the configuration: for a model that generates by
    # diffusion over blocks (ModelConfig.block_length L > 1) this is the
    # number of TOKENS a row can emit in one window, a multiple of L: the
    # window generates decode_steps / L whole blocks, each in up to
    # denoising_steps forwards a row (the first of them 2L positions
    # wide: the block before rides it, llama._make_block_window_fn).
    decode_steps: int = 4
    # the prefill policy of an iteration, one field. None is prefill
    # priority: an iteration that ships a prefill batch ships no decode
    # window, so prompt batches drain at full cadence (interleaving a
    # K-step window between every prefill batch delays batch build-up).
    # A number is token-budgeted mixing (the vLLM-style middle ground):
    # every iteration ships BOTH a decode window and a prefill batch
    # trimmed to at most this many prompt tokens, so a burst of long
    # prompts cannot starve running decodes (ITL p99 bounded by window +
    # budget-prefill time instead of the full burst drain).
    prefill_token_budget: Optional[int] = None
    # self-speculative decoding: a host-side prompt-lookup drafter
    # (engine/spec_decode.py) proposes up to spec_tokens candidates per
    # greedy row from its own prompt+generated history; ONE batched
    # [B, spec_tokens+1] verify forward checks them and the longest
    # greedy-matching prefix (plus the bonus token) is accepted — 1..K+1
    # tokens per dispatch. OFF by default so the compiled-program set
    # (and the pipelined window path) is untouched; when on, the decode
    # arm runs synchronously (the win is tokens-per-dispatch, not
    # dispatch overlap). Non-greedy / penalty / logit_bias / logprobs
    # rows transparently bypass speculation.
    spec_decode: bool = False
    spec_tokens: int = 4      # K: max draft tokens verified per step
    spec_ngram_max: int = 4   # longest suffix n-gram the drafter matches
    spec_ngram_min: int = 1   # shortest n-gram worth matching
    # on-device stop table width (eos_token_ids + stop_token_ids rows,
    # padded with -1); requests with more ids fall back to the (lagging
    # but correct) host-side check
    max_eos_ids: int = 8
    # long-context: prompts whose prefill extent exceeds this take the
    # sequence-parallel ring-attention prefill (parallel/ring_attention.py)
    # instead of the chunked path — requires a mesh with a "seq" axis > 1.
    # None disables. The long path compiles one program per padded-length
    # bucket (pow2, seq-divisible); page_buckets must still cover the
    # decode-side table width for these prompts.
    long_prefill_threshold: Optional[int] = None
    # bucketing (static shapes under jit); keep these sets SMALL — every
    # (bucket combination) is one XLA compile, and warmup() pre-compiles
    # the full grid so serving never compiles mid-flight
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    prefill_buckets: Tuple[int, ...] = (16, 64, 512)
    page_buckets: Tuple[int, ...] = (8, 64)
    watermark_pages: int = 4  # keep-free headroom before admitting
    # pages of the window layers' K/V pool, for a model whose kinds of
    # layer keep a pool each (ModelConfig.kv_pool_by_kind; num_pages is
    # then the pool of the layers that see the whole context). 0: every
    # row of max_batch at the most it can hold (the table's slots)
    window_pages: int = 0

    def __post_init__(self) -> None:
        if self.prefill_chunk % self.page_size != 0:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be a multiple "
                f"of page_size ({self.page_size}): chunk starts must stay "
                f"page-aligned for the page-granular KV commit")
        if self.spec_decode and self.spec_tokens < 1:
            raise ValueError(
                f"spec_tokens ({self.spec_tokens}) must be >= 1 when "
                f"spec_decode is enabled")

    @staticmethod
    def _pick(buckets: Tuple[int, ...], n: int) -> int:
        for b in buckets:
            if n <= b:
                return b
        b = buckets[-1]
        while b < n:
            b *= 2
        return b

    def bucket_batch(self, n: int) -> int:
        return min(self._pick(self.batch_buckets, n), self.max_batch)

    def prefill_bucket_batch(self, n: int) -> int:
        """The batch buckets a prefill may run in: bucket_batch(1) and
        bucket_batch(max_prefill_batch), the image warmed_grid() takes
        and warmup() compiles, so a mid-serving prompt mix never
        triggers a fresh XLA compile. It names the warmed SET; which of
        the two a dispatch runs in is choose_prefill_bucket's, by what
        the warmed programs cost on this device. For n rows this is the
        answer of an engine that has measured nothing: one row in the
        small bucket, more in the wide one."""
        small = self.bucket_batch(1)
        return small if n <= small else self.bucket_batch(
            self.max_prefill_batch)

    def bucket_len(self, n: int) -> int:
        return min(self._pick(self.prefill_buckets, n), self.prefill_chunk)

    def bucket_pages(self, n: int) -> int:
        return self._pick(self.page_buckets, n)

    def warmed_grid(self) -> dict:
        """The EXACT images of the bucket helpers over every admissible
        serving input — the shape set warmup() must compile so no jitted
        engine entry point ever compiles mid-serving. Computed by
        enumeration rather than from the bucket tuples directly because
        ``_pick`` doubles past its last bucket: with exotic configs
        (``prefill_chunk`` above the largest prefill bucket,
        ``max_batch`` outside ``batch_buckets``) the reachable shapes are
        a strict superset of the declared buckets. The compile-fence
        grid-coverage test pins warmup() to this set."""
        cap_pages = min(self.page_buckets[-1], max(self.num_pages - 1, 1))
        return {
            "prefill_lens": sorted({
                self.bucket_len(n)
                for n in range(1, self.prefill_chunk + 1)}),
            "decode_batches": sorted({
                self.bucket_batch(n)
                for n in range(1, self.max_batch + 1)}),
            "prefill_batches": sorted({
                self.prefill_bucket_batch(n)
                for n in range(1, max(self.max_prefill_batch,
                                      self.max_batch) + 1)}),
            "page_buckets": sorted({
                self.bucket_pages(n) for n in range(1, cap_pages + 1)}),
        }


# the columns of the block window's per-row counts, in the order
# llama._make_block_window_fn returns them ([B, 5])
_BLOCK_WINDOW_COUNTS = ("blocks", "forwards", "folded_commits",
                        "early_exits", "dropped_tokens")


def _whole_blocks(n_tokens: int, block: int) -> int:
    """The positions of n_tokens tokens that lie in whole blocks."""
    return n_tokens // block * block


# a per-row cost within this share of the least is a tie: the timing of
# a warmed program repeats to a few percent (PERF.md, PR 43)
_COST_TIE = 0.05
# warmup() times every warmed prefill program once, and a second time
# while the timing as a whole stays inside this many seconds
_COST_TIMING_SECONDS = 1.0
# (PB, T) -> device ms of that warmed prefill program with one row live
# and with every row live
PrefillCosts = Dict[Tuple[int, int], Tuple[float, float]]


def prefill_cost_ms(costs: PrefillCosts, PB: int, T: int,
                    rows: int) -> float:
    """What the warmed program (PB, T) costs with ``rows`` live rows, in
    device ms: the reading at one live row (the program's fixed part:
    dense work on padded rows, weight reads) plus a line to the reading
    at PB (attention and the sorted expert dispatch follow the live
    rows)."""
    one, full = costs[(PB, T)]
    return one if PB == 1 else one + (rows - 1) / (PB - 1) * (full - one)


def choose_prefill_bucket(costs: PrefillCosts, T: int, n: int,
                          unmeasured: int) -> Tuple[int, int]:
    """(batch bucket, rows shipped) of a prefill dispatch with n rows
    waiting at length bucket T: of the batch buckets ``costs`` has a
    reading for at T, the one whose program costs the least a row it
    ships (each ships min(n, PB)); a near-tie goes to the one that ships
    more rows. A dense model past the MXU's ridge gains nothing from a
    batch and ships a row a program; one bound by the read of its
    experts shares that read and keeps the wide bucket. Without a
    reading (an engine that was never warmed, or warmed one bucket) the
    answer is ``unmeasured``, EngineConfig.prefill_bucket_batch's."""
    offers = []
    for PB, t in costs:
        if t == T:
            rows = min(n, PB)
            offers.append(
                (prefill_cost_ms(costs, PB, T, rows) / rows, rows, PB))
    if not offers:
        return unmeasured, min(n, unmeasured)
    least = min(offers)[0]
    _, rows, PB = max((o for o in offers if o[0] <= least * (1 + _COST_TIE)),
                      key=lambda o: (o[1], -o[0]))
    return PB, rows


@dataclass(eq=False)  # identity semantics: `in`/`==` must never deep-compare
class Sequence:
    req: PreprocessedRequest
    context: Context
    out: asyncio.Queue
    tokens: List[int]            # prompt + generated (host truth)
    num_prompt: int
    pages: List[int] = field(default_factory=list)
    # True from admission on a prefix hit until the row's first chunk is
    # dispatched: that chunk starts from the last hit page's snapshot
    state_from_page: bool = False
    # row of the recurrent-state pool, for a model that keeps state
    # beside its pages (claimed at admission, released with the pages)
    state_slot: Optional[int] = None
    computed: int = 0            # positions already in the KV cache
    generated: int = 0
    finished: Optional[str] = None
    finish_emitted: bool = False
    last_token: int = 0          # next decode input
    arrival: float = field(default_factory=time.monotonic)
    # disaggregation: keep pages alive after finish so the prefill worker
    # can extract them (caller must release_pages() afterwards)
    hold_pages: bool = False
    # dynaprof cost attribution (host-side counters, no device work):
    # queue wait stamped at admission; occupancy-weighted device-step
    # share (each dispatch distributes exactly 1.0 across its batch, so
    # fleet-wide shares sum to the dispatch count); peak page footprint
    queue_wait_s: float = 0.0
    # the TTFT split, beside `arrival` (monotonic; None until reached):
    # admission, the first prefill dispatch that carries a chunk of the
    # prompt, the first token-bearing emission. A preempted sequence
    # keeps the stamps of its first pass.
    t_admit: Optional[float] = None
    t_first_dispatch: Optional[float] = None
    t_first_token: Optional[float] = None
    # wire ctx of the span ambient in generate() (None = not sampled):
    # parent of the engine.* spans recorded at finish
    trace_ctx: Optional[dict] = None
    prefix_hit: int = 0
    dispatch_share: float = 0.0
    dispatches: int = 0
    max_pages: int = 0
    # dynacache prefix split: how this request's prompt pages were
    # sourced at first admission (device reuse vs host-tier restore vs
    # fresh compute) + how long its queued restores waited to dispatch
    device_hit_blocks: int = 0
    host_restored_blocks: int = 0
    restore_t0: Optional[float] = None
    restore_wait_s: float = 0.0
    # dynaslo: last token-bearing emission (None until the first token
    # leaves the engine) — TTFT on the first emission, per-token ITL on
    # every later gap, e2e at finish (all host clock reads, no syncs)
    last_emit_t: Optional[float] = None
    # incremental chained-hash state over `tokens` (kv_manager
    # ChainHashCache, engine-lazily created): admission's prefix match
    # and every page-boundary publish extend it instead of re-hashing
    # the whole sequence
    hash_cache: Optional[ChainHashCache] = None
    # dynahot DL022: the request's eos/stop id lists are immutable per
    # sequence, so the per-token append path reads one cached frozenset
    # membership instead of rebuilding `x or []` defaults every token
    _stop_set: Optional[frozenset] = None
    _dev_stop_count: int = -1
    # cfg.block_length of the model that serves it: above 1 (generation
    # by diffusion over blocks) only whole blocks have K/V in the cache
    block: int = 1
    # the row's pages of the window layers' pool (a model with a pool a
    # kind of layer): logical pages wfirst, wfirst + 1, ...; the pages
    # before wfirst were given back. wreserved: its reservation there.
    wpages: List[int] = field(default_factory=list)
    wfirst: int = 0
    wreserved: int = 0

    @property
    def stop_set(self) -> frozenset:
        s = self._stop_set
        if s is None:
            stop = self.req.stop
            eos = () if stop.ignore_eos else (self.req.eos_token_ids or ())
            s = frozenset(eos) | frozenset(stop.stop_token_ids or ())
            self._stop_set = s
        return s

    @property
    def dev_stop_count(self) -> int:
        """Rows the full stop-id set would occupy in the device stop
        table (list lengths, duplicates counted, matching the decode
        window's eos-table seeding)."""
        n = self._dev_stop_count
        if n < 0:
            stop = self.req.stop
            n = 0 if stop.ignore_eos else len(self.req.eos_token_ids or ())
            n += len(stop.stop_token_ids or ())
            self._dev_stop_count = n
        return n

    def max_new(self) -> int:
        mt = self.req.stop.max_tokens
        return mt if mt is not None else 1 << 30

    @property
    def prefill_extent(self) -> int:
        """Tokens whose KV must exist before decode can run. Fresh request:
        the whole prompt (its last logits seed sampling). Resumed after
        preemption: everything except the final token, which is the next
        decode input (its KV is written by that decode step). A model
        that generates by blocks: the whole blocks of what there is
        (prompt, and after a preemption what was generated); the tail
        opens the first block the window generates, as final positions."""
        if self.block > 1:
            return _whole_blocks(len(self.tokens), self.block)
        return self.num_prompt if self.generated == 0 else len(self.tokens) - 1


@dataclass
class _PendingWindow:
    """A dispatched-but-unread decode window. ``res`` holds device
    arrays (futures under JAX async dispatch); reading its ``toks`` back
    is deferred until after the NEXT window is enqueued. Its ``counts``
    are the block window's per-row counts ([B, 5]: _BLOCK_WINDOW_COUNTS)
    or a module's WINDOW_COUNTS vector."""

    batch: List[Sequence]
    res: WindowResults
    index: Dict[int, int] = field(default_factory=dict)  # id(seq) → row
    processed: bool = False


@dataclass(eq=False)  # identity: two mid-prompt markers are not one
class _PendingPrefill:
    """A dispatched-but-unread prefill batch: ``sampled`` is the on-device
    first-token draw for rows that completed their prompt this chunk
    (None when no row finished)."""

    finishing: List[Tuple[int, Sequence]]
    sampled: Optional[jax.Array]
    aux: Optional[tuple] = None  # (lp [B], top_vals [B,N], top_ids [B,N])
    processed: bool = False


class JaxEngine:
    """AsyncEngine over the JAX model (token-level core engine)."""

    @profiling.setup_span("engine_init")
    def __init__(self, model_cfg: ModelConfig, engine_cfg: Optional[EngineConfig]
                 = None, params=None, seed: int = 0, dtype=None, mesh=None,
                 quant: Optional[str] = None,
                 worker_label: Optional[str] = None):
        # before this process's first jit where enable_compile_cache()
        # was not called (a test, a library user): the pools below are
        # eager programs already
        profiling.install_jit_listeners()
        self.cfg = model_cfg
        self.ecfg = engine_cfg or EngineConfig()
        # dynashard replica identity: a STABLE per-replica label (e.g.
        # "r0") threaded through stats() → ForwardPassMetrics → the
        # aggregator's `replica` gauge label, the per-request cost block
        # and dyntrace spans — instance ids (lease hex) are unique but
        # not stable across restarts, so dashboards key on this instead
        self.worker_label = worker_label or ""
        self.mesh_devices = int(mesh.size) if mesh is not None else 1
        self.mesh_axes = ({k: int(v) for k, v in mesh.shape.items()
                           if int(v) > 1} if mesh is not None else {})
        self.mesh_shape = (",".join(f"{k}={v}" for k, v in
                                    self.mesh_axes.items())
                           or "single")
        # the family's record (models/registry.py FAMILIES): its module
        # and what that declares; asked once, here
        self.family = fam = family_of(model_cfg)
        model = fam.module
        self.mesh = mesh
        # tokens a decode forward yields a row: 1, or the block of a
        # model that generates by diffusion over blocks (a property of
        # the configuration, like the module; no EngineConfig field)
        self.block = model_cfg.block_length if fam.by_blocks else 1
        if self.block > 1:
            _check_block_sizes(model_cfg, self.ecfg)
        for feature, on in (
                ("host_pages", self.ecfg.host_pages > 0),
                ("spec_decode", self.ecfg.spec_decode),
                ("long_prefill_threshold",
                 self.ecfg.long_prefill_threshold is not None),
                ("mesh", mesh is not None and mesh.size > 1)):
            if on:
                fam.refuse(feature)
        # the one refusal made by the request (generate): None where
        # the family serves penalties
        self._penalty_refusal = fam.refusal("sampling_penalty")
        # commit_forwards: forwards that only commit a block. The window
        # has had none since a block's K/V ride the next block's first
        # forward; the name stays in stats() for who reads it, at 0
        self.diffusion = dict.fromkeys(
            _BLOCK_WINDOW_COUNTS + ("commit_forwards", "tokens"), 0)
        # what the family's decode window counts by itself and returns
        # before the state (routed and held expert pairs,
        # models/granite.py); summed into stats()
        self.window_counts = dict.fromkeys(fam.window_counts, 0)
        # a one-device mesh names the replica's OWN device (dynashard's
        # one-chip replicas): params and pools are built and committed
        # there, and the step thread uploads its inputs there — without
        # this every such replica lands on the process's default device
        self.device = (mesh.devices.flat[0]
                       if mesh is not None and mesh.size == 1 else None)
        with self._on_device():
            if params is None:
                if quant == "int8":
                    # init + quantize on host CPU so the bf16 tree never
                    # exists in HBM (how 8B-shaped weights start on a 16
                    # GB chip); see models/quant.py
                    from ..models.quant import host_init_quantized
                    params = host_init_quantized(model, model_cfg, seed,
                                                 device=self.device)
                else:
                    params = model.init_params(model_cfg,
                                               jax.random.PRNGKey(seed))
            elif quant == "int8":
                from ..models.quant import quantize_params
                params = quantize_params(params)
            self.params = params
            spec = KVCacheSpec(self.ecfg.num_pages, self.ecfg.page_size)
            self.kv_k, self.kv_v = model.init_kv_cache(model_cfg, spec,
                                                       dtype)
            # recurrent state beside the paged KV, for a family that
            # declares it (init_state): one pool the step thread donates
            # through every program as it does the KV pools, max_batch
            # slots + one drop slot that padding rows read and write.
            # None for every other module: their programs take no operand
            # for it and their call forms are unchanged.
            self.state = None
            # True where the family also keeps the state at each page's
            # end under the page's id (init_state_snapshots): the pool is
            # the last member of self.state, a prefix hit hands over
            # pages AND state, and the prefix cache stays on
            self._state_snapshots = False
            self.state_restores_total = 0
            self._state_free: List[int] = []
            # slots held and slots there, summed at every decode dispatch
            # (_count_decode_slots): the pool's fill over a window is a
            # ratio of two deltas, and idle time around it counts nothing
            self.state_slots_held_total = 0
            self.state_slots_seen_total = 0
            if fam.init_state is not None:
                self.state = fam.init_state(
                    model_cfg, self.ecfg.max_batch + 1, dtype)
                self._state_free = list(range(self.ecfg.max_batch))[::-1]
                if fam.init_state_snapshots is not None:
                    self._state_snapshots = True
                    self.state = (*self.state, fam.init_state_snapshots(
                        model_cfg, spec, dtype))
            # the window layers' pools and their books, for a model whose
            # kinds of layer keep a pool each: (K, V) [L_win, pages_w,
            # ...], donated through every program behind the full layers'
            # pools and returned last, as a state pool is. None otherwise.
            self.wkv = None
            self.wpm: Optional[WindowPagePool] = None
            if fam.pool_by_kind:
                slots = model.window_table_slots(
                    model_cfg, self.ecfg.page_size,
                    max(self.ecfg.prefill_chunk,
                        2 * self.ecfg.decode_steps + 1))
                pages_w = (self.ecfg.window_pages
                           or self.ecfg.max_batch * slots + 1)
                self.wpm = WindowPagePool(pages_w, self.ecfg.page_size,
                                          model_cfg.sliding_window, slots)
                self.wkv = model.init_window_kv_cache(
                    model_cfg, KVCacheSpec(pages_w, self.ecfg.page_size),
                    dtype)
            # the window pool's fill and the rows past the window, summed
            # at every decode dispatch (_count_decode_slots)
            self.kv_window_pages_held_total = 0
            self.kv_window_pages_seen_total = 0
            self.decode_row_steps_total = 0
            self.decode_row_steps_past_window_total = 0
            # positions a prefill program ran the self half and the
            # cross half of the stack on, for a family whose record
            # declares cross_on_last (_cross_rows_stats)
            self.self_rows_total = 0
            self.cross_rows_total = 0
        if mesh is not None:
            from ..parallel.mesh import shard_kv_cache, shard_params
            self.params = shard_params(self.params, model_cfg, mesh)
            self.kv_k, self.kv_v = shard_kv_cache(self.kv_k, self.kv_v,
                                                  model_cfg, mesh)
        # all three attention paths (prefill, K=1 decode, fused decode
        # window) keep the Pallas kernel under a mesh via shard_map over
        # the head axis (ops/paged_attention.py *_sharded wrappers)
        self.prefill_fn, self.decode_fn = model.make_step_fns(
            model_cfg, mesh=mesh)
        if mesh is not None and mesh.size > 1:
            d = mesh.shape.get("data", 1)
            bad = [b for b in self.ecfg.batch_buckets if b % d]
            if d > 1 and bad:
                raise ValueError(
                    f"batch_buckets {bad} not divisible by mesh data axis "
                    f"({d}): shard_map decode windows need whole rows per "
                    f"data shard")
        # the module's fused window (read-only pool + window buffer: one
        # pool copy in HBM; models/window.py, llama.make_decode_window_fn)
        self.decode_multi_fn = model.make_decode_window_fn(
            model_cfg, True, self.ecfg.max_top_k, mesh=mesh)
        # self-speculative decode: the [B, K+1] verify forward (only
        # built — and only warmed — when the flag is on, so the default
        # compiled-program set is untouched). Model families without a
        # verify fn (MLA's latent cache) silently keep the standard path.
        self.verify_fn = None
        if self.ecfg.spec_decode:
            if fam.make_verify_fn is not None:
                self.verify_fn = fam.make_verify_fn(model_cfg, mesh=mesh)
            else:
                log.warning("spec_decode enabled but %s has no "
                            "make_verify_fn; speculation disabled",
                            model.__name__)
        self.spec_steps = 0
        self.spec_draft_tokens_total = 0
        self.spec_accepted_tokens_total = 0
        # sequence-parallel long-prefill (ring attention over the mesh's
        # "seq" axis) — the serving wire-up of parallel/ring_attention.py
        # (r2 built it but nothing reached it; VERDICT r2 missing #5)
        self.long_prefill_fn = None
        self.long_prefills_total = 0
        if (self.ecfg.long_prefill_threshold is not None
                and mesh is not None and mesh.shape.get("seq", 1) > 1):
            # Gemma-2's sliding window / softcap thread through the ring
            # as position predicates (parallel/ring_attention.py) — all
            # three model families take this path (VERDICT r4 task 7)
            from ..parallel.ring_attention import (make_long_prefill_fn,
                                                   make_mla_long_prefill_fn)
            # MLA takes the latent-only ring exchange (only the shared
            # compressed stream rotates on ICI); GQA rotates per-head K/V
            builder = (make_mla_long_prefill_fn if model_cfg.is_mla
                       else make_long_prefill_fn)
            self.long_prefill_fn = builder(model_cfg, mesh)
            self._seq_par = mesh.shape["seq"]
        # resolve the dynaheat None-means-env config knobs ONCE, here,
        # so every later read sees a concrete value (the ecfg object is
        # per-engine; bench/tests that pass explicit values are
        # untouched)
        if self.ecfg.host_tier_int8 is None:
            self.ecfg.host_tier_int8 = (
                self.ecfg.host_pages > 0
                and not env_bool("DYN_HOST_TIER_FP16"))
        if self.ecfg.evict_policy is None:
            self.ecfg.evict_policy = env_str("DYN_EVICT_POLICY") or "cost"
        if self.ecfg.restore_overlap is None:
            self.ecfg.restore_overlap = env_bool("DYN_RESTORE_OVERLAP", True)
        # async frames must take _pm_lock (declared below) before
        # touching the page pool; sync frames on the engine step path
        # are serialized by the single-worker executor
        self.pm = PageManager(self.ecfg.num_pages,  # guarded-by: self._pm_lock
                              self.ecfg.page_size,
                              host_pages=self.ecfg.host_pages,
                              evict_policy=self.ecfg.evict_policy,
                              # a hit hands over pages, and state only
                              # where the module snapshots it by the page
                              # ... and never where the window layers
                              # have given the hit's pages back
                              prefix_reuse=((self.state is None
                                             or self._state_snapshots)
                                            and self.wkv is None))
        # host-DRAM offload pools (same per-page layout as the HBM pool)
        self.host_k = self.host_v = None
        self.host_k_s = self.host_v_s = None
        if self.ecfg.host_pages > 0:
            # derive page geometry from the ACTUAL device pools: the two
            # pools differ per family (MLA: latent [.., 1, ps, r] vs rope
            # [.., 1, ps, dr]) — rebuilding from GQA config fields here
            # would allocate wrong-shaped host pools for MLA and crash
            # the first offload landing
            # (their leading axis too: models/longcat_flash.py keeps
            # two pool entries a layer)
            hk = (self.kv_k.shape[0], self.ecfg.host_pages,
                  *self.kv_k.shape[2:])
            hv = (self.kv_v.shape[0], self.ecfg.host_pages,
                  *self.kv_v.shape[2:])
            if self.ecfg.host_tier_int8:
                # compressed tier: int8 rows + f32 per-row scales — the
                # D2H/H2D link moves ~half the bytes and the same host
                # RAM holds ~2x the pages (engine/kv_compress.py)
                self.host_k = np.zeros(hk, np.int8)
                self.host_v = np.zeros(hv, np.int8)
                self.host_k_s = np.zeros(hk[:-1] + (1,), np.float32)
                self.host_v_s = np.zeros(hv[:-1] + (1,), np.float32)
            else:
                # the pool's .dtype is already a numpy dtype (ml_dtypes
                # registers bf16) — resolving it through a device
                # round-trip (np.asarray(jnp.zeros(...))) was dynajit
                # DL017's first true positive
                hdtype = np.dtype(self.kv_k.dtype)
                self.host_k = np.zeros(hk, hdtype)
                self.host_v = np.zeros(hv, hdtype)
        self.offload_pages_total = 0
        self.restore_pages_total = 0
        # guards PageManager between the event-loop thread's reads
        # (cache_snapshot) and the step thread's admission and disagg jobs
        # (reserve/release/submit), which the single-worker executor
        # already serializes with the engine's steps
        self._pm_lock = threading.Lock()
        self.waiting: List[Sequence] = []
        self.prefilling: List[Sequence] = []
        self.running: List[Sequence] = []
        # pipelined dispatch state: windows/prefills enqueued on device but
        # not yet read back, plus finished sequences whose pages must stay
        # allocated until every in-flight window containing them completes
        # (a premature free could hand a page to a new sequence while the
        # old window still writes it)
        self._inflight: List[_PendingWindow] = []
        self._pending: Optional[_PendingWindow] = None
        # the prefills not read back yet, in the order they were enqueued:
        # at most two (_step_window)
        self._pending_prefills: List[_PendingPrefill] = []
        self._deferred_free: List[Sequence] = []
        # (key, SamplingBatch, device arrays) of the last decode-window
        # dispatch, reused while its key holds (_dispatch_decode_window)
        self._samp_cache: Optional[tuple] = None
        # tiered-KV overlap state: offload gathers dispatched but not yet
        # copied to the host pool (device arrays + target slots), and HBM
        # pages whose host→HBM restore is still queued (their sequences
        # are gated out of prefill until the copy dispatches)
        self._offload_inflight: List[Tuple] = []
        self._unrestored_pages: set = set()
        # restore_overlap staging: ONE drained restore batch whose H2D +
        # dequantize dispatched on the previous drain (overlapping the
        # intervening device step) and whose page inject lands on the
        # next. Rows: (page, block_hash) per restored page + the device
        # arrays; pages stay in _unrestored_pages until injected.
        self._restore_staged: Optional[Tuple] = None
        # per-sequence max context implied by the warmed bucket grid: a
        # request may never need more pages than the largest page bucket,
        # or serving would compile mid-flight (VERDICT r2 weak #6)
        self.cap_pages = min(self.ecfg.page_buckets[-1],
                             max(self.ecfg.num_pages - 1, 1))
        self.cap_tokens = self.cap_pages * self.ecfg.page_size
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._aio_loop: Optional[asyncio.AbstractEventLoop] = None
        # thread id of the loop's thread, captured in start(): _emit's
        # on/off-loop routing is one integer compare (no exception probe)
        self._aio_loop_tid: Optional[int] = None
        # the serving loop's ledger (runtime/profiling.py), held from
        # start(); brackets on the null ledger do nothing
        self._loop_ledger = profiling.NULL_LEDGER
        self._stopped = False
        # dynarevive graceful drain: a draining engine refuses new work
        # (typed NoCapacity) while in-flight sequences run to completion
        self.draining = False
        # the step thread lives inside the replica's device scope, so
        # every host→device upload of a step input goes straight there
        self._exec = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="jax-step",
            initializer=lambda: self._on_device().__enter__())
        # observability (ForwardPassMetrics analog, kv_router/protocols.rs)
        self.steps = 0
        # step timeline: bounded ring of scheduler events (queue-wait,
        # batch occupancy, tokens/step, spec accepts) surfaced through
        # /v1/traces on the HTTP frontend (dyntrace)
        self.step_timeline = tracing.StepTimeline(
            env_int("DYN_STEP_TIMELINE") or 0)
        tracing.register_timeline(f"jax-engine-{id(self):x}",
                                  self.step_timeline)
        # runtime compile fence (engine/jit_fence.py): armed by warmup(),
        # counts every post-warmup XLA compile; DYN_JIT_FENCE=warn|raise
        # escalates. The counter rides stats() → ForwardPassMetrics →
        # dyn_engine_post_warmup_compiles_total.
        self.fence = CompileFence(f"jax-engine-{id(self):x}",
                                  timeline=self.step_timeline)
        # stamp every fenced jit dispatch with its call form so a fence
        # trip can name the offending form (jit name + operand
        # dtype[shape] + static kwargs). note_dispatch stores raw refs
        # only; rendering happens on the trip path, never per dispatch.
        for _attr in ("prefill_fn", "decode_fn", "decode_multi_fn",
                      "verify_fn", "long_prefill_fn"):
            _fn = getattr(self, _attr, None)
            if _fn is not None:
                setattr(self, _attr,
                        _stamp_dispatch(self.fence, _attr, _fn))
        # the step thread's phase ledger (engine/profiler.py)
        self.profiler = EngineProfiler(f"jax-engine-{id(self):x}")
        # per-page KV bytes (both pools) for attribution/occupancy
        # accounting — .nbytes is shape metadata, not a device sync
        self._page_bytes = int(
            (self.kv_k.nbytes + self.kv_v.nbytes)
            // max(self.ecfg.num_pages, 1))
        # dispatches that distributed a step share (the attribution
        # conservation invariant: sum of per-request shares == this)
        self.batch_dispatches_total = 0
        self.queue_wait_seconds_total = 0.0
        # the TTFT split, summed at each request's first emission:
        # queue_wait + prefill_wait + first_token == engine_ttft
        self.prefill_wait_seconds_total = 0.0
        self.first_token_seconds_total = 0.0
        self.engine_ttft_seconds_total = 0.0
        self.first_tokens_total = 0
        self.prefill_tokens_total = 0
        # fill counters, added where the slots are chosen: real prompt
        # tokens / rows against the slots of the bucket that ran
        self.prefill_slots_total = 0
        self.prefill_dispatches_total = 0
        # of those, the programs in which no row's logits were wanted
        # (_dispatch_prefill's last_idx all negative): no head computed
        self.prefill_logits_skipped_total = 0
        # and the dispatches behind which the same iteration
        # enqueued a decode window (_step_window, rule 2)
        self.prefill_window_topups_total = 0
        # and those enqueued in the same iteration as the prefill before
        # them (_step_window, rule 1)
        self.prefill_runahead_total = 0
        # a row's chunks, and those that start past position 0: from
        # what the row's earlier chunks left (pages, and for a model
        # with recurrent state the state in its slot)
        self.prefill_row_chunks_total = 0
        self.prefill_row_chunks_carried_total = 0
        # of those, the programs whose expert layers run as one kernel
        # (ops/moe_grouped.py): known from the bucket's rows by the rule
        # the program was built under
        self.moe_grouped_programs_total = 0
        # the same question of a decode window's forwards, a live row
        # each: those whose program ran its experts as the kernel, of
        # decode_rows_total (a token a step) or diffusion_forwards_total
        # (a block window, whose two kinds of forward have rows of their
        # own: _window_forwards_grouped)
        self.moe_grouped_window_forwards_total = 0
        # what warmup() read of its prefill programs on this device
        # (_time_prefill_programs); empty until then, and
        # _dispatch_prefill keeps the config's rule
        self._prefill_costs: PrefillCosts = {}
        # candidate rows a dispatch left for a later program, and the
        # dispatches whose bucket is not the config's rule's
        self.prefill_rows_held_back_total = 0
        self.prefill_bucket_narrowed_total = 0
        self.decode_rows_total = 0
        self.decode_slots_total = 0
        self.decode_windows_total = 0
        # of those, the windows with a sampled row: their draws ran
        # sample_tokens' sampled arm, every other window's one argmax
        self.decode_windows_sampled_total = 0
        self.warmup_seconds = 0.0
        # iterations where a decode window dispatched WHILE prompts were
        # still prefilling — the observable for budgeted mixing
        self.mixed_dispatches = 0
        self.decode_tokens_total = 0
        self.prefix_hit_tokens_total = 0
        self.prompt_tokens_total = 0
        # dynacache: windowed hit rate over the last DYN_CACHE_WINDOW
        # admissions — the lifetime ratio above goes flat after enough
        # traffic, so the aggregator gauge reads this recent-traffic view
        # instead (ISSUE 11 satellite; totals stay exported alongside)
        self._hit_window: deque = deque(
            maxlen=max(env_int("DYN_CACHE_WINDOW") or 256, 1))
        # dynaslo: per-role mergeable latency histograms (TTFT, ITL,
        # queue wait, e2e) — host-side counter arithmetic only, shipped
        # via stats() → ForwardPassMetrics.latency_hist and merged by
        # the metrics aggregator into fleet-wide quantiles. The role
        # defaults to "unified"; disagg wrappers relabel via set_role().
        self.latency = slo.LatencyRecorder("unified")
        profiling.register_cache(f"jax-engine-{id(self):x}", self)
        # dynablack: incident bundles fold this engine's stats() (cost
        # table, cache, memory) at capture time — weakly held, cold path
        blackbox.get_recorder().register_stats_source(
            self.worker_label or f"jax-engine-{id(self):x}", self)

    def _on_device(self):
        """Default-device scope of a one-device replica (a no-op for the
        plain single engine and for multi-device meshes)."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _state_args(self, slots, src=None) -> tuple:
        """The trailing operands of a step program: the pools a family
        keeps beside the K/V pools and the rows' places in them
        (``_row_slots``), in the two positional places ``state`` and
        ``state_slots``. A family with recurrent state passes (state
        pools, slots) and, for a prefill of a module that snapshots by
        the page (``src`` given), the page whose snapshot each row starts
        from (-1: none); a family whose window layers keep pools of their
        own (window pools, the rows' tables); a family that declares BOTH
        the two as pairs in that one order, window first: ((window pools,
        state pools), (tables, slots)). Nothing otherwise."""
        fam = self.family
        if fam.pool_by_kind:
            # _window_tables: the same two places
            if fam.init_state is not None:
                return ((self.wkv, self.state), slots)
            return (self.wkv, slots)
        if fam.init_state is None:
            return ()
        if src is None or not self._state_snapshots:
            return (self.state, slots)
        return (self.state, slots, src)

    def _no_src(self, n: int) -> np.ndarray:
        """``src`` of n rows that start from no page's snapshot."""
        return np.full(n, -1, np.int32)

    def _keep_pools(self, pools) -> None:
        """What a program returned last, kept as the engine's again: the
        window layers' pools, the state, or (window pools, state), by
        what the family's record declares (``_state_args``' order)."""
        fam = self.family
        if fam.pool_by_kind and fam.init_state is not None:
            self.wkv, self.state = pools
        elif fam.pool_by_kind:
            self.wkv = pools
        elif fam.init_state is not None:
            self.state = pools

    def _take_state(self, out):
        """A step program's results without the pools it returned last
        (``_keep_pools``)."""
        if self.family.pool_by_kind or self.family.init_state is not None:
            *out, pools = out
            self._keep_pools(pools)
        return out

    def _row_slots(self, batch, B: int, wrows=(), T: Optional[int] = None,
                   paged: bool = False):
        """The rows' places in what the family keeps beside the K/V
        pools, for a program of ``B`` rows of which ``batch`` lead: the
        state slot of each (the drop slot for padding), the tables into
        the window layers' pool (``_window_tables`` of ``wrows``, ``T``,
        ``paged``; ``wrows`` may be a generator: only a family with such a
        pool reads it), both as (tables, slots) for a family that
        declares both, None for one that declares neither."""
        slots = None
        if self.family.init_state is not None:
            slots = np.full(B, self.ecfg.max_batch, np.int32)
            slots[:len(batch)] = [s.state_slot for s in batch]
        if not self.family.pool_by_kind:
            return slots
        tables = self._window_tables(wrows, B, T, paged)
        return tables if slots is None else (tables, slots)

    def _drop_slots(self, n: int, T: Optional[int] = None,
                    paged: bool = False):
        """``_row_slots`` of n padding rows: every row reads and writes
        the drop slot and page 0 of the window layers' pool, and writes
        nothing there."""
        return self._row_slots((), n, (), T, paged)

    def _window_tables(self, rows, B: int, T: Optional[int] = None,
                       paged: bool = False):
        """The rows' operand into the window layers' pool (None for a
        model without one): (table [B, S_w], base [B]) for a decode
        window, with ``T`` also the pool's write slots of a prefill chunk
        or a single step, flat [B, T] or with ``paged`` by the page
        [B, T // ps]. ``rows``: (sequence, first position written, tokens
        written) a live row; the others are padding that reads page 0 and
        writes nothing. Slot s of a row's table is its logical page
        ``wfirst + s``, so positions count from ``base = wfirst * ps``."""
        if self.wkv is None:
            return None
        wpm, ps = self.wpm, self.ecfg.page_size
        table = np.zeros((B, wpm.table_slots), np.int32)
        base = np.zeros(B, np.int32)
        slots = None
        if T is not None:
            slots = (np.full((B, max(T // ps, 1)), wpm.num_pages, np.int32)
                     if paged else np.full((B, T), DROP_SLOT, np.int32))
        for i, (seq, start, n) in enumerate(rows):
            assert len(seq.wpages) <= wpm.table_slots, (
                len(seq.wpages), wpm.table_slots)
            table[i, :len(seq.wpages)] = seq.wpages
            base[i] = seq.wfirst * ps
            if slots is None:
                continue
            held = np.fromiter(seq.wpages, np.int64, len(seq.wpages))
            if paged:
                first = start // ps - seq.wfirst
                npg = (n + ps - 1) // ps
                slots[i, :npg] = held[first:first + npg]
            else:
                pos = np.arange(start, start + n)
                slots[i, :n] = held[pos // ps - seq.wfirst] * ps + pos % ps
        if slots is None:
            return table, base
        return table, base, slots

    def _blank_tokens(self, n: int) -> np.ndarray:
        """The window's token operand of n rows that carry nothing: one
        id a row, or for a model that generates by blocks two blocks a
        row, no pending block (-1) beside an open one with every
        position masked (-1)."""
        if self.block == 1:
            return np.zeros(n, np.int32)
        return np.full((n, 2 * self.block), -1, np.int32)

    def _take_window(self, out, topn: int) -> WindowResults:
        """A window program's results by name (models/window.py knows
        their order), without the pools it returned: those are the
        engine's again. ``counts``: the block window's per-row counts, or
        the vector a module's WINDOW_COUNTS names; None for a window
        that counts nothing."""
        fam = self.family
        res = unpack_window(
            out, topn, counts=fam.by_blocks or bool(fam.window_counts),
            state=fam.pool_by_kind or fam.init_state is not None)
        self.kv_k, self.kv_v = res.kv_k, res.kv_v
        self._keep_pools(res.state)
        return res._replace(kv_k=None, kv_v=None, state=None)

    @property
    def role(self) -> str:
        return self.latency.role

    def set_role(self, role: str) -> None:
        """Label this engine's serving role (prefill|decode|unified) for
        the stats plane and latency histograms (dynaslo). Call before
        serving; earlier observations keep their original role."""
        self.latency.role = role

    # ---------------------------------------------------------- lifecycle

    def warmup(self, progress: bool = False, decode: bool = True) -> int:
        """Pre-compile the full bucket grid (prefill T×P, decode B×P,
        sampling per B) so no compile ever happens mid-serving — a
        mid-flight compile stalls every in-flight request for the compile
        latency. Returns the number of programs compiled.
        ``decode=False`` skips the decode-window grid — for prefill-only
        workers (disagg), whose engine never runs a decode step."""
        with self._on_device(), \
                profiling.setup_ledger().span("warmup") as span:
            n = self._warmup(progress, decode, span)
        # the span's own seconds: stats() shows both, and they agree
        self.warmup_seconds = span.seconds
        return n

    @contextlib.contextmanager
    def _warm(self, kind: str):
        """One program of warmup()'s grid: a ``warmup.<kind>`` span of the
        set-up ledger and, under the call form the fence noted, a row of
        ``warmup_programs`` (the span's seconds and jit stages)."""
        ledger = profiling.setup_ledger()
        with ledger.span("warmup." + kind) as span:
            yield
        ledger.add_warm_program(self.fence.last_dispatch_form(), span)

    def _warmup(self, progress: bool, decode: bool, span) -> int:
        ecfg = self.ecfg
        bracket = profiling.setup_ledger().span
        # the EXACT reachable shape images (not the declared bucket
        # tuples): _pick doubles past its last bucket, so exotic configs
        # reach shapes the tuples alone would miss — compiling them
        # mid-serving (the compile fence below counts such misses)
        grid = ecfg.warmed_grid()
        page_buckets = grid["page_buckets"] or [8]
        n = 0
        # under a mesh: the committed (NamedSharding) decode-window carry
        # per batch bucket, captured below to warm the pipelined call
        # forms (see the committed-carry note in the decode loop)
        carries: Dict[int, tuple] = {}
        prefill_bs = grid["prefill_batches"]
        for P in page_buckets:
            for T in grid["prefill_lens"]:
                for PB in prefill_bs:
                    with self._warm("prefill"):
                        # warm exactly the serving variant: page-granular
                        # commit for ps-aligned buckets, row scatter
                        # otherwise
                        pslots = (jnp.full((PB, T // ecfg.page_size),
                                           ecfg.num_pages, jnp.int32)
                                  if T % ecfg.page_size == 0 else None)
                        logits, self.kv_k, self.kv_v = self._take_state(
                            self.prefill_fn(
                                self.params, jnp.zeros((PB, T), jnp.int32),
                                jnp.zeros((PB, T), jnp.int32) - 1,
                                self.kv_k, self.kv_v,
                                jnp.zeros((PB, P), jnp.int32),
                                jnp.full((PB, T), DROP_SLOT, jnp.int32),
                                jnp.zeros((PB,), jnp.int32), pslots,
                                *self._state_args(
                                    self._drop_slots(PB, T,
                                                     pslots is not None),
                                    self._no_src(PB))))
                    n += 1
                    if self.block > 1:
                        # prefill samples nothing and its program has no
                        # head (logits is None): the first token comes
                        # out of the first block the window generates
                        continue
                    # penalties=None EXPLICITLY: the jit cache keys on the
                    # call's (args, kwargs) treedef, so an explicit-None
                    # kwarg and an omitted default are DIFFERENT entries —
                    # _sample_device always passes penalties=, and warming
                    # the omitted form left every serving bucket one
                    # compile short (found by the compile fence)
                    with bracket("warmup.sample"):
                        toks = sample_tokens(
                            logits, jnp.zeros(PB),
                            jnp.zeros(PB, jnp.int32), jnp.ones(PB),
                            jnp.zeros(PB, jnp.uint32),
                            jnp.zeros(PB, jnp.int32),
                            max_top_k=ecfg.max_top_k, penalties=None)
                        if (ecfg.warmup_logprobs
                                and ecfg.max_top_logprobs > 0):
                            # _sample_device runs logprob_aux EAGERLY
                            # after every prefill/decode dispatch that
                            # asked for logprobs, so its op-by-op
                            # executables compile per logits bucket on
                            # the first such request — a fence trip the
                            # jitted-window variants above don't cover
                            # (DL026, same finding class)
                            logprob_aux(logits, toks,
                                        ecfg.max_top_logprobs)
            for B in (grid["decode_batches"] if decode else []):
                tableB = jnp.zeros((B, P), jnp.int32)
                if ecfg.decode_steps > 1:
                    # warm the penalty-free variant always; the penalized
                    # window programs too when warmup_penalties (default:
                    # a first penalty request pays one compile per bucket
                    # mid-serving — documented tradeoff, most deployments
                    # never send penalties and should not double warmup)
                    pen_variants = [None]
                    if ecfg.warmup_penalties:
                        V = self.cfg.vocab_size
                        pen_variants.append((
                            jnp.zeros((B, V), jnp.int32),
                            jnp.zeros((B, V), jnp.int8),
                            jnp.ones(B), jnp.zeros(B), jnp.zeros(B)))
                    # logprobs_topn is a STATIC argname: serving flips it
                    # to max_top_logprobs for any window with a logprobs
                    # request, so each value is its own program per
                    # bucket — warm both or the first logprobs request
                    # compiles mid-serving (DL026 warmup-form-drift)
                    topn_variants = [0]
                    if ecfg.warmup_logprobs and ecfg.max_top_logprobs > 0:
                        topn_variants.append(ecfg.max_top_logprobs)
                    for pv in pen_variants:
                        for topn in topn_variants:
                            # kwargs explicitly, matching the serving
                            # call form in _dispatch_decode_window — the
                            # jit cache distinguishes explicit static
                            # kwargs from omitted defaults (compile-fence
                            # finding, same class as the penalties=None
                            # note above)
                            def window(*carry, pv=pv, topn=topn):
                                return self._take_window(
                                    self.decode_multi_fn(
                                        self.params, *carry, self.kv_k,
                                        self.kv_v, tableB, jnp.zeros(B),
                                        jnp.zeros(B, jnp.int32),
                                        jnp.ones(B),
                                        jnp.zeros(B, jnp.uint32),
                                        jnp.full((B, ecfg.max_eos_ids), -1,
                                                 jnp.int32),
                                        pv, *self._state_args(
                                            self._drop_slots(B)),
                                        k_steps=ecfg.decode_steps,
                                        logprobs_topn=topn), topn)

                            with self._warm("window"):
                                res = window(
                                    jnp.asarray(self._blank_tokens(B)),
                                    jnp.zeros(B, jnp.int32) - 1,
                                    jnp.zeros(B, bool),
                                    jnp.zeros(B, jnp.int32),
                                    jnp.ones(B, jnp.int32))
                            n += 1
                            if pv is None and self.mesh is not None:
                                # committed-carry variant: under a mesh
                                # the pipelined window's (tok, pos, done,
                                # steps, remaining) arrive COMMITTED
                                # (NamedSharding outputs of the previous
                                # window / _merge_carry) while the
                                # host-array call above is uncommitted —
                                # DIFFERENT jit cache entries, so without
                                # this the first chained window would
                                # compile mid-serving (found by the
                                # compile fence on the first sharded
                                # engine). Feed the window its own carry
                                # to warm that variant; save it for the
                                # merge-combo loop below.
                                if topn == 0:
                                    carries[B] = res.carry
                                with self._warm("window"):
                                    window(*res.carry)
                                n += 1
                else:
                    with self._warm("window"):
                        logits, self.kv_k, self.kv_v = self._take_state(
                            self.decode_fn(
                                self.params, jnp.zeros(B, jnp.int32),
                                jnp.zeros(B, jnp.int32) - 1, self.kv_k,
                                self.kv_v, tableB,
                                jnp.full((B,), DROP_SLOT, jnp.int32),
                                *self._state_args(self._drop_slots(B, 1))))
                    n += 1
                    with bracket("warmup.sample"):
                        toks = sample_tokens(
                            logits, jnp.zeros(B),
                            jnp.zeros(B, jnp.int32),
                            jnp.ones(B), jnp.zeros(B, jnp.uint32),
                            jnp.zeros(B, jnp.int32),
                            max_top_k=ecfg.max_top_k, penalties=None)
                        if (ecfg.warmup_logprobs
                                and ecfg.max_top_logprobs > 0):
                            logprob_aux(logits, toks,
                                        ecfg.max_top_logprobs)
                if self.verify_fn is not None:
                    # speculative verify grid: one [B, K+1] program per
                    # (B, P) bucket + the accept-mask program per B
                    Kv = ecfg.spec_tokens + 1
                    with self._warm("window"):
                        logits, self.kv_k, self.kv_v = self.verify_fn(
                            self.params, jnp.zeros((B, Kv), jnp.int32),
                            jnp.zeros((B, Kv), jnp.int32) - 1, self.kv_k,
                            self.kv_v, tableB,
                            jnp.full((B, Kv), DROP_SLOT, jnp.int32))
                        verify_greedy_draft(
                            logits, jnp.zeros((B, Kv - 1), jnp.int32),
                            jnp.zeros(B, jnp.int32))
                    n += 1
                if progress:
                    print(f"warmup: {n} programs, {span.seconds:.0f}s",
                          flush=True)
        # long-context ring-prefill buckets: every padded length a served
        # long prompt can hit, so the first long request never compiles
        # mid-serving (same invariant as the chunked grid)
        if self.long_prefill_fn is not None:
            from ..parallel.ring_attention import scatter_prefill_kv
            t = self._long_bucket(self.ecfg.long_prefill_threshold + 1)
            while True:
                with self._warm("prefill"):
                    logits, k_all, v_all = self.long_prefill_fn(
                        self.params, jnp.zeros((1, t), jnp.int32),
                        jnp.zeros((1, t), jnp.int32) - 1)
                    self.kv_k, self.kv_v = scatter_prefill_kv(
                        self.kv_k, self.kv_v, k_all, v_all,
                        jnp.full((1, t), DROP_SLOT, jnp.int32))
                n += 1
                with bracket("warmup.sample"):
                    toks = sample_tokens(
                        logits, jnp.zeros(1), jnp.zeros(1, jnp.int32),
                        jnp.ones(1), jnp.zeros(1, jnp.uint32),
                        jnp.zeros(1, jnp.int32),
                        max_top_k=ecfg.max_top_k, penalties=None)
                    if ecfg.warmup_logprobs and ecfg.max_top_logprobs > 0:
                        logprob_aux(logits, toks, ecfg.max_top_logprobs)
                if t >= self.cap_tokens:
                    break
                t *= 2
        # carry-merge combos (tiny programs): window N+1's inputs stitch
        # the previous window's device carry with host rows for newly
        # admitted sequences — one compile per (B_prev, B_new) pair
        if decode and ecfg.decode_steps > 1:
            bset = grid["decode_batches"]
            for Bp in bset:
                # under a mesh the in-flight window's carry is COMMITTED
                # (NamedSharding) — warm the merge with the real warmed
                # carry so serving's exact sharding mix (committed carry
                # + uncommitted host rows) hits the cache (unsharded
                # engines keep the host-zeros form: committed and
                # uncommitted coincide on one device)
                carry = carries.get(Bp) if self.mesh is not None else None
                if carry is None:
                    carry = (jnp.asarray(self._blank_tokens(Bp)),
                             jnp.zeros(Bp, jnp.int32),
                             jnp.zeros(Bp, bool), jnp.zeros(Bp, jnp.int32),
                             jnp.ones(Bp, jnp.int32))
                for Bn in bset:
                    with self._warm("window"):
                        rows = (jnp.zeros(Bn, jnp.int32),
                                jnp.zeros(Bn, bool),
                                jnp.asarray(self._blank_tokens(Bn)),
                                jnp.zeros(Bn, jnp.int32) - 1,
                                jnp.zeros(Bn, jnp.int32),
                                jnp.ones(Bn, jnp.int32))
                        # no wrapper stamps this program's dispatches:
                        # its row is named from here
                        self.fence.note_dispatch("_merge_carry",
                                                 (*carry, *rows))
                        _merge_carry(*carry, *rows)
                    n += 1
        # host-tier copy programs: offload gathers / restore scatters run
        # MID-SERVING on pow2-padded page batches (engine._drain_kv_tier)
        # — warm every reachable pow2 size so the first eviction/restore
        # under load never compiles (the dynajit warmup-coverage check
        # pins these entries to this loop)
        if self.host_k is not None:
            size = 1
            while True:
                with bracket("warmup.kv_tier"):
                    idx = jnp.zeros(size, jnp.int32)
                    # the serving drain builds its index operands as
                    # jnp.asarray(<python list>, jnp.int32) — a DIFFERENT
                    # lowering (convert_element_type) from zeros/full
                    # above, one tiny program per distinct padded length.
                    # Warm that call form too, or the first drain of each
                    # pow2 size compiles mid-serving (compile-fence
                    # finding on the cache A/B arms).
                    jax.block_until_ready(
                        jnp.asarray([0] * size, jnp.int32))
                # both pools: their page shapes differ per model family
                # (MLA latent vs rope), so each is its own program set
                for pool_attr in ("kv_k", "kv_v"):
                    with self._warm("kv_tier"):
                        self._warm_tier_copy(pool_attr, idx)
                    n += 1
                if size >= self.ecfg.num_pages:
                    break
                size *= 2
        # the grid's first executions were dispatched without a wait:
        # what of them the device has not finished is waited for here, in
        # the warmup span's own seconds, under no child
        jax.block_until_ready(self.kv_k)
        self._time_prefill_programs(grid)
        # arm the runtime compile fence: from here on, ANY XLA compile is
        # a serving stall — counted always, warn/raise per DYN_JIT_FENCE
        self.fence.arm()
        return n

    def _warm_tier_copy(self, pool_attr: str, idx) -> None:
        """One pool's host-tier programs at one padded size: the offload
        gather and the restore scatter (with the int8 round trip where
        the tier compresses)."""
        pool = getattr(self, pool_attr)
        # no wrapper stamps these dispatches: the row is named from here
        self.fence.note_dispatch("_gather_pages", (pool, idx))
        g = _gather_pages(pool, idx)
        if self.ecfg.host_tier_int8:
            from .kv_compress import dequantize_pages, quantize_pages

            q, s = quantize_pages(g)
            if self.mesh is not None:
                # serving restores dequantize UNCOMMITTED host arrays;
                # under a mesh the committed quantize outputs here are a
                # different jit cache entry — round-trip through the host
                # so warmup matches the serving call form
                q = jnp.asarray(np.asarray(q))  # dynalint: disable=implicit-host-transfer
                s = jnp.asarray(np.asarray(s))  # dynalint: disable=implicit-host-transfer
            rows = dequantize_pages(q, s)
        else:
            rows = g
            if self.mesh is not None:
                # same committed-vs-uncommitted note: serving restores
                # inject np views of the host pool. Warmup-time sync,
                # not a hot-path leak.
                rows = jnp.asarray(np.asarray(rows))  # dynalint: disable=implicit-host-transfer
        setattr(self, pool_attr, _inject_pages(
            getattr(self, pool_attr),
            jnp.full((idx.shape[0],), self.ecfg.num_pages, jnp.int32),
            rows))

    @profiling.setup_span("warmup.cost_timing")
    def _time_prefill_programs(self, grid: dict) -> None:
        """Read what each warmed prefill program (PB, T) costs on this
        device, for _dispatch_prefill's choice of a batch bucket: every
        row live, and for PB > 1 one row live (prefill_cost_ms draws the
        line between). The table holds the arm WITH the head (every
        ``last_idx`` >= 0, as a prompt's last chunk): choose_prefill_bucket
        compares buckets like for like, and a chunk that skips the head
        is cheaper by the same amount in every bucket. Real token ids
        from a fixed key (all-zero tokens would send every token to one
        expert), positions from 0, nothing committed (dropped slots,
        pages and state slots), in warmup()'s call form so that nothing
        compiles. Each program runs once, and a second time for the
        lesser of two, the short ones first, while the whole stays
        inside _COST_TIMING_SECONDS. One warmed bucket: nothing to
        choose, nothing timed."""
        buckets = grid["prefill_batches"]
        self._prefill_costs = {}
        if len(buckets) < 2:
            return
        ecfg, ps = self.ecfg, self.ecfg.page_size
        ids = np.random.default_rng(0)
        page_buckets = grid["page_buckets"] or [8]

        def run(PB: int, T: int, live: int) -> float:
            # the page bucket of a prompt's first chunk of T tokens
            P = next((p for p in page_buckets if p * ps >= T),
                     page_buckets[-1])
            n = min(T, P * ps)
            tokens = np.zeros((PB, T), np.int32)
            positions = np.full((PB, T), -1, np.int32)
            last_idx = np.zeros(PB, np.int32)
            tokens[:live, :n] = ids.integers(
                0, self.cfg.vocab_size, (live, n))
            positions[:live, :n] = np.arange(n)
            last_idx[:live] = n - 1
            operands = jax.block_until_ready((
                jnp.asarray(tokens), jnp.asarray(positions),
                jnp.zeros((PB, P), jnp.int32),
                jnp.full((PB, T), DROP_SLOT, jnp.int32),
                jnp.asarray(last_idx),
                (jnp.full((PB, T // ps), ecfg.num_pages, jnp.int32)
                 if T % ps == 0 else None)))
            tokens, positions, table, slots, last_idx, pslots = operands
            t0 = time.perf_counter()
            out = jax.block_until_ready(self.prefill_fn(
                self.params, tokens, positions, self.kv_k, self.kv_v,
                table, slots, last_idx, pslots,
                *self._state_args(self._drop_slots(PB, T, pslots is not None),
                                  self._no_src(PB))))
            ms = (time.perf_counter() - t0) * 1e3
            _logits, self.kv_k, self.kv_v = self._take_state(out)
            return ms

        forms = [(PB, T, live) for T in grid["prefill_lens"]
                 for PB in buckets for live in sorted({1, PB})]
        t0 = time.monotonic()
        read = {form: run(*form) for form in forms}
        for form in sorted(forms, key=read.get):    # the short ones first
            if (time.monotonic() - t0 + read[form] / 1e3
                    < _COST_TIMING_SECONDS):
                read[form] = min(read[form], run(*form))
        self._prefill_costs = {
            (PB, T): (read[PB, T, 1], read[PB, T, PB])
            for PB, T, _ in forms}
        log.info("prefill programs, ms at one row live / all: %s",
                 self._prefill_cost_table())

    def _prefill_cost_table(self) -> dict:
        """``_prefill_costs`` as stats() shows it: "<PB>x<T>" -> [ms with
        one row live, ms with every row live]."""
        return {f"{PB}x{T}": [round(one, 3), round(full, 3)]
                for (PB, T), (one, full) in self._prefill_costs.items()}

    def start(self) -> None:
        if self._loop_task is None:
            self._aio_loop = asyncio.get_running_loop()
            self._aio_loop_tid = threading.get_ident()
            # dynaprof: the serving loop gets a lag monitor + stall
            # watchdog for as long as an engine runs on it (refcounted;
            # stop() releases), and its ledger for _loop's own bracket
            # and the frontend's intake sum
            self._loop_ledger = profiling.acquire_loop_profiler().ledger
            self._loop_task = asyncio.ensure_future(self._loop())

    async def stop(self) -> None:
        self._stopped = True
        self._wake.set()
        if self._loop_task:
            await self._loop_task
            await profiling.release_loop_profiler()
        self._exec.shutdown(wait=False)
        # compiles are process-global: a stopped engine has no serving
        # path left to stall, and its armed fence would trip (raise mode:
        # kill) the next engine's warm-up in the same process
        self.fence.disarm()

    async def drain(self, timeout_s: float = 10.0) -> bool:
        """dynarevive graceful drain: refuse new work (``generate``
        raises typed NoCapacity) and run every in-flight sequence to its
        natural finish, bounded by ``timeout_s``. On timeout, leftovers
        are cancelled on the normal cancel path (pages free, clients get
        a "cancelled" finish). Returns True when everything finished
        inside the budget. The engine keeps running — call ``stop()``
        afterwards to end the scheduler loop."""
        self.draining = True
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(timeout_s, 0.0)

        def busy() -> bool:
            return bool(self.waiting or self.prefilling or self.running
                        or self._inflight or self._pending_prefills)

        while busy() and loop.time() < deadline:
            await asyncio.sleep(0.02)
        drained = not busy()
        if not drained:
            log.warning("engine drain timed out with work in flight "
                        "(waiting=%d prefilling=%d running=%d); "
                        "cancelling leftovers", len(self.waiting),
                        len(self.prefilling), len(self.running))
            for seq in self.waiting + self.prefilling + self.running:
                seq.context.kill()
            self._wake.set()
        return drained

    # ------------------------------------------------------ AsyncEngine API

    async def generate(self, request: PreprocessedRequest,
                       context: Context) -> AsyncIterator[EngineOutput]:
        if not isinstance(request, PreprocessedRequest):
            request = PreprocessedRequest.from_dict(request)
        if self.draining:
            # typed refusal (HTTP 503 + Retry-After upstream): a
            # draining engine admits nothing new while in-flight
            # sequences finish
            raise guard.NoCapacity("engine draining")
        self.start()
        if self.worker_label or self.mesh_devices > 1:
            # dynashard: stamp which replica/submesh serves this request
            # on the enclosing span (serve.generate_tokens on a worker,
            # http.request when served in-process)
            span = tracing.current_span()
            if span is not None:
                span.set_attribute("replica", self.worker_label)
                span.set_attribute("mesh_shape", self.mesh_shape)
        seq = Sequence(req=request, context=context, out=asyncio.Queue(),
                       tokens=list(request.token_ids),
                       num_prompt=len(request.token_ids),
                       trace_ctx=tracing.get_tracer().current_trace_ctx(),
                       block=self.block)
        if self._penalty_refusal and (
                _wants_count_state(request.sampling)
                or getattr(request.sampling, "logit_bias", None)):
            yield EngineOutput(finish_reason="error",
                               text=self._penalty_refusal)
            return
        if context.t_received is not None:
            # the frontend's first leg: its handler's entry to the stamp
            # engine_ttft_seconds_total starts from
            self._loop_ledger.add("intake", seq.arrival - context.t_received)
        if seq.num_prompt == 0:
            yield EngineOutput(finish_reason="error", text="empty prompt")
            return
        self.waiting.append(seq)
        self._wake.set()
        while True:
            out: EngineOutput = await seq.out.get()
            yield out
            if out.finish_reason is not None:
                return

    # ------------------------------------------------------------- metrics

    def stats(self) -> dict:
        """ForwardPassMetrics analog for the KV router
        (reference kv_router/protocols.rs:18-30). Keys here that match
        ForwardPassMetrics field names ride the stats plane into the
        metrics aggregator's dyn_worker_*/dyn_engine_* gauges."""
        lag = profiling.loop_lag_snapshot()
        return {
            # dynashard replica identity: the stable per-replica label +
            # submesh geometry ride the stats plane so the aggregator can
            # label gauges per replica (instance ids alone are unstable
            # lease hex) and dashboards can split by mesh size
            "worker_label": self.worker_label,
            "mesh_shape": self.mesh_shape,
            "mesh_devices": self.mesh_devices,
            # dynaslo: serving role + per-role mergeable latency
            # histograms (TTFT/ITL/queue-wait/e2e) — the aggregator
            # merges these across workers into fleet-wide quantiles
            "role": self.role,
            "latency_hist": self.latency.to_wire(),
            # dynaprof: loop health + page-pool occupancy
            "loop_lag_p50_seconds": lag["p50_s"],
            "loop_lag_p99_seconds": lag["p99_s"],
            "batch_dispatches_total": self.batch_dispatches_total,
            "kv_free_blocks": len(self.pm.free),
            "kv_cached_blocks": len(self.pm.reusable),
            "host_free_blocks": len(self.pm.host_free),
            "memory": memory_snapshot(self.pm, self._page_bytes),
            "request_active_slots": len(self.running) + len(self.prefilling),
            "request_total_slots": self.ecfg.max_batch,
            "kv_active_blocks": self.pm.active,
            "kv_total_blocks": self.ecfg.num_pages - 1,
            **self._state_stats(),
            **self._window_pool_stats(),
            **self._cross_rows_stats(),
            **self.window_counts,
            **self._diffusion_stats(),
            "num_requests_waiting": len(self.waiting),
            "queue_wait_seconds_total": round(self.queue_wait_seconds_total,
                                              4),
            # where the step thread's time went (engine/profiler.py
            # PHASES; sums to the wall time) and the TTFT split
            "step_phase_seconds_total": self.profiler.phase_snapshot(),
            # the step thread's CPU time by the same phases: wall less
            # CPU in a phase that does host work is the GIL or the run
            # queue (thread_runq_wait_seconds_total tells them apart)
            "step_phase_cpu_seconds_total": self.profiler.cpu_snapshot(),
            "step_iterations_total": self.profiler.step_iterations,
            # the loop and detokeniser threads' ledger, the frontend's
            # two legs, per-thread CPU / run-queue time, GC pauses
            **profiling.host_stats(self.profiler.native_id),
            "prefill_wait_seconds_total": self.prefill_wait_seconds_total,
            "first_token_seconds_total": self.first_token_seconds_total,
            "engine_ttft_seconds_total": self.engine_ttft_seconds_total,
            "first_tokens_total": self.first_tokens_total,
            # slot fill: real tokens / rows against the bucket that ran
            "prefill_tokens_total": self.prefill_tokens_total,
            "prefill_slots_total": self.prefill_slots_total,
            "prefill_dispatches_total": self.prefill_dispatches_total,
            "prefill_logits_skipped_total":
                self.prefill_logits_skipped_total,
            "prefill_window_topups_total":
                self.prefill_window_topups_total,
            "prefill_runahead_total": self.prefill_runahead_total,
            "prefill_row_chunks_total": self.prefill_row_chunks_total,
            "prefill_row_chunks_carried_total":
                self.prefill_row_chunks_carried_total,
            "moe_grouped_programs_total": self.moe_grouped_programs_total,
            "moe_grouped_window_forwards_total":
                self.moe_grouped_window_forwards_total,
            # the choice of a prefill's batch bucket (_dispatch_prefill)
            "prefill_rows_held_back_total":
                self.prefill_rows_held_back_total,
            "prefill_bucket_narrowed_total":
                self.prefill_bucket_narrowed_total,
            "decode_rows_total": self.decode_rows_total,
            "decode_slots_total": self.decode_slots_total,
            "decode_windows_total": self.decode_windows_total,
            "decode_windows_sampled_total":
                self.decode_windows_sampled_total,
            "warmup_seconds": self.warmup_seconds,
            "prefill_program_cost_ms": self._prefill_cost_table(),
            "gpu_cache_usage_perc": self.pm.usage(),
            # dynacache: the headline rate is WINDOWED (last
            # DYN_CACHE_WINDOW admissions) so the aggregator gauge tracks
            # recent traffic instead of flattening into the lifetime mean;
            # the cumulative counters ride alongside for totals/rates
            "gpu_prefix_cache_hit_rate": self._windowed_hit_rate(),
            "gpu_prefix_cache_hit_rate_lifetime":
                (self.prefix_hit_tokens_total /
                 max(self.prompt_tokens_total, 1)),
            "prefix_hit_tokens_total": self.prefix_hit_tokens_total,
            "prompt_tokens_total": self.prompt_tokens_total,
            **{f"cache_{k}": v
               for k, v in self.pm.cache_stats().items()},
            "host_cache_usage_perc": self.pm.host_usage(),
            "host_offload_pages_total": self.offload_pages_total,
            "host_restore_pages_total": self.restore_pages_total,
            "long_prefills_total": self.long_prefills_total,
            # compile fence: XLA compiles observed after warmup() armed
            # the fence (0 = the zero-compile serving invariant holds)
            "post_warmup_compiles_total": self.fence.post_warmup_compiles,
            # speculative decode observability: acceptance rate is
            # accepted/drafted (drafter quality); mean accepted length is
            # accepted drafts per verify step (tokens-per-dispatch gain —
            # each step also emits its bonus token on top)
            "spec_decode_steps": self.spec_steps,
            "spec_decode_draft_tokens_total": self.spec_draft_tokens_total,
            "spec_decode_accepted_tokens_total":
                self.spec_accepted_tokens_total,
            "spec_decode_acceptance_rate":
                (self.spec_accepted_tokens_total /
                 max(self.spec_draft_tokens_total, 1)),
            "spec_decode_mean_accepted_len":
                (self.spec_accepted_tokens_total /
                 max(self.spec_steps, 1)),
        }

    def _state_stats(self) -> dict:
        """The recurrent-state pool's keys of stats(); none for a model
        without one."""
        if self.state is None:
            return {}
        total = self.ecfg.max_batch
        return {"state_slots_total": total,
                "state_slots_active": total - len(self._state_free),
                "state_slots_held_total": self.state_slots_held_total,
                "state_slots_seen_total": self.state_slots_seen_total,
                # rows whose first chunk started from a page's snapshot
                # (0 for ever where the module keeps none)
                "state_restores_total": self.state_restores_total,
                # the pool by slot and, where there is one, by page
                "state_pool_bytes": int(sum(x.nbytes for x in self.state))}

    def _cross_rows_stats(self) -> dict:
        """stats() of a family whose programs run the layers that keep
        nothing a position on each row's last position alone (none for
        any other): the positions, padding included, that the prefill
        programs ran the self half on (rows x chunk length a dispatch)
        and the cross half on (rows x 1)."""
        if not self.family.cross_on_last:
            return {}
        return {"self_rows_total": self.self_rows_total,
                "cross_rows_total": self.cross_rows_total}

    def _window_pool_stats(self) -> dict:
        """stats() of the window layers' pool (none for a model with one
        pool): its pages now, the fill summed at every decode dispatch
        (held / seen), pages handed to rows and pages given back while
        the row ran, and the decode row-steps past the window. The
        ``kv_*_blocks`` keys above stay the full layers' pool."""
        if self.wpm is None:
            return {}
        wpm = self.wpm
        return {
            "kv_window_total_blocks": wpm.capacity,
            "kv_window_active_blocks": wpm.held,
            "kv_window_reserved_blocks": wpm.reserved,
            "kv_window_table_slots": wpm.table_slots,
            "kv_window_pages_held_total": self.kv_window_pages_held_total,
            "kv_window_pages_seen_total": self.kv_window_pages_seen_total,
            "kv_window_pages_allocated_total": wpm.allocated_total,
            "kv_window_pages_released_total": wpm.released_total,
            "decode_row_steps_total": self.decode_row_steps_total,
            "decode_row_steps_past_window_total":
                self.decode_row_steps_past_window_total}

    def _reserve_window(self, seq: Sequence) -> bool:
        """Reserve, at admission, the most window-pool pages the row will
        hold at once over its life: its tokens at their most (prompt,
        budget, the lookahead a decode dispatch covers), up to the
        table's slots. False: the pool is spoken for."""
        most = min(seq.num_prompt + seq.max_new()
                   + 2 * self.ecfg.decode_steps, self.cap_tokens)
        need = self.wpm.peak(max(most, len(seq.tokens) + 1))
        if not self.wpm.reserve(need):
            return False
        seq.wreserved = need
        return True

    def _give_back(self, rows) -> None:
        """Give back each row's window-pool pages that no query at its
        position or later can see. ``rows``: (sequence, the position of
        its next query). On the step thread, under ``dyn.kv.release`` in
        the trace (inside the dispatch phase that called it)."""
        with TraceAnnotation("dyn.kv.release"):
            for seq, pos in rows:
                seq.wfirst = self.wpm.give_back(seq.wpages, seq.wfirst, pos)

    def _diffusion_stats(self) -> dict:
        """stats() of a model that generates by diffusion over blocks
        (none for any other): blocks started by a live row, forwards a
        row went through (the denoising forwards: a block of 4 costs 4,
        and the first of them carries the block before it as well, whose
        K/V it makes final: ``folded_commits``, over ``blocks`` the share
        of blocks committed so; ``commit_forwards``, forwards that only
        commit, stays 0: the window has none), tokens emitted, tokens
        generated past a stop id or
        the budget inside a block and dropped, blocks that finished in
        fewer denoising forwards than their schedule. Summed from the
        window program's own counts at read-back (_process_window);
        ``decode_tokens_total`` keeps counting tokens, not forwards."""
        if self.block == 1:
            return {}
        return {f"diffusion_{k}_total": v for k, v in self.diffusion.items()}

    def _windowed_hit_rate(self) -> float:
        """Prefix-hit tokens / prompt tokens over the admission window
        (0.0 while empty). One pass over a bounded deque — cheap enough
        for every stats scrape."""
        hit = total = 0
        for h, p in self._hit_window:
            hit += h
            total += p
        return hit / total if total else 0.0

    def cache_snapshot(self) -> dict:
        """dynacache /debug/cache view: pool + host-tier occupancy, the
        allocation/eviction/restore counters, windowed vs lifetime hit
        rate, and the bounded top-K hot prefix chains."""
        topk = max(env_int("DYN_CACHE_TOPK") or 20, 0)
        with self._pm_lock:
            pm = self.pm
            snap = {
                "pool": {
                    "total_blocks": self.ecfg.num_pages - 1,
                    "active_blocks": pm.active,
                    "cached_blocks": len(pm.reusable),
                    "free_blocks": len(pm.free),
                    "usage": round(pm.usage(), 4),
                },
                "host_tier": {
                    "total_blocks": pm.host_pages,
                    "used_blocks": len(pm.host_by_hash),
                    "free_blocks": len(pm.host_free),
                    "usage": round(pm.host_usage(), 4),
                },
                "hit_rate_windowed": round(self._windowed_hit_rate(), 4),
                "hit_rate_lifetime": round(
                    self.prefix_hit_tokens_total
                    / max(self.prompt_tokens_total, 1), 4),
                "prefix_hit_tokens_total": self.prefix_hit_tokens_total,
                "prompt_tokens_total": self.prompt_tokens_total,
                **pm.cache_stats(),
                "top_prefixes": pm.top_prefixes(topk),
            }
        return snap

    # ------------------------------------------------------- scheduler loop

    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        # this coroutine's own work between two steps, from the step
        # future's wake-up to the next hand-off, is the loop ledger's
        # `engine_loop` (a leave without its enter does nothing)
        led = self._loop_ledger
        # `await run_in_executor` suspends this coroutine at least once
        # per iteration (the step future is never done at await time), so
        # the event loop drains its ready queue every step without a
        # yield of its own here
        while not self._stopped:
            if not (self.waiting or self.prefilling or self.running
                    or self._inflight or self._pending_prefills):
                led.leave("engine_loop")
                self._wake.clear()
                self.profiler.slept = True   # the gap to the next step is idle
                await self._wake.wait()
                continue
            if guard.chaos() is not None:
                # worker-scoped chaos (dynarevive): a delay rule on
                # `engine.stall` freezes the scheduler loop for its ms —
                # the kill-mid-decode / stalled-worker scenarios in the
                # same seeded grammar as the transport faults. The
                # `guard.chaos() is not None` gate keeps the hot path
                # free of the coroutine when no chaos is configured.
                await guard.chaos_point("engine.stall")
            try:
                step = loop.run_in_executor(self._exec, self._step)
                led.leave("engine_loop")
                await step
                led.enter("engine_loop")
                self._reap()
            except Exception:  # noqa: BLE001 — engine loop must survive
                log.exception("engine step failed")
                await loop.run_in_executor(self._exec, self._abort_all)
        led.leave("engine_loop")
        # shutdown: drain in-flight windows so no client hangs on a queue
        if self._inflight or self._pending_prefills:
            try:
                await loop.run_in_executor(self._exec, self._flush_pipeline)
            except Exception:  # noqa: BLE001
                log.exception("pipeline flush on stop failed")

    def _step(self) -> None:
        """One scheduler iteration (executor thread), inside the phase
        ledger: the gap since the last iteration is settled on entry, and
        whatever of the iteration no phase names is ``other``."""
        self.profiler.step_begin()
        try:
            self._step_phases()
        finally:
            self.profiler.step_end()

    def _step_phases(self) -> None:
        """One of three arms: speculative (``spec_decode``), single-step
        (``decode_steps`` <= 1, the reference the window is tested
        against) and the pipelined window arm that every deployment
        runs. Each admits on this thread (``_admit``)."""
        self._drain_kv_tier()
        if self.verify_fn is not None:
            self._admit()
            self._step_spec()
        elif self.ecfg.decode_steps <= 1:
            self._step_single()
        else:
            self._step_window()

    def _step_single(self) -> None:
        """Single-step decode: fully synchronous; budgeted mixing
        interleaves a decode step behind the trimmed prefill batch."""
        self._admit()
        budget = self.ecfg.prefill_token_budget
        if self.prefilling:
            pf = self._dispatch_prefill(budget)
            if pf is not None:
                self._process_prefill(pf)
        if self.running and (budget is not None or not self.prefilling):
            if budget is not None and self.prefilling:
                self.mixed_dispatches += 1
            self._decode_step_single()

    def _step_window(self) -> None:
        """The pipelined window arm: enqueue the next decode window and
        the next prefill chunk BEFORE reading back the previous ones, so
        the host round-trip overlaps device compute (the on-device carry
        makes this exact, not speculative). In order: dispatch, admit,
        read back what earlier iterations dispatched, free deferred
        pages.

        Prefill priority (``prefill_token_budget`` None), by what the
        iteration can observe:

        1. with something to prefill it enqueues the prefill first, and
           after that dispatch and admission, if something is STILL left
           (another chunk, a prompt admitted meanwhile, rows the bucket
           choice held back) and no earlier prefill is un-read, the next
           prefill at once, behind it (``prefill_runahead_total``): a
           dispatch is formed from host state that the dispatch before it
           left current, and needs none of its results. No window lies
           between two prefills. At most two prefills are un-read at any
           time (the one an iteration ships and one ahead): the iteration
           after such a pair reads the older back before it ships, and
           the newer at its end, so the run-ahead engages at the first
           iteration of a run of prefills and every later one ships one.
           The bound is the host's bookkeeping: a mid-prompt chunk has
           nothing to read and counts as read once visited, so more than
           two programs may lie unfinished on the device;
        2. after the last of these dispatches and its admission, if
           NOTHING is left to prefill, it enqueues the next window behind
           the prefill (``prefill_window_topups_total``). Every earlier
           prefill is read back before that window is built, so its rows
           decode in it; behind a single prefill the window's rows come
           from the in-flight window's carry as in any steady iteration,
           behind two the in-flight window (it ended before the first of
           them began) is read back first and the rows come from the
           host. If something is left it enqueues no window and the next
           iteration ships the next prefill;
        3. the order of programs on the device is the one an engine
           without the run-ahead of (1) and the window of (2) gives the
           same admissions: a program only reaches the queue one host
           iteration sooner. So the iteration after a window behind a
           prefill first reads the prefill back (it lies AHEAD of the
           window, and its rows enter ``running``) and admits, and then
           ships a prefill if one is now due, else the next window; it
           reads the topped-up window back only after that."""
        prev = self._pending
        unread = self._pending_prefills
        earlier = list(unread)
        budget = self.ecfg.prefill_token_budget
        if budget is None and prev is not None and earlier:
            # the last iteration shipped prev behind its last prefill
            # (rule 3)
            self._read_prefills(earlier)
            self._admit()
        if budget is None and self.prefilling:
            # the last iteration's run-ahead left two: the older, ended
            # on the device by now, is read before a third ships
            self._read_prefills(unread[:-1])
            shipped = self._ship_prefill()
            if shipped and self.prefilling and len(unread) < 2:
                self.prefill_runahead_total += self._ship_prefill()
            if not shipped:
                # the sweep shipped NOTHING (every candidate
                # restore-gated, cancelled, or cache-covered): the device
                # would idle a whole iteration, a decode window fills it
                self._pending = self._dispatch_decode_window()
            elif not self.prefilling:
                # rule 2. The rows of the prefills before the last one,
                # long done on the device, decode in this window too; the
                # window in flight ended before the first of two began
                if prev is not None and len(unread) > 1:
                    self._process_window(prev)
                self._read_prefills(unread[:-1])
                self._pending = self._dispatch_decode_window()
                self.prefill_window_topups_total += self._pending is not None
            else:
                self._pending = None
        else:
            # budgeted mixing (or nothing to prefill): decode windows
            # keep their cadence even while prompts are prefilling
            self._pending = self._dispatch_decode_window()
            pf = self._dispatch_prefill(budget)
            if pf is not None:
                unread.append(pf)
                self.mixed_dispatches += self._pending is not None
            self._admit()
        if prev is not None:
            self._process_window(prev)
        self._read_prefills(earlier)
        self._drain_deferred()
        # idle drain: with no live work left, read back the remaining
        # windows now so final tokens/finishes emit and pages free
        if (not (self.running or self.prefilling or self.waiting)
                and (self._inflight or unread)):
            self._flush_pipeline()

    def _ship_prefill(self) -> bool:
        """One prefill of the priority arm, un-read, and the admission
        behind it: admission's host work overlaps the device, and what it
        admits enters ``prefilling`` for the next sweep. False when the
        sweep shipped nothing."""
        pf = self._dispatch_prefill(None)
        if pf is not None:
            self._pending_prefills.append(pf)
        self._admit()
        return pf is not None

    def _read_prefills(self, pfs: List[_PendingPrefill]) -> None:
        """Read back ``pfs``, oldest first (read ones are skipped)."""
        for pf in pfs:
            self._process_prefill(pf)

    def _flush_pipeline(self) -> None:
        """Synchronize: read back every in-flight window/prefill so host
        state is current and all page releases are safe. Called before
        preemption (pool pressure), on shutdown, and by disagg jobs that
        need exclusive page ownership."""
        for w in list(self._inflight):
            self._process_window(w)
        self._pending = None
        self._read_prefills(list(self._pending_prefills))
        self._drain_deferred()

    def _abort_all(self) -> None:
        """Error path: drop pipeline state, release everything, fail all
        in-flight requests (the loop itself must survive). Covers the
        sequences parked OUTSIDE prefilling/running: deferred frees and
        every un-read prefill's finishing rows — dropping either would
        hang its client on a queue that never sees a finish_reason."""
        try:
            jax.block_until_ready(self.kv_k)
        except Exception:  # noqa: BLE001
            pass
        # land inflight offload gathers: their host slots are already
        # hash-mapped, so abandoning them would leave stale host content
        # a future restore could read
        try:
            self._land_inflight_offloads(self._offload_inflight)
        except Exception:  # noqa: BLE001
            pass
        self._offload_inflight.clear()
        parked = list(self._deferred_free)
        for pf in self._pending_prefills:
            parked += [s for _, s in pf.finishing]
        self._inflight.clear()
        self._pending = None
        self._pending_prefills.clear()
        self._deferred_free.clear()
        for seq in parked + self.prefilling + self.running:
            self._release(seq)
            self._finish(seq, "error")
        self.prefilling.clear()
        self.running.clear()

    # ----------------------------------------------------------- admission

    def _admit(self) -> None:
        """Admission, on the step thread only. The guard keeps the common
        no-waiters iteration at one compare and out of the ``admit``
        phase."""
        if self.waiting:
            self._admit_waiting()

    @_phased("admit")
    def _admit_waiting(self) -> None:
        while self.waiting and (len(self.running) + len(self.prefilling)
                                < self.ecfg.max_batch):
            seq = self.waiting[0]
            if seq.context.stopped:
                self.waiting.pop(0)
                self._finish(seq, _cancel_reason(seq.context))
                continue
            if seq.num_prompt >= self.cap_tokens:
                # admission is clamped to the warmed bucket grid: a prompt
                # needing more pages than the largest page bucket would
                # force a fresh XLA compile mid-serving (VERDICT r2 weak
                # #6) — reject instead (long prompts route to the
                # sequence-parallel ring-prefill path when configured)
                self.waiting.pop(0)
                self._emit(seq, EngineOutput(
                    token_ids=[],
                    text=f"prompt length {seq.num_prompt} exceeds engine "
                         f"context capacity {self.cap_tokens}"))
                self._finish(seq, "error")
                continue
            if self.state is not None and not self._state_free:
                # every state slot is held (a finished row keeps its slot
                # until no window in flight lists it); wait for frees
                break
            if self.wpm is not None and not self._reserve_window(seq):
                break  # the window layers' pool is spoken for; wait
            chain = self._chain(seq)
            if self._state_snapshots:
                # a hit must leave a token to prefill: the state comes
                # back in the row's first chunk, from the last hit page's
                # snapshot (a resumed row's extent is one token short of
                # its tokens, so PageManager's own cap is one page long)
                chain = chain[:max(seq.prefill_extent - 1, 0)
                              // self.ecfg.page_size]
            with self._pm_lock:
                alloc = self.pm.allocate_sequence(seq.tokens, chain=chain)
                if (alloc is None
                        or self.pm.available < self.ecfg.watermark_pages):
                    if alloc is not None:
                        self.pm.release_sequence(alloc[0])
                    if self.wpm is not None:
                        self.wpm.unreserve(seq.wreserved)
                        seq.wreserved = 0
                    break  # out of pages; wait for frees
                if alloc.restores:
                    # gate this sequence out of prefill until its
                    # host→HBM restores have dispatched (chunked drain)
                    self._unrestored_pages.update(
                        p for p, _ in alloc.restores)
            self.waiting.pop(0)
            pages, cached_tokens = alloc
            seq.pages = pages
            seq.computed = min(cached_tokens, seq.prefill_extent)
            if self.state is not None:
                # whatever the slot holds is dropped by the row's first
                # chunk: zeros where it starts at position 0, the last
                # hit page's snapshot after a hit (_dispatch_prefill)
                seq.state_slot = self._state_free.pop()
                seq.state_from_page = seq.computed > 0
            if alloc.restores:
                # restore_wait stops when the sequence clears the
                # _unrestored_pages gate in _dispatch_prefill
                seq.restore_t0 = time.monotonic()
            if seq.generated == 0:  # don't double-count resumed sequences
                seq.t_admit = time.monotonic()
                wait = seq.t_admit - seq.arrival
                self.queue_wait_seconds_total += wait
                seq.queue_wait_s = wait
                self.latency.observe("queue_wait", wait)
                seq.prefix_hit = seq.computed
                seq.device_hit_blocks = alloc.device_hit_blocks
                seq.host_restored_blocks = alloc.host_restored_blocks
                self.step_timeline.add(
                    "admit", queue_wait_ms=round(wait * 1000.0, 3),
                    request_id=seq.context.id,
                    occupancy=len(self.running) + len(self.prefilling) + 1,
                    waiting=len(self.waiting))
                self.prefix_hit_tokens_total += seq.computed
                self.prompt_tokens_total += seq.num_prompt
                self._hit_window.append((seq.computed, seq.num_prompt))
            # proto: request.lifecycle admitted->prefill
            self.prefilling.append(seq)

    # ------------------------------------------------------- KV tier drain

    def _land_inflight_offloads(self, entries) -> None:
        """Copy parked offload gathers into the host pool (the D2H
        readback that overlapped the intervening device steps). Under
        host_tier_int8 each entry carries (q, s) pairs — quantized on
        device before the D2H, so these np.asarray reads move int8."""
        for k_dev, v_dev, oslots, n in entries:
            if self.ecfg.host_tier_int8:
                (kq, ks), (vq, vs) = k_dev, v_dev
                self.host_k[:, oslots] = np.asarray(kq)[:, :n]
                self.host_k_s[:, oslots] = np.asarray(ks)[:, :n]
                self.host_v[:, oslots] = np.asarray(vq)[:, :n]
                self.host_v_s[:, oslots] = np.asarray(vs)[:, :n]
            else:
                self.host_k[:, oslots] = np.asarray(k_dev)[:, :n]
                self.host_v[:, oslots] = np.asarray(v_dev)[:, :n]

    def _drain_kv_tier(self, full: bool = False) -> None:
        """Run queued HBM↔host page copies (executor thread, before any
        device step so offloads read pre-step content and restores land
        before their pages are attended to). Batched, pow2-padded gathers
        keep the compile count logarithmic in batch size.

        Overlap strategy (its defaults are unmeasured on a directly
        attached chip): offload gathers dispatch WITHOUT a synchronous
        readback — the device arrays park in ``_offload_inflight`` and
        are copied to the host pool on a LATER drain, overlapping the
        intervening device step. Restores are chunked
        (``tier_restore_chunk`` per iteration) so a bulk restore cannot
        stall every other request; their sequences stay gated via
        ``_unrestored_pages`` until the copy dispatches.

        With ``restore_overlap`` the drained batch is PIPELINED: its
        host-slot gather + H2D + dequantize dispatch now, but the page
        inject lands at the START of the next drain — the transfer gets
        the whole intervening device step to complete instead of
        stalling it. Staged pages stay in ``_unrestored_pages`` until
        injected; rows whose page was recycled in between are remapped
        to the out-of-range pad target at inject time (the scatter
        drops them), so a late inject can never clobber a reallocated
        page.

        ``full=True`` drains EVERYTHING now — required by the paths that
        hand pages to a consumer with no later drain between (disagg
        reserve/extract/inject)."""
        if self.host_k is None:
            return
        with self.profiler.phase("kv_tier"):
            self._drain_kv_tier_ops(full)

    def _drain_kv_tier_ops(self, full: bool) -> None:
        chunk = None if full else (self.ecfg.tier_restore_chunk or None)
        # land the previous drain's staged restore batch FIRST: its H2D
        # overlapped the intervening step, so this inject is cheap
        if self._restore_staged is not None:
            self._inject_staged()
        with self._pm_lock:
            off, res = self.pm.drain_tier_ops(restore_limit=chunk)
            # block hash per drained page, captured under the lock — the
            # inject-time validity check compares against by_hash
            res_hashes = [self.pm.pages[p].block_hash for p, _ in res]
            # the gate set mirrors the still-queued restores exactly —
            # this also un-gates pages whose stale restore _pop_fresh
            # cancelled on reallocation (their new owner must not wait
            # for a copy that will never run). Newly staged pages are
            # added back below.
            self._unrestored_pages = {p for p, _ in
                                      self.pm.pending_restore}
        if off:
            pages = [p for p, _ in off]
            slots = [s for _, s in off]
            idx = jnp.asarray(_pad_pow2(pages, 0), jnp.int32)
            # dispatch only — no np.asarray round-trip here
            k_dev = _gather_pages(self.kv_k, idx)
            v_dev = _gather_pages(self.kv_v, idx)
            if self.ecfg.host_tier_int8:
                from .kv_compress import quantize_pages

                k_dev = quantize_pages(k_dev)  # (q, s) device pair
                v_dev = quantize_pages(v_dev)
            self._offload_inflight.append((k_dev, v_dev, slots, len(off)))
            self.offload_pages_total += len(off)
        # harvest offload gathers whose D2H overlapped earlier steps. With
        # restores about to run, EVERY inflight offload must land first (a
        # restore may read a slot whose content is still in flight);
        # otherwise keep the newest gather in flight to overlap the next
        # step
        land_all = bool(res) or full
        if self._offload_inflight and (land_all
                                       or len(self._offload_inflight) > 1):
            keep = [] if land_all else self._offload_inflight[-1:]
            harvest = (self._offload_inflight if land_all
                       else self._offload_inflight[:-1])
            self._offload_inflight = keep
            self._land_inflight_offloads(harvest)
        if res:
            rt0 = time.monotonic()
            pages = [p for p, _ in res]
            slots = [s for _, s in res]
            # pad the host gather with slot 0 (content discarded)
            hsl = _pad_pow2(slots, 0)
            if self.ecfg.host_tier_int8:
                # H2D moves int8 + scales; dequant runs on device
                from .kv_compress import dequantize_pages

                k_rows = dequantize_pages(
                    jnp.asarray(self.host_k[:, hsl]),
                    jnp.asarray(self.host_k_s[:, hsl]))
                v_rows = dequantize_pages(
                    jnp.asarray(self.host_v[:, hsl]),
                    jnp.asarray(self.host_v_s[:, hsl]))
            else:
                k_rows = jnp.asarray(self.host_k[:, hsl])
                v_rows = jnp.asarray(self.host_v[:, hsl])
            overlap = bool(self.ecfg.restore_overlap) and not full
            if overlap:
                # pipeline: park the in-flight rows; the inject lands at
                # the start of the NEXT drain. Pages stay gated.
                self._restore_staged = (pages, res_hashes, k_rows, v_rows)
                self._unrestored_pages.update(pages)
            else:
                # serial (A/B control / full drain): inject in the same
                # drain. Pad targets out-of-range → dropped by the
                # scatter.
                idx = _pad_pow2(pages, self.ecfg.num_pages)
                iidx = jnp.asarray(idx, jnp.int32)
                self.kv_k = _inject_pages(self.kv_k, iidx, k_rows)
                self.kv_v = _inject_pages(self.kv_v, iidx, v_rows)
            self.restore_pages_total += len(res)
            # dynacache: restore drain visibility — a step-timeline event
            # and a dyntrace span per drained batch (dispatch time only;
            # no sync added — the copies land with the next device step).
            # Both are no-ops when their ring/sampling is off.
            rdt = time.monotonic() - rt0
            self.step_timeline.add(
                "cache.restore", pages=len(res),
                queued=len(self._unrestored_pages),
                staged=int(overlap),
                dispatch_ms=round(rdt * 1000.0, 3))
            tracing.get_tracer().record_span(
                "cache.restore", rdt, start=rt0, parent=None,
                attributes={"pages": len(res), "staged": overlap,
                            "queued": len(self._unrestored_pages)})

    def _inject_staged(self) -> None:
        """Land the staged restore batch (restore_overlap second half).
        Rows whose page was recycled since staging (sequence released
        and the page re-popped — its hash no longer maps to it) are
        remapped to the out-of-range pad target so the scatter drops
        them; their content now belongs to someone else."""
        pages, hashes, k_rows, v_rows = self._restore_staged
        self._restore_staged = None
        with self._pm_lock:
            tgt = [p if self.pm.by_hash.get(h) == p else self.ecfg.num_pages
                   for p, h in zip(pages, hashes)]
        iidx = jnp.asarray(_pad_pow2(tgt, self.ecfg.num_pages), jnp.int32)
        self.kv_k = _inject_pages(self.kv_k, iidx, k_rows)
        self.kv_v = _inject_pages(self.kv_v, iidx, v_rows)
        self._unrestored_pages.difference_update(pages)

    # ------------------------------------------------------------- prefill

    @_phased("dispatch_prefill")
    def _dispatch_prefill(self, token_budget: Optional[int] = None
                          ) -> Optional[_PendingPrefill]:
        """Enqueue one chunked-prefill step over a BATCH of prefilling
        sequences (each contributes its next chunk) WITHOUT reading back.
        On the HOST a batch is the cheaper: N prompts cost one round
        trip, not N, and under pipelining that round trip overlaps the
        in-flight decode window. On the DEVICE it is so only where the
        rows share something, as the read of a layer's experts: a dense
        chunk of a few hundred tokens is compute-bound alone, and N
        prompts in a program padded to its bucket cost more than N
        programs of one. So the batch formed here is what MAY ship, and
        choose_prefill_bucket takes the warmed bucket that costs the
        least a row by what warmup() read of the programs; the rows it
        leaves stay in ``prefilling``, in order, for the next sweep.

        ``last_idx`` tells the program which rows' logits will be read:
        the chunk's last position for a row that ends its prompt here
        and draws its first token from them (_draws_first_token), -1 for
        every other row, padding included: a chunk in the middle of a
        prompt, a preemption-resume, a model that generates by blocks.
        A program with no entry >= 0 computes no head (and, for a
        family that declares cross_on_last, none of the stack's suffix:
        llama.prefill_logits) and returns zeros, which nobody reads;
        ``prefill_logits_skipped_total`` counts those dispatches."""
        candidates: List[Sequence] = []
        for seq in list(self.prefilling):
            if seq.context.stopped:
                self.prefilling.remove(seq)
                self._terminate(seq, _cancel_reason(seq.context))
                continue
            if self._unrestored_pages and not self._unrestored_pages.isdisjoint(
                    seq.pages):
                # host-tier restores for this sequence are still queued
                # (chunked drain): computing on its pages now would read
                # stale KV. It waits; the drain clears a chunk per
                # iteration
                continue
            if seq.restore_t0 is not None:
                # dynacache: the sequence's host-tier restores have all
                # dispatched — admission→here is its restore wait
                seq.restore_wait_s = time.monotonic() - seq.restore_t0
                seq.restore_t0 = None
            if seq.prefill_extent - seq.computed <= 0:
                # resumed sequence fully covered by the prefix cache
                self.prefilling.remove(seq)
                seq.last_token = seq.tokens[-1]
                # proto: request.lifecycle prefill->decode
                self.running.append(seq)
                continue
            if (self.long_prefill_fn is not None
                    and seq.prefill_extent - seq.computed
                    > self.ecfg.long_prefill_threshold):
                # sequence-parallel ring prefill: one dispatch for the
                # whole prompt, sharded over the mesh's seq axis
                self.prefilling.remove(seq)
                self._long_prefill(seq)
                continue
            candidates.append(seq)
        if not candidates:
            return None
        # bucket-homogeneous batching: the dispatch pads every row to the
        # LARGEST member's (T, P) bucket, so one long prompt in a batch of
        # short ones multiplies the whole batch's padded attention flops.
        # Keep FIFO fairness for the head, then prefer its bucket-mates.
        head = candidates[0]

        def tbucket(s):
            return self.ecfg.bucket_len(
                min(s.prefill_extent - s.computed, self.ecfg.prefill_chunk))

        hb = tbucket(head)
        # fill with the head's bucket-mates, then with SMALLER-bucket
        # prompts only (they ride along without raising T; a larger-bucket
        # member would promote every row's padded attention to its bucket)
        mates = [s for s in candidates[1:] if tbucket(s) == hb]
        picked = {id(head)} | {id(s) for s in mates}
        batch = [head] + mates
        batch += [s for s in candidates[1:]
                  if id(s) not in picked and tbucket(s) < hb]
        batch = batch[: self.ecfg.max_prefill_batch]
        if token_budget is not None:
            # budgeted mixing: trim the batch to ~token_budget prompt
            # tokens. The head always ships (its chunk may alone exceed a
            # small budget — per-iteration prefill is then bounded by
            # max(prefill_chunk, budget), keeping chunk starts page-aligned
            # rather than slicing mid-chunk)
            kept, total = [], 0
            for s in batch:
                c = min(s.prefill_extent - s.computed,
                        self.ecfg.prefill_chunk)
                if kept and total + c > token_budget:
                    break
                kept.append(s)
                total += c
            batch = kept

        unmeasured = self.ecfg.prefill_bucket_batch(len(batch))
        B, rows = choose_prefill_bucket(
            self._prefill_costs, hb, len(batch), unmeasured)
        self.prefill_rows_held_back_total += len(batch) - rows
        self.prefill_bucket_narrowed_total += int(B != unmeasured)
        batch = batch[:rows]
        chunks = [min(s.prefill_extent - s.computed, self.ecfg.prefill_chunk)
                  for s in batch]
        T = self.ecfg.bucket_len(max(chunks))
        P = self.ecfg.bucket_pages(max(len(s.pages) for s in batch))

        tokens = np.zeros((B, T), np.int32)
        positions = np.full((B, T), -1, np.int32)
        table = np.zeros((B, P), np.int32)
        last_idx = np.full(B, -1, np.int32)
        ps = self.ecfg.page_size
        # page-granular KV commit when the bucket is page-aligned AND every
        # chunk start is (prefix hits are whole pages and chunk sizes are
        # ps-multiples, so misalignment means an exotic config slipped past
        # __post_init__ — fall back to the row scatter rather than crash)
        use_paged = (T % ps == 0
                     and all(s.computed % ps == 0 for s in batch))
        slots = np.full((B, T), DROP_SLOT, np.int32)
        pslots = np.full((B, max(T // ps, 1)), self.ecfg.num_pages, np.int32)
        if self.wkv is not None:
            # the window layers' pages: give back what the chunk's first
            # query no longer sees, then cover the chunk
            self._give_back([(s, s.computed) for s in batch])
            for seq, chunk in zip(batch, chunks):
                self.wpm.cover(seq.wpages, seq.wfirst, seq.computed + chunk)
        sslots = self._row_slots(
            batch, B, ((s, s.computed, c) for s, c in zip(batch, chunks)),
            T, use_paged)
        ssrc = self._no_src(B)
        for i, (seq, chunk) in enumerate(zip(batch, chunks)):
            start = seq.computed
            self.prefill_row_chunks_total += 1
            self.prefill_row_chunks_carried_total += start > 0
            if seq.state_from_page:
                # the first chunk after a hit (whole pages, so start is
                # a page's first token): the state after the page before
                ssrc[i] = seq.pages[start // ps - 1]
                seq.state_from_page = False
                self.state_restores_total += 1
            tokens[i, :chunk] = seq.tokens[start:start + chunk]
            positions[i, :chunk] = np.arange(start, start + chunk)
            pages = np.asarray(seq.pages, np.int64)
            table[i, :len(seq.pages)] = seq.pages
            if (start + chunk >= seq.prefill_extent
                    and self._draws_first_token(seq)):
                last_idx[i] = chunk - 1
            # flat slots are always built: they are the commit path of an
            # unaligned chunk; every model module ignores them when
            # page_slots is present
            pos = np.arange(start, start + chunk)
            slots[i, :chunk] = pages[pos // ps] * ps + pos % ps
            if use_paged:
                first = start // ps
                npg = (chunk + ps - 1) // ps
                pslots[i, :npg] = pages[first:first + npg]

        logits, self.kv_k, self.kv_v = self._take_state(self.prefill_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            self.kv_k, self.kv_v, jnp.asarray(table), jnp.asarray(slots),
            jnp.asarray(last_idx),
            jnp.asarray(pslots) if use_paged else None,
            *self._state_args(sslots, ssrc)))
        self._account_dispatch(batch)
        self.steps += 1
        self.prefill_slots_total += B * T
        self.prefill_dispatches_total += 1
        wants_logits = bool((last_idx >= 0).any())
        self.prefill_logits_skipped_total += not wants_logits
        if self.family.cross_on_last:
            self.self_rows_total += B * T
            self.cross_rows_total += B * wants_logits
        self.moe_grouped_programs_total += moe_kernel_takes(
            self.cfg, self.params, self.mesh, B * T)
        self._stamp_first_dispatch(batch)
        self.step_timeline.add(
            "prefill", batch=len(batch), tokens=int(sum(chunks)),
            occupancy=len(self.running) + len(self.prefilling),
            waiting=len(self.waiting))

        finishing: List[Tuple[int, Sequence]] = []
        for i, (seq, chunk) in enumerate(zip(batch, chunks)):
            seq.computed += chunk
            self.prefill_tokens_total += chunk
            if seq.computed >= seq.prefill_extent:
                self.prefilling.remove(seq)
                finishing.append((i, seq))
        if not finishing:
            # a chunk dispatch with nothing to read back still returns a
            # (finishing-empty) marker: _step must distinguish
            # "dispatched, mid-prompt" from "dispatched nothing" so
            # prefill-priority only skips the decode window on iterations
            # that actually shipped prefill work
            return _PendingPrefill(finishing=[], sampled=None)
        # one on-device sampling pass over the full bucket (avoids a fresh
        # compile per finishing-count); skipped entirely when every
        # finishing row is a preemption-resume (next token already sampled)
        # and for a model that generates by blocks (the logits at the
        # prompt's last position are of that position's own token): the
        # program was told so and computed none
        if wants_logits:
            sampled, aux = self._sample_device(batch, logits)
        else:
            sampled, aux = None, None
        return _PendingPrefill(finishing=finishing, sampled=sampled,
                               aux=aux)

    def _draws_first_token(self, seq: Sequence) -> bool:
        """Whether the prefill program that ends ``seq``'s prompt is
        sampled from (_process_prefill appends the draw): not after a
        preemption (the next token is sampled already), and never for a
        model that generates by blocks (the logits at the prompt's last
        position are of that position's own token)."""
        return self.block == 1 and seq.generated == 0

    def _long_prefill(self, seq: Sequence) -> None:
        """Whole-prompt sequence-parallel prefill via ring attention: run
        the seq-sharded stack over the padded prompt, scatter the per-layer
        K/V into the paged pool, sample the first token. Synchronous (one
        dispatch covers thousands of tokens, so the pipelining that hides
        per-window round-trips buys little here)."""
        from ..parallel.ring_attention import scatter_prefill_kv

        extent = seq.prefill_extent
        ps = self.ecfg.page_size
        T = self._long_bucket(extent)
        tokens = np.zeros((1, T), np.int32)
        positions = np.full((1, T), -1, np.int32)
        tokens[0, :extent] = seq.tokens[:extent]
        positions[0, :extent] = np.arange(extent)
        logits, k_all, v_all = self.long_prefill_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions))
        self._account_dispatch([seq])
        self.prefill_slots_total += T
        self.prefill_dispatches_total += 1
        self._stamp_first_dispatch([seq])
        pages = np.asarray(seq.pages, np.int64)
        pos = np.arange(T)
        # positions below seq.computed are prefix-cache hits living in
        # pages SHARED with other sequences — the ring pass recomputes
        # them (whole-prompt program; the math needs their K/V in flight)
        # but must NOT write them back: FP accumulation-order differences
        # vs the committed content would mutate pages another decoding
        # sequence is attending to
        writable = (pos >= seq.computed) & (pos < extent)
        slots = np.where(writable,
                         pages[np.minimum(pos // ps, len(pages) - 1)] * ps
                         + pos % ps, DROP_SLOT)[None, :]
        self.kv_k, self.kv_v = scatter_prefill_kv(
            self.kv_k, self.kv_v, k_all, v_all,
            jnp.asarray(slots, jnp.int32))
        self.prefill_tokens_total += extent - seq.computed
        seq.computed = extent
        self.long_prefills_total += 1
        self.steps += 1
        self._commit_full_pages(seq)
        if seq.generated == 0:
            toks_d, aux_d = self._sample_device([seq], logits)
            with self.profiler.phase("readback_prefill"):
                aux = (tuple(np.asarray(a) for a in aux_d)
                       if aux_d is not None else None)
                tok = int(np.asarray(toks_d)[0])
            with self.profiler.phase("process_prefill"):
                self._append_token(seq, tok, lp=self._lp_entry(seq, aux, 0))
                if seq.finished is None:
                    # proto: request.lifecycle prefill->decode
                    self.running.append(seq)
        else:
            # resumed after preemption: next token already sampled
            seq.last_token = seq.tokens[-1]
            # proto: request.lifecycle prefill->decode
            self.running.append(seq)

    def _stamp_first_dispatch(self, batch: List[Sequence]) -> None:
        """TTFT split: the first prefill dispatch that carries a chunk
        of each prompt (one clock read per dispatch, not per row)."""
        now = time.monotonic()
        for seq in batch:
            if seq.t_first_dispatch is None:
                seq.t_first_dispatch = now

    def _long_bucket(self, extent: int) -> int:
        """Padded length for the ring prefill: pow2 multiples of
        lcm(seq_axis, page_size) — divisible by the seq axis for
        shard_map, page-aligned, logarithmically many compiles."""
        base = math.lcm(self._seq_par, self.ecfg.page_size)
        T = base
        while T < extent:
            T *= 2
        return T

    def _process_prefill(self, pf: _PendingPrefill) -> None:
        """Read back a dispatched prefill's first-token draws and admit
        the finished prompts into decode."""
        if pf.processed:
            return
        pf.processed = True
        if pf in self._pending_prefills:
            self._pending_prefills.remove(pf)
        if not pf.finishing:
            return      # a mid-prompt chunk: nothing to read back
        with self.profiler.phase("readback_prefill"):
            # the step thread blocked on the device: the prefill program
            # and whatever was queued ahead of it
            toks = np.asarray(pf.sampled) if pf.sampled is not None else None
            aux = (tuple(np.asarray(a) for a in pf.aux)
                   if pf.aux is not None else None)
        with self.profiler.phase("process_prefill"):
            for i, seq in pf.finishing:
                self._commit_full_pages(seq)
                if self._draws_first_token(seq):
                    self._append_token(seq, int(toks[i]),
                                       lp=self._lp_entry(seq, aux, i))
                    if seq.finished is None:
                        # proto: request.lifecycle prefill->decode
                        self.running.append(seq)
                else:
                    # resumed after preemption: last token already sampled
                    # (or a model that generates by blocks: nothing is)
                    seq.last_token = seq.tokens[-1]
                    # proto: request.lifecycle prefill->decode
                    self.running.append(seq)

    # -------------------------------------------------------------- decode

    def _grow_or_preempt(self, batch: List[Sequence], lookahead: int) -> None:
        """Grow every batch member's pages ``lookahead`` tokens ahead
        (clamped to the grid capacity); on pool exhaustion, flush the
        pipeline (so releases are safe and deferred frees land) and
        preempt newest-arrival sequences until the batch fits."""
        for seq in list(batch):
            if seq not in batch:
                continue
            if seq.finished is not None or seq.context.stopped:
                # a flush below may have finished earlier batch members
                batch.remove(seq)
                continue
            target = min(len(seq.tokens) + lookahead, self.cap_tokens)
            if self.pm.grow(seq.pages, target):
                continue
            self._flush_pipeline()  # host state current; frees landed
            if seq.finished is not None or seq.context.stopped:
                batch.remove(seq)  # the flush finished/cancelled it
                continue
            target = min(len(seq.tokens) + lookahead, self.cap_tokens)
            while not self.pm.grow(seq.pages, target):
                live = [s for s in self.running if s.finished is None]
                if not live:
                    batch.remove(seq)
                    break
                victim = max(live, key=lambda s: s.arrival)
                log.warning("KV pool exhausted; preempting %s",
                            victim.context.id)
                if victim in batch:
                    batch.remove(victim)
                self.running.remove(victim)
                self._release(victim)
                victim.computed = 0  # keep tokens/generated: resume not redo
                # proto: request.lifecycle decode->admitted
                self.waiting.insert(0, victim)
                if victim is seq:
                    break
        if self.wpm is not None:
            # the window layers' pages: what no later query sees goes
            # back, then the same lookahead is covered, inside each row's
            # reservation (made at admission for its whole life)
            self._give_back([(s, len(s.tokens) - 1) for s in batch])
            for seq in batch:
                self.wpm.cover(seq.wpages, seq.wfirst,
                               min(len(seq.tokens) + lookahead,
                                   self.cap_tokens))
        # drain tier ops queued by grow-evictions NOW, before this step's
        # forward dispatch: the evicted page's new owner writes it in the
        # program we're about to enqueue, and a drain on the NEXT step
        # would gather content the device has already overwritten —
        # poisoning the host tier with spliced pages
        self._drain_kv_tier()

    @_phased("dispatch_window")
    def _decode_step_single(self, batch: Optional[List[Sequence]] = None
                            ) -> None:
        """K=1 decode: one forward + sample per dispatch, synchronous.
        ``batch`` restricts the step to a subset of running rows (the
        spec-decode fallback arm); None takes every running row."""
        if batch is None:
            batch = [s for s in self.running if s.finished is None]
        batch = batch[: self.ecfg.max_batch]
        for seq in list(batch):
            if seq.context.stopped:
                batch.remove(seq)
                self.running.remove(seq)
                self._release(seq)
                self._finish(seq, _cancel_reason(seq.context))
        self._grow_or_preempt(batch, 1)
        if not batch:
            return
        B = self.ecfg.bucket_batch(len(batch))
        P = self.ecfg.bucket_pages(max(len(s.pages) for s in batch))
        tokens = np.zeros(B, np.int32)
        positions = np.full(B, -1, np.int32)
        table = np.zeros((B, P), np.int32)
        slots = np.full(B, DROP_SLOT, np.int32)
        sslots = self._row_slots(
            batch, B, ((s, len(s.tokens) - 1, 1) for s in batch), 1)
        for i, seq in enumerate(batch):
            pos = len(seq.tokens) - 1  # position of last_token
            tokens[i] = seq.last_token
            positions[i] = pos
            table[i, :len(seq.pages)] = seq.pages
            page = seq.pages[pos // self.ecfg.page_size]
            slots[i] = (page * self.ecfg.page_size
                        + pos % self.ecfg.page_size)
        logits, self.kv_k, self.kv_v = self._take_state(self.decode_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            self.kv_k, self.kv_v, jnp.asarray(table), jnp.asarray(slots),
            *self._state_args(sslots)))
        toks_d, aux_d = self._sample_device(batch, logits)
        self._account_dispatch(batch)
        self._count_decode_slots(batch, B, 1)
        with self.profiler.phase("readback_window"):
            sampled = np.asarray(toks_d)[:len(batch)]
            aux = (tuple(np.asarray(a) for a in aux_d)
                   if aux_d is not None else None)
        self.steps += 1
        self.decode_tokens_total += len(batch)
        with self.profiler.phase("process_window"):
            for i, (seq, tok) in enumerate(zip(batch, sampled)):
                self._append_token(seq, int(tok),
                                   lp=self._lp_entry(seq, aux, i))
        self.step_timeline.add(
            "decode", batch=len(batch), tokens=len(batch),
            occupancy=len(self.running) + len(self.prefilling),
            waiting=len(self.waiting))

    # -------------------------------------------------- speculative decode

    def _step_spec(self) -> None:
        """Scheduler iteration with self-speculative decoding enabled.

        Synchronous (no cross-iteration pipelining): the speculative win
        is up to K+1 tokens per dispatch, not dispatch overlap — and the
        drafter reads host token lists every step, so they must be
        exact. Prefill keeps its existing policies (priority or budgeted
        mixing). Rows whose drafter finds a candidate continuation take
        the batched verify step; everything else — no draft found,
        non-greedy sampling, penalties, logit_bias, logprobs — falls
        back to the standard fused-window/single-token dispatch."""
        budget = self.ecfg.prefill_token_budget
        if self.prefilling:
            pf = self._dispatch_prefill(budget)
            if pf is not None:
                self._process_prefill(pf)
        if self.prefilling and budget is None:
            return
        for seq in list(self.running):
            if seq.context.stopped:
                self._terminate(seq, _cancel_reason(seq.context))
        batch = [s for s in self.running if s.finished is None]
        batch = batch[: self.ecfg.max_batch]
        if not batch:
            return
        if self.prefilling:
            self.mixed_dispatches += 1
        spec_rows: List[Sequence] = []
        drafts: Dict[int, List[int]] = {}
        rest: List[Sequence] = []
        for seq in batch:
            d = self._draft_for(seq)
            if d:
                spec_rows.append(seq)
                drafts[id(seq)] = d
            else:
                rest.append(seq)
        if spec_rows:
            self._decode_step_spec(spec_rows, drafts)
        # the spec step's pool-pressure preemption can evict rows parked
        # in `rest` (they lose their pages and requeue) — never dispatch
        # a row the scheduler no longer runs
        rest = [s for s in rest if s in self.running]
        if rest:
            if self.ecfg.decode_steps > 1:
                pend = self._dispatch_decode_window(batch=rest)
                if pend is not None:
                    self._process_window(pend)
            else:
                self._decode_step_single(batch=rest)
        self._drain_deferred()

    def _draft_for(self, seq: Sequence) -> List[int]:
        """Prompt-lookup draft for one row, or [] when the row bypasses
        speculation. Bypass covers exactly the semantics a greedy
        multi-token verify cannot reproduce: sampled rows (temperature),
        count-state penalties and logit_bias (their logits depend on
        tokens accepted earlier in the SAME step), and logprobs requests
        (the verify path returns no per-token aux)."""
        s = seq.req.sampling
        if (not s.greedy or _wants_count_state(s)
                or getattr(s, "logit_bias", None)
                or seq.req.output.logprobs is not None):
            return []
        # clamp the draft so even a full accept (K drafts + bonus) stays
        # inside the row's token budget and the warmed grid capacity
        k = min(self.ecfg.spec_tokens,
                seq.max_new() - seq.generated - 1,
                self.cap_tokens - len(seq.tokens) - 1)
        if k <= 0:
            return []
        return propose_ngram_draft(seq.tokens, k, self.ecfg.spec_ngram_max,
                                   self.ecfg.spec_ngram_min)

    @_phased("dispatch_window")
    def _decode_step_spec(self, batch: List[Sequence],
                          drafts: Dict[int, List[int]]) -> None:
        """One batched multi-token verify: each row's input is [pending
        decode token, draft...], every input's KV scatters into its page
        slot during the forward, and the vectorized greedy accept-mask
        keeps the longest matching draft prefix plus the bonus token.

        Rejected drafts leave junk KV past each row's accepted extent.
        That is safe by the engine's standing invariants: causal masking
        hides positions beyond any query's own position, a slot is
        rewritten when its position's REAL token becomes the decode
        input (before anything attends to it), and page commits only
        ever publish positions strictly behind the newest token."""
        K = self.ecfg.spec_tokens
        # page coverage for every potential write this step (positions
        # through len(tokens)-1+K) plus the next pending token's slot
        self._grow_or_preempt(batch, K + 1)
        batch = [s for s in batch
                 if s.finished is None and not s.context.stopped]
        if not batch:
            return
        B = self.ecfg.bucket_batch(len(batch))
        P = self.ecfg.bucket_pages(max(len(s.pages) for s in batch))
        T = K + 1
        ps = self.ecfg.page_size
        tokens = np.zeros((B, T), np.int32)
        positions = np.full((B, T), -1, np.int32)
        table = np.zeros((B, P), np.int32)
        slots = np.full((B, T), DROP_SLOT, np.int32)
        draft_arr = np.zeros((B, K), np.int32)
        draft_len = np.zeros(B, np.int32)
        for i, seq in enumerate(batch):
            d = drafts[id(seq)][:K]
            n = len(d)
            pos0 = len(seq.tokens) - 1  # position of the pending token
            tokens[i, :n + 1] = [seq.last_token] + d
            pr = np.arange(pos0, pos0 + n + 1)
            positions[i, :n + 1] = pr
            table[i, :len(seq.pages)] = seq.pages
            pages = np.asarray(seq.pages, np.int64)
            slots[i, :n + 1] = pages[pr // ps] * ps + pr % ps
            draft_arr[i, :n] = d
            draft_len[i] = n
        logits, self.kv_k, self.kv_v = self.verify_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(positions),
            self.kv_k, self.kv_v, jnp.asarray(table), jnp.asarray(slots))
        out_d, acc_d = verify_greedy_draft(
            logits, jnp.asarray(draft_arr), jnp.asarray(draft_len))
        self._account_dispatch(batch)
        with self.profiler.phase("readback_window"):
            out = np.asarray(out_d)  # host sync — the spec arm is synchronous
            acc = np.asarray(acc_d)
        self.steps += 1
        self.spec_steps += 1
        step_accepted = step_drafted = 0
        with self.profiler.phase("process_window"):
            for i, seq in enumerate(batch):
                accepted = int(acc[i])
                self.spec_draft_tokens_total += int(draft_len[i])
                self.spec_accepted_tokens_total += accepted
                step_drafted += int(draft_len[i])
                step_accepted += accepted
                for j in range(accepted + 1):
                    if seq.finished is not None or seq.context.stopped:
                        break  # tokens past an accepted stop are discarded
                    self._append_token(seq, int(out[i, j]))
                    self.decode_tokens_total += 1
        self.step_timeline.add(
            "spec_verify", batch=len(batch), drafted=step_drafted,
            accepted=step_accepted,
            occupancy=len(self.running) + len(self.prefilling),
            waiting=len(self.waiting))

    @_phased("dispatch_window")
    def _dispatch_decode_window(self, batch: Optional[List[Sequence]] = None
                                ) -> Optional[_PendingWindow]:
        """Enqueue the next fused K-step decode window WITHOUT reading
        back. Rows carried over from the in-flight window take their
        (token, position, done, step, budget) state from the on-device
        carry — the host's lagging view never enters the feedback loop —
        while newly admitted rows are seeded from host state. For a model
        that generates by blocks the carry's token is two blocks a row
        ([B, 2L]: the pending block, whose K/V the window's first
        forward makes final, beside the open one, -1 = masked) and its
        position the open block's start: a carried row starts a fresh
        block, a host-seeded one the block its tail opens. ``batch``
        restricts the window to a subset of running rows (the spec-decode
        fallback arm, which has already swept cancellations)."""
        K = self.ecfg.decode_steps
        if batch is None:
            for seq in list(self.running):
                if seq.context.stopped:
                    self._terminate(seq, _cancel_reason(seq.context))
            batch = [s for s in self.running if s.finished is None]
        else:
            batch = [s for s in batch if s.finished is None
                     and not s.context.stopped]
        # submit_prefilled can push running past max_batch; overflow rows
        # simply wait a round (arrays below are sized ≤ max_batch)
        batch = batch[: self.ecfg.max_batch]
        if not batch:
            return None
        # grow pages to cover this window AND the in-flight one (device
        # positions can lead host state by up to K tokens; a window of
        # blocks writes whole blocks from the host's last whole block on,
        # which is no further)
        self._grow_or_preempt(batch, 2 * K)
        # the flush inside _grow_or_preempt may have finished rows
        batch = [s for s in batch
                 if s.finished is None and not s.context.stopped]
        if not batch:
            return None

        prev = self._pending  # None if _grow_or_preempt flushed
        # sampling penalties need ACCURATE host token lists (counts are
        # rebuilt from seq.tokens each dispatch): land the in-flight
        # window first, trading the pipelining overlap away only for
        # batches that actually use penalties
        if prev is not None and any(_wants_count_state(s.req.sampling)
                                    for s in batch):
            self._process_window(prev)
            prev = None
            # the readback may have finished rows (EOS/length) and freed
            # their pages — dispatching them would scatter into page 0
            batch = [s for s in batch
                     if s.finished is None and not s.context.stopped]
            if not batch:
                return None
        B = self.ecfg.bucket_batch(len(batch))
        P = self.ecfg.bucket_pages(max(len(s.pages) for s in batch))
        E = self.ecfg.max_eos_ids
        # while the batch composition (rows, page counts, bucket shape) is
        # unchanged, the page table, stop table and sampler params are
        # bit-identical — reuse last dispatch's device arrays instead of
        # rebuilding + re-uploading them. The key holds the Sequence
        # objects themselves (identity compare), so no stale hit is
        # possible. NOTE: a hit also freezes the build-time random seeds
        # of UNSEEDED sampled rows for the cached span.
        key = (B, P, list(batch), [len(s.pages) for s in batch],
               [(s.wfirst, len(s.wpages)) for s in batch])
        cached = self._samp_cache
        if cached is not None and cached[0] == key:
            sb, (d_table, d_temp, d_topk, d_topp, d_seeds,
                 d_eos, d_sslots) = cached[1], cached[2]
        else:
            table = np.zeros((B, P), np.int32)
            eos = np.full((B, E), -1, np.int32)
            # a row's state slot is fixed from admission to release, and
            # a release changes the batch: safe under the key above
            sslots = self._row_slots(batch, B, ((s, 0, 0) for s in batch))
            for i, seq in enumerate(batch):
                table[i, :len(seq.pages)] = seq.pages
                ids: List[int] = []
                if not seq.req.stop.ignore_eos:
                    ids.extend(seq.req.eos_token_ids or [])
                ids.extend(seq.req.stop.stop_token_ids or [])
                if ids:
                    eos[i, :min(len(ids), E)] = ids[:E]
            sb = SamplingBatch.build([s.req.sampling for s in batch], B)
            d_table, d_eos = jnp.asarray(table), jnp.asarray(eos)
            d_temp = jnp.asarray(sb.temperature)
            d_topk = jnp.asarray(sb.top_k)
            d_topp = jnp.asarray(sb.top_p)
            d_seeds = jnp.asarray(sb.seeds)
            d_sslots = (None if sslots is None else
                        jax.tree_util.tree_map(jnp.asarray, sslots))
            self._samp_cache = (key, sb, (d_table, d_temp, d_topk, d_topp,
                                          d_seeds, d_eos, d_sslots))
        from_carry = np.zeros(B, bool)
        src = np.zeros(B, np.int32)
        ntok = self._blank_tokens(B)
        npos = np.full(B, -1, np.int32)
        nsteps = np.zeros(B, np.int32)
        nrem = np.ones(B, np.int32)
        for i, seq in enumerate(batch):
            if prev is not None and id(seq) in prev.index:
                from_carry[i] = True
                src[i] = prev.index[id(seq)]
            else:
                if self.block > 1:
                    # the row's next block: what lies past its whole
                    # blocks is final from the start, the rest masked.
                    # Before it the last whole block, if a window made
                    # it: its K/V are not in the pool yet (_kv_extent)
                    L = self.block
                    npos[i] = whole = seq.prefill_extent
                    if self._kv_extent(seq, len(seq.tokens)) < whole:
                        ntok[i, :L] = seq.tokens[whole - L:whole]
                    tail = seq.tokens[whole:]
                    ntok[i, L:L + len(tail)] = tail
                else:
                    ntok[i] = seq.last_token
                    npos[i] = len(seq.tokens) - 1
                nsteps[i] = seq.generated
                nrem[i] = max(min(seq.max_new() - seq.generated,
                                  self.cap_tokens - len(seq.tokens)), 1)
        if prev is not None:
            tok, pos, done, steps, rem = _merge_carry(
                *prev.res.carry, jnp.asarray(src), jnp.asarray(from_carry),
                jnp.asarray(ntok), jnp.asarray(npos), jnp.asarray(nsteps),
                jnp.asarray(nrem))
        else:
            tok, pos = jnp.asarray(ntok), jnp.asarray(npos)
            done = jnp.zeros(B, bool)
            steps, rem = jnp.asarray(nsteps), jnp.asarray(nrem)
        pen = self._penalty_args(batch, sb, B)
        topn = (self.ecfg.max_top_logprobs
                if self._wants_logprobs(batch) else 0)
        res = self._take_window(self.decode_multi_fn(
            self.params, tok, pos, done, steps, rem, self.kv_k, self.kv_v,
            d_table, d_temp, d_topk, d_topp, d_seeds, d_eos, pen,
            *self._state_args(d_sslots), k_steps=K, logprobs_topn=topn),
            topn)
        self._account_dispatch(batch)
        self._count_decode_slots(batch, B, K)
        self.steps += 1
        pend = _PendingWindow(batch=list(batch), res=res,
                              index={id(s): i for i, s in enumerate(batch)})
        self._inflight.append(pend)
        return pend

    def _process_window(self, pend: _PendingWindow) -> None:
        """Read back a dispatched window's tokens (the only host sync in
        the decode loop — overlapped with the NEXT window's compute) and
        apply host-side bookkeeping: emission, stop conditions, prefix
        commits. Host stop checks mirror the device masking, so they agree
        except for >max_eos_ids stop lists (host wins, device lags)."""
        if pend.processed:
            return
        pend.processed = True
        with self.profiler.phase("readback_window"):
            # the step thread blocked on the device until this window
            # (and whatever was queued ahead of it) has run
            res = pend.res
            toks = np.asarray(res.toks)
            aux = (tuple(np.asarray(a) for a in res.aux)
                   if res.aux is not None else None)
            # outputs of the same program as toks — ready the moment
            # toks is, so these reads add no extra device sync. carry is
            # never donated (warmup's merge-combo loop reuses one), so
            # reading done here is safe even with the next window in
            # flight.
            counts = np.asarray(res.emitted)
            done = np.asarray(res.carry[2])
            info = None if res.counts is None else np.asarray(res.counts)
        if info is not None and self.block == 1:
            for k, v in zip(self.window_counts, info):
                self.window_counts[k] += int(v)
        elif info is not None:
            summed = dict(zip(_BLOCK_WINDOW_COUNTS,
                              info[:len(pend.batch)].sum(axis=0)))
            for k, v in summed.items():
                self.diffusion[k] += int(v)
            self.moe_grouped_window_forwards_total += (
                self._window_forwards_grouped(
                    info.shape[0], int(summed["blocks"]),
                    int(summed["forwards"])))
            self.diffusion["tokens"] += int(counts[:len(pend.batch)].sum())
        if pend in self._inflight:
            self._inflight.remove(pend)
        if self._pending is pend:
            self._pending = None
        K = toks.shape[1]
        emitted = 0
        with self.profiler.phase("process_window"):
            # pure bookkeeping: emission, stop mirror, page publish
            for i, seq in enumerate(pend.batch):
                if seq.finished is not None:
                    continue
                # a window of blocks returns tokens by position: a row's
                # new ones start past the final positions its first block
                # came with (the host's tokens are current here: windows
                # are read back in order)
                off = len(seq.tokens) % self.block
                if not seq.context.stopped \
                        and self._device_stops_complete(seq):
                    # one chunk per row-window, cut by the device's
                    # emitted count and done flag
                    emitted += self._append_row(
                        seq, toks[i], int(counts[i]), bool(done[i]), aux, i,
                        off)
                    continue
                # token by token, for the rows the device cannot speak
                # for: a stop list wider than max_eos_ids (the host's
                # check wins, the device lags) and cancelled rows
                for j in (range(K) if self.block == 1 else
                          range(off, off + int(counts[i]))):
                    if seq.finished is not None or seq.context.stopped:
                        break  # tokens past EOS/stop are discarded
                    self._append_token(seq, int(toks[i, j]),
                                       lp=self._lp_entry(seq, aux, i, j))
                    self.decode_tokens_total += 1
                    emitted += 1
        self.step_timeline.add(
            "decode_window", batch=len(pend.batch), tokens=emitted,
            occupancy=len(self.running) + len(self.prefilling),
            waiting=len(self.waiting))

    def _count_decode_slots(self, batch: List[Sequence], B: int,
                            K: int) -> None:
        """Fill counters of one decode dispatch: live rows x K steps
        against the B x K slots of the bucket that ``_pick`` chose (K
        tokens a row a window either way: K steps of one token, or K / L
        blocks of L)."""
        self.decode_rows_total += len(batch) * K
        self.decode_slots_total += B * K
        if self.block == 1:
            self.moe_grouped_window_forwards_total += (
                len(batch) * K * moe_kernel_takes(
                    self.cfg, self.params, self.mesh, B))
        self.decode_windows_total += 1
        self.decode_windows_sampled_total += any(
            not s.req.sampling.greedy for s in batch)
        if self.state is not None:
            self.state_slots_held_total += (self.ecfg.max_batch
                                            - len(self._state_free))
            self.state_slots_seen_total += self.ecfg.max_batch
        if self.wpm is not None:
            self.kv_window_pages_held_total += self.wpm.held
            self.kv_window_pages_seen_total += self.wpm.capacity
            self.decode_row_steps_total += len(batch) * K
            # row-steps whose query has positions behind its window (the
            # host's view of the row at dispatch: the device may be a
            # window ahead, never behind)
            self.decode_row_steps_past_window_total += K * sum(
                len(s.tokens) > self.wpm.window for s in batch)

    def _window_forwards_grouped(self, B: int, blocks: int,
                                 forwards: int) -> int:
        """Of the ``forwards`` the live rows of a block window of ``B``
        rows went through, those whose program ran the routed experts as
        the kernel: a block's first forward is two blocks wide (one a
        block a row: ``blocks``), the others one, and each kind asks the
        shape rule at its own rows (``moe_kernel_takes``)."""
        def takes(blocks_wide: int) -> bool:
            return moe_kernel_takes(self.cfg, self.params, self.mesh,
                                    B * blocks_wide * self.block)

        first = min(blocks, forwards)
        return first * takes(2) + (forwards - first) * takes(1)

    def _device_stops_complete(self, seq: Sequence) -> bool:
        """True when the row's full stop-id set fit the on-device stop
        table, so the window's done flag / emitted count are authoritative
        and the host can bulk-append without per-token stop checks."""
        return seq.dev_stop_count <= self.ecfg.max_eos_ids

    def _append_row(self, seq: Sequence, row: np.ndarray, n: int,
                    dev_done: bool, aux, i: int, off: int = 0) -> int:
        """Bulk-append one window row using the device's valid-token
        count: ONE EngineOutput (one cross-thread wakeup) for the whole
        window instead of one per token, one page-publish sweep, and the
        finish decision read off the device's done flag. Token identity
        with the per-token path is pinned by test."""
        n = min(n, row.shape[0] - off)
        if n <= 0:
            if dev_done and seq.finished is None:
                # row entered the window already frozen but never got its
                # host-side finish (defensive: unreachable under FIFO
                # window processing) — terminate so it can't re-dispatch
                self._terminate(seq, FINISH_LENGTH)
            return 0
        ids = [int(t) for t in row[off:off + n]]
        prev_filled = len(seq.tokens)
        seq.tokens.extend(ids)
        seq.last_token = ids[-1]
        seq.generated += n
        self.decode_tokens_total += n
        lps = tops = None
        if aux is not None and seq.req.output.logprobs is not None:
            entries = [self._lp_entry(seq, aux, i, off + j)
                       for j in range(n)]
            lps = [e[0] for e in entries]
            tops = [e[1] for e in entries]
        self._emit(seq, EngineOutput(
            token_ids=ids, prompt_tokens=seq.num_prompt,
            logprobs=lps, top_logprobs=tops))
        # prefix-cache publish when the row crossed a page boundary (same
        # len-1 publishable-extent rule as _append_token; commit_chain
        # dedups blocks already published)
        self._publish(seq, prev_filled)
        if dev_done:
            last = ids[-1]
            hit = last in seq.stop_set
            self._terminate(seq, FINISH_EOS if hit else FINISH_LENGTH)
        elif (seq.generated >= seq.max_new()
              or len(seq.tokens) >= self.cap_tokens):
            # host caps the device couldn't see at seed time (defensive
            # mirror of _append_token's length cut)
            self._terminate(seq, FINISH_LENGTH)
        return n

    # -------------------------------------------- deferred page reclamation

    def _release_or_defer(self, seq: Sequence) -> None:
        """Release a sequence's pages unless an in-flight window still
        writes them (freeing early could hand a page to a new owner while
        the old window's scatter lands — corrupting prefix-cache pages).
        The pending finish emission rides with the release."""
        if any(id(seq) in w.index for w in self._inflight):
            if seq not in self._deferred_free:
                self._deferred_free.append(seq)
        else:
            self._release(seq)
            self._emit_finish(seq)

    def _drain_deferred(self) -> None:
        still: List[Sequence] = []
        for seq in self._deferred_free:
            if any(id(seq) in w.index for w in self._inflight):
                still.append(seq)
            else:
                self._release(seq)
                self._emit_finish(seq)
        self._deferred_free = still

    # ------------------------------------------------------------- helpers

    def _penalty_state(self, seqs: List[Sequence], pad_to: int):
        """(counts [B,V] int32 of GENERATED tokens, presence [B,V] int8
        over the full context) rebuilt from the host token lists — the
        stateless-per-dispatch form (slots migrate between sequences, so
        device-resident histograms would need per-dispatch resharding
        anyway). Only ever built for batches that use penalties."""
        V = self.cfg.vocab_size
        counts = np.zeros((pad_to, V), np.int32)
        presence = np.zeros((pad_to, V), np.int8)
        for i, s in enumerate(seqs):
            # host-list → host-array construction, not a device sync
            gen = np.asarray(s.tokens[s.num_prompt:], np.int64)  # dynalint: disable=jax-host-sync-in-hot-path
            if gen.size:
                counts[i] = np.bincount(gen, minlength=V)[:V]
            ctx = np.asarray(s.tokens, np.int64)  # dynalint: disable=jax-host-sync-in-hot-path
            presence[i, ctx[ctx < V]] = 1
        return (jnp.asarray(counts), jnp.asarray(presence))

    def _penalty_args(self, seqs: List[Sequence], sb: SamplingBatch,
                      pad_to: int):
        """The (counts, presence, rep, freq, pres[, bias]) tuple the
        samplers take, or None for penalty/bias-free batches (the only
        warmed path). The bias element is appended only when some row
        sets logit_bias — its own treedef, so bias-free penalty batches
        reuse the 5-tuple program."""
        biased = [getattr(s.req.sampling, "logit_bias", None)
                  for s in seqs]
        if not sb.has_penalties and not any(biased):
            return None
        if sb.has_penalties:
            state = self._penalty_state(seqs, pad_to)
        else:
            # bias-only: counts/presence are mathematically unused
            # (rep=1, freq=pres=0 broadcast them away) — [B, 1]
            # placeholders instead of 2x [B, V] arrays per dispatch
            state = (jnp.zeros((pad_to, 1), jnp.int32),
                     jnp.zeros((pad_to, 1), jnp.int8))
        out = state + (jnp.asarray(sb.rep), jnp.asarray(sb.freq),
                       jnp.asarray(sb.pres))
        if any(biased):
            V = self.cfg.vocab_size
            rows = [self._bias_row(s) if b else None
                    for s, b in zip(seqs, biased)]
            bias = np.zeros((pad_to, V), np.float32)
            for i, r in enumerate(rows):
                if r is not None:
                    bias[i] = r
            out = out + (jnp.asarray(bias),)
        return out

    def _bias_row(self, seq: Sequence) -> np.ndarray:
        """Per-sequence dense logit_bias row, built once and cached on
        the Sequence (the dict is immutable per request; only the batch
        assembly runs per dispatch)."""
        row = getattr(seq, "_bias_row", None)
        if row is None:
            V = self.cfg.vocab_size
            row = np.zeros(V, np.float32)
            bias_map = seq.req.sampling.logit_bias
            if bias_map:
                for t, v in bias_map.items():
                    if 0 <= int(t) < V:
                        row[int(t)] = v
            seq._bias_row = row
        return row

    def _sample_device(self, seqs: List[Sequence], logits) -> jax.Array:
        """On-device token draw, no readback. logits: [B_padded, V]
        (bucketed); pads sampling params to match so every distinct batch
        bucket compiles exactly once."""
        pad_to = logits.shape[0]
        sb = SamplingBatch.build([s.req.sampling for s in seqs], pad_to)
        steps = np.zeros(pad_to, np.int32)
        steps[:len(seqs)] = [s.generated for s in seqs]
        pen = self._penalty_args(seqs, sb, pad_to)
        toks = sample_tokens(logits, jnp.asarray(sb.temperature),
                             jnp.asarray(sb.top_k), jnp.asarray(sb.top_p),
                             jnp.asarray(sb.seeds), jnp.asarray(steps),
                             max_top_k=self.ecfg.max_top_k, penalties=pen)
        aux = None
        if self._wants_logprobs(seqs):
            aux = logprob_aux(jnp.asarray(logits), toks,
                              self.ecfg.max_top_logprobs)
        return toks, aux

    def _wants_logprobs(self, seqs: List[Sequence]) -> bool:
        return any(s.req.output.logprobs is not None for s in seqs)

    def _lp_entry(self, seq: Sequence, aux, i: int, j: Optional[int] = None):
        """(logprob, {token_id: logprob, ...}) for row i (step j in a
        window) — None unless this sequence asked for logprobs."""
        if aux is None or seq.req.output.logprobs is None:
            return None
        lp, tv, ti = aux
        if j is None:
            chosen, vals, ids = lp[i], tv[i], ti[i]
        else:
            chosen, vals, ids = lp[i, j], tv[i, j], ti[i, j]
        topn = min(int(seq.req.output.logprobs), len(ids))
        top = {int(t): float(v) for t, v in zip(ids[:topn], vals[:topn])}
        return float(chosen), top

    def _append_token(self, seq: Sequence, tok: int, lp=None) -> None:
        """Record a generated token: emit, check termination, commit pages."""
        seq.tokens.append(tok)
        seq.last_token = tok
        seq.generated += 1
        eos = tok in seq.stop_set
        self._emit(seq, EngineOutput(
            token_ids=[tok], prompt_tokens=seq.num_prompt,
            logprobs=[lp[0]] if lp is not None else None,
            top_logprobs=[lp[1]] if lp is not None else None))
        # prefix-cache publish: commit a page only once every slot in it
        # holds WRITTEN KV. The newest token's KV is written when it next
        # serves as a decode input — which never happens for a terminal
        # token under on-device stop freezing — so the publishable extent
        # is len(tokens) - 1 positions, one token past the page boundary.
        # Committing at filled % ps == 0 (the pre-pipelining rule) would
        # publish a page whose last slot is junk and poison later hits.
        # (multi-token publish: commit() dedups the already-published
        # blocks, and speculative accepts can append several tokens
        # between boundary checks, so _publish commits everything the
        # extent covers, not just the newest block)
        self._publish(seq, len(seq.tokens) - 1)
        if eos:
            self._terminate(seq, FINISH_EOS)
        elif (seq.generated >= seq.max_new()
              or len(seq.tokens) >= self.cap_tokens):
            # the capacity cut mirrors the device-side `remaining` clamp:
            # the device froze this row at the grid boundary, so stop
            # appending its (repeated) trailing tokens
            self._terminate(seq, FINISH_LENGTH)

    def _kv_extent(self, seq: Sequence, n_tokens: int) -> int:
        """Positions of a row of n_tokens tokens whose K/V in the pool is
        final: all but the newest token's (written when it next serves
        as a decode input), or for a model that generates by blocks what
        prefill wrote (``seq.computed``: whole blocks) and of the whole
        blocks a window made all but the last: a block's K/V are made
        final by the first forward of the block AFTER it
        (llama._make_block_window_fn), in the next window for a
        window's last block, so the pool stands one block behind the
        tokens read back. A block cut by a stop id or the budget is
        never committed, nor is the last block of a row that finishes."""
        if self.block > 1:
            return max(_whole_blocks(n_tokens, self.block) - self.block,
                       seq.computed)
        return n_tokens - 1

    def _publish(self, seq: Sequence, prev_filled: int) -> None:
        """Publish to the prefix cache the pages that the tokens appended
        since ``prev_filled`` completed: those the final extent now
        covers and did not before."""
        ps = self.ecfg.page_size
        extent = self._kv_extent(seq, len(seq.tokens))
        if extent // ps > max(self._kv_extent(seq, prev_filled), 0) // ps:
            self.pm.commit_chain(seq.pages, seq.tokens, extent,
                                 chain=self._chain(seq))

    def _terminate(self, seq: Sequence, reason: str) -> None:
        """Terminal-state a sequence. The finished flag is set NOW (no
        more tokens append); the finish_reason EMISSION rides with the
        page release, which defers until any in-flight window containing
        the row completes — so by the time a client sees finish, the
        engine's capacity accounting already reflects the freed pages."""
        if seq in self.running:
            self.running.remove(seq)
        if seq.finished is None:
            # proto: request.lifecycle prefill|decode->finished|timeout|cancelled
            seq.finished = reason
        self._release_or_defer(seq)

    def _chain(self, seq: Sequence) -> List[int]:
        """Full-block hashes of seq.tokens via the per-sequence
        incremental cache (created on first use); none where nothing
        is ever matched or published (a model with recurrent state), so
        admission and the page-boundary publishes hash nothing."""
        if not self.pm.prefix_reuse:
            return []
        if seq.hash_cache is None:
            seq.hash_cache = ChainHashCache(self.ecfg.page_size)
        return seq.hash_cache.extend(seq.tokens)

    def _commit_full_pages(self, seq: Sequence) -> None:
        self.pm.commit_chain(seq.pages, seq.tokens, seq.prefill_extent,
                             chain=self._chain(seq))

    def _release(self, seq: Sequence) -> None:
        if seq.hold_pages:
            return  # disagg prefill-only: caller extracts, then releases
        if seq.pages:
            self.pm.release_sequence(seq.pages)
            seq.pages = []
        if self.wpm is not None:
            # both pools, under the same rule as the state slot below
            self.wpm.release(seq.wpages)
            self.wpm.unreserve(seq.wreserved)
            seq.wfirst = seq.wreserved = 0
        if seq.state_slot is not None:
            # reached only once no window in flight lists the row
            # (_release_or_defer; preemption flushes first), so the next
            # owner's programs are all dispatched after the last one that
            # wrote this slot for the old row
            self._state_free.append(seq.state_slot)
            seq.state_slot = None

    def _finish(self, seq: Sequence, reason: str) -> None:
        if seq.finished is None:
            # proto: request.lifecycle admitted->finished|timeout|cancelled
            seq.finished = reason
        self._emit_finish(seq)

    def _account_dispatch(self, batch: List[Sequence]) -> None:
        """dynaprof attribution: each dispatch distributes exactly 1.0
        step share across its batch (occupancy weighting), so the sum of
        per-request shares equals batch_dispatches_total — the
        conservation invariant tests/test_profiling.py pins. Host-side
        counter updates only."""
        share = 1.0 / len(batch)
        for seq in batch:
            seq.dispatch_share += share
            seq.dispatches += 1
            if len(seq.pages) > seq.max_pages:
                seq.max_pages = len(seq.pages)
        self.batch_dispatches_total += 1

    def _attribution(self, seq: Sequence) -> dict:
        """Per-request cost block: where this request's share of the
        engine's time and memory went."""
        ps = self.ecfg.page_size
        prompt_blocks = (seq.num_prompt + ps - 1) // ps
        return {
            "queue_wait_ms": round(seq.queue_wait_s * 1000.0, 3),
            # the TTFT split (None where the request never got there):
            # admission -> first prefill dispatch -> first emission, and
            # first -> last emission
            "prefill_wait_ms": _span_ms(seq.t_admit, seq.t_first_dispatch),
            "first_token_ms": _span_ms(seq.t_first_dispatch,
                                       seq.t_first_token),
            "decode_ms": _span_ms(seq.t_first_token, seq.last_emit_t),
            "device_step_share": round(seq.dispatch_share, 6),
            "dispatches": seq.dispatches,
            "prompt_tokens": seq.num_prompt,
            "prefix_hit_tokens": seq.prefix_hit,
            # dynacache prefix split: device_hit + host_restored + the
            # implied fresh remainder sum to prompt_blocks (conservation,
            # pinned by tests/test_cache_obs.py). router_overlap_blocks
            # is merged in by the frontend's KvRouter when the finish
            # cost block passes its attribution listener.
            "prompt_blocks": prompt_blocks,
            "device_hit_blocks": seq.device_hit_blocks,
            "host_restored_blocks": seq.host_restored_blocks,
            "restore_wait_ms": round(seq.restore_wait_s * 1000.0, 3),
            "decode_tokens": seq.generated,
            "kv_pages_peak": seq.max_pages,
            "kv_bytes_peak": seq.max_pages * self._page_bytes,
            "finish_reason": seq.finished,
            # dynashard: which replica/submesh served this request —
            # /v1/traces/{rid} and the usage cost extension surface these
            "replica": self.worker_label,
            "mesh_shape": self.mesh_shape,
        }

    def _emit_finish(self, seq: Sequence) -> None:
        if seq.finish_emitted or seq.finished is None:
            return
        seq.finish_emitted = True
        # dynaslo e2e: arrival → finish emission (cancel/error finishes
        # included — a timed-out request IS a latency observation)
        self.latency.observe("e2e", time.monotonic() - seq.arrival)
        if seq.trace_ctx is not None:
            self._record_request_spans(seq)
        cost = self._attribution(seq)
        profiling.record_attribution(seq.context.id, cost)
        self._emit(seq, EngineOutput(token_ids=[], finish_reason=seq.finished,
                                     prompt_tokens=seq.num_prompt,
                                     completion_tokens=seq.generated,
                                     cost=cost))

    def _record_request_spans(self, seq: Sequence) -> None:
        """The request's path through the engine as child spans of the
        span that was ambient in generate(): /v1/traces/{request_id}
        lists them under ``stages`` and the span-end listener feeds the
        stage histograms."""
        tracer = tracing.get_tracer()
        marks = (("engine.queue", seq.arrival, seq.t_admit),
                 ("engine.prefill_wait", seq.t_admit, seq.t_first_dispatch),
                 ("engine.prefill", seq.t_first_dispatch, seq.t_first_token),
                 ("engine.decode", seq.t_first_token, seq.last_emit_t))
        for name, start, end in marks:
            if start is not None and end is not None:
                tracer.record_span(name, end - start, start=start,
                                   parent=seq.trace_ctx)

    def _emit(self, seq: Sequence, out: EngineOutput) -> None:
        if out.token_ids:
            # dynaslo: first token-bearing emission is TTFT; later gaps
            # are per-token ITL (an n-token window emission records n
            # per-token gaps of gap/n, so window size never skews the
            # distribution). Host clock reads only. The same stamp rides
            # the output (never the wire) to the frontend, which sums
            # `now` -> its chunk's resp.write returned as
            # emit_to_wire_seconds_total.
            now = out.emit_t = time.monotonic()
            if seq.last_emit_t is None:
                self.latency.observe("ttft", now - seq.arrival)
                seq.t_first_token = now
                if seq.t_first_dispatch is not None:
                    # the TTFT split of a request prefilled here; with
                    # queue_wait these add up to engine_ttft exactly
                    self.prefill_wait_seconds_total += (
                        seq.t_first_dispatch - seq.t_admit)
                    self.first_token_seconds_total += (
                        now - seq.t_first_dispatch)
                    self.engine_ttft_seconds_total += now - seq.arrival
                    self.first_tokens_total += 1
            else:
                n = len(out.token_ids)
                self.latency.observe("itl", (now - seq.last_emit_t) / n, n)
            seq.last_emit_t = now
        # steps run in the executor thread; asyncio.Queue is not thread-safe,
        # so route puts through the loop. Thread-id compare instead of an
        # asyncio.get_running_loop() probe: off-loop the probe RAISES
        # RuntimeError per emission (= per token on the decode path)
        tid = self._aio_loop_tid
        if tid is None or threading.get_ident() == tid:
            seq.out.put_nowait(out)
        else:
            self._aio_loop.call_soon_threadsafe(seq.out.put_nowait, out)

    def _reap(self) -> None:
        """Drop finished sequences that linger in running (safety net)."""
        self.running = [s for s in self.running if s.finished is None]

    # ------------------------------------------------- disaggregation plane
    # Engine-side primitives for prefill/decode disaggregation (reference
    # vllm_v0.7.2-dynamo-kv-disagg-patch: remote_prefill.py
    # RemotePrefillRequest staging + DynamoNixlConnector block reads/writes).
    # On TPU the RDMA path becomes: gather pages → host bytes → TCP/DCN →
    # donated scatter back into the destination pool (llm/disagg/transfer.py);
    # same-process transfers skip the host round-trip entirely.

    async def reserve_remote(self, token_ids: List[int]
                             ) -> Optional["RemoteReservation"]:
        """Decode-side page reservation for a remote prefill: claims pages
        covering the prompt (reusing the longest cached prefix) without
        admitting a sequence. Returns None when the pool is full."""
        loop = asyncio.get_running_loop()
        if len(token_ids) >= self.cap_tokens:
            # same warmed-grid clamp as _admit: a reservation past the
            # largest page bucket would force a mid-serving compile when
            # the sequence enters decode via submit_prefilled
            return None

        def _do():
            with self._pm_lock:
                alloc = self.pm.allocate_sequence(token_ids)
            if alloc is None:
                return None
            if alloc.restores:
                # the reservation's host-tier hits must be resident before
                # submit_prefilled starts decoding on them — no scheduler
                # drain is guaranteed to run in between, so the chunked
                # path cannot be relied on here
                self._drain_kv_tier(full=True)
            return RemoteReservation(pages=alloc[0], cached_tokens=alloc[1],
                                     page_size=self.ecfg.page_size)

        return await loop.run_in_executor(self._exec, _do)

    async def release_pages(self, pages: List[int]) -> None:
        """Return pages claimed by reserve_remote()/prefill_only()."""
        loop = asyncio.get_running_loop()

        def _do():
            with self._pm_lock:
                self.pm.release_sequence(list(pages))

        await loop.run_in_executor(self._exec, _do)

    async def extract_pages(self, page_ids: List[int], *,
                            drain: bool = True
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Gather KV pages to host memory: returns (k, v) arrays of shape
        [L, n, KV, page_size, hd] (kv-head-major pool layout). Serialized
        with engine steps on the single-worker executor so it never races
        buffer donation. ``drain=False`` skips the host-tier drain — safe
        only for follow-up ranged extracts of a request whose first
        extract already drained (the streaming transfer plane)."""
        loop = asyncio.get_running_loop()

        def _do():
            # restored pages must be resident first (full: the chunked
            # drain could leave some queued)
            if drain:
                self._drain_kv_tier(full=True)
            # pow2-pad the gather so extracts compile O(log n) programs
            # instead of one per distinct page count (dynajit DL015);
            # the D2H readback below is the extract's whole purpose
            npages = len(page_ids)
            idx = jnp.asarray(_pad_pow2(list(page_ids), 0), jnp.int32)
            k = np.asarray(_gather_pages(self.kv_k, idx))  # dynalint: disable=implicit-host-transfer
            v = np.asarray(_gather_pages(self.kv_v, idx))  # dynalint: disable=implicit-host-transfer
            return k[:, :npages], v[:, :npages]

        return await loop.run_in_executor(self._exec, _do)

    async def extract_pages_chunked(self, page_ids: List[int],
                                    chunk_pages: int):
        """Ranged/async extract for the streaming transfer plane: yields
        ``(offset, k, v, seconds)`` per ``chunk_pages``-sized slice of
        ``page_ids``. The device gather + D2H copy for slice i+1 is
        dispatched (``copy_to_host_async``) before slice i's host sync
        completes, so the device→host stage of the next chunk runs under
        whatever the consumer does with the current one (compress, socket
        write). ``seconds`` is the blocking time this chunk cost — the
        extract-stage figure for the transfer breakdown."""
        loop = asyncio.get_running_loop()
        cp = max(int(chunk_pages), 1)
        slices = [page_ids[i:i + cp] for i in range(0, len(page_ids), cp)]

        def _gather(ids, first):
            if first:
                self._drain_kv_tier(full=True)
            # pad the (only-ever-shorter) final slice to the chunk size:
            # every chunk of a stream then shares ONE gather program per
            # chunk_pages value instead of compiling the remainder length
            # mid-serving (dynajit DL015)
            idx = jnp.asarray(list(ids) + [0] * (cp - len(ids)), jnp.int32)
            kg = _gather_pages(self.kv_k, idx)
            vg = _gather_pages(self.kv_v, idx)
            for a in (kg, vg):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            return kg, vg, len(ids)

        def _host(kg, vg, real):
            # the D2H sync IS the extract stage
            k = np.asarray(kg)  # dynalint: disable=implicit-host-transfer
            v = np.asarray(vg)  # dynalint: disable=implicit-host-transfer
            return k[:, :real], v[:, :real]

        if not slices:
            return
        t0 = time.monotonic()
        pending = await loop.run_in_executor(self._exec, _gather,
                                             slices[0], True)
        for i in range(len(slices)):
            nxt = (loop.run_in_executor(self._exec, _gather,
                                        slices[i + 1], False)
                   if i + 1 < len(slices) else None)
            k, v = await loop.run_in_executor(self._exec, _host, *pending)
            dt = time.monotonic() - t0
            yield i * cp, k, v, dt
            t0 = time.monotonic()
            if nxt is not None:
                pending = await nxt

    async def inject_pages(self, page_ids: List[int], k: np.ndarray,
                           v: np.ndarray) -> None:
        """Scatter host KV pages [L, n, KV, page_size, hd] into the pool at
        page_ids (donated jit — in-place on device; the block_copy.cu
        analog for ingest)."""
        loop = asyncio.get_running_loop()

        def _do():
            # evictions queued when these pages were reserved must capture
            # their OLD content before this injection overwrites it
            self._drain_kv_tier(full=True)
            # pow2-pad the scatter (pad target = num_pages → dropped by
            # the donated .at[...].set(mode="drop")) so injects compile
            # O(log n) programs, not one per page count (dynajit DL015)
            pad = _pad_pow2(list(page_ids), self.ecfg.num_pages)
            idx = jnp.asarray(pad, jnp.int32)
            kp = np.zeros((k.shape[0], len(pad) - k.shape[1],
                           *k.shape[2:]), k.dtype)
            vp = np.zeros((v.shape[0], len(pad) - v.shape[1],
                           *v.shape[2:]), v.dtype)
            self.kv_k = _inject_pages(
                self.kv_k, idx,
                jnp.asarray(np.concatenate([k, kp], axis=1)))
            self.kv_v = _inject_pages(
                self.kv_v, idx,
                jnp.asarray(np.concatenate([v, vp], axis=1)))
            jax.block_until_ready(self.kv_k)

        await loop.run_in_executor(self._exec, _do)

    async def prefill_only(self, request: PreprocessedRequest,
                           context: Context) -> Tuple[int, List[int]]:
        """Prefill worker path: compute the prompt's KV + sample the first
        token, holding the pages for extraction. Returns (first_token,
        page_ids); the caller MUST release_pages(page_ids) when done.
        (Reference prefill_worker.py:109-137 — max_tokens=1 generate.)"""
        import copy

        req = copy.copy(request)
        req.stop = copy.copy(request.stop)
        req.stop.max_tokens = 1
        self.start()
        seq = Sequence(req=req, context=context, out=asyncio.Queue(),
                       tokens=list(req.token_ids),
                       num_prompt=len(req.token_ids), hold_pages=True)
        if seq.num_prompt == 0:
            raise ValueError("empty prompt")
        self.waiting.append(seq)
        self._wake.set()
        first: Optional[int] = None
        while True:
            out: EngineOutput = await seq.out.get()
            if out.token_ids:
                first = out.token_ids[0]
            if out.finish_reason is not None:
                break
        if first is None:
            # failed before sampling: nothing to extract, so return the held
            # pages ourselves (hold_pages disabled the engine-side release)
            if seq.pages:
                await self.release_pages(seq.pages)
                seq.pages = []
            raise RuntimeError(f"prefill produced no token "
                               f"({out.finish_reason})")
        return first, seq.pages

    async def submit_prefilled(self, request: PreprocessedRequest,
                               context: Context, pages: List[int],
                               first_token: int) -> Sequence:
        """Decode-side entry after a remote prefill: the reserved pages now
        hold the prompt's KV (injected via inject_pages); enter decode
        directly with the remotely sampled first token already emitted."""
        if not isinstance(request, PreprocessedRequest):
            request = PreprocessedRequest.from_dict(request)
        if len(request.token_ids) >= self.cap_tokens:
            raise ValueError(
                f"prompt length {len(request.token_ids)} exceeds engine "
                f"context capacity {self.cap_tokens} (reserve_remote would "
                f"have refused this reservation)")
        self.start()
        seq = Sequence(req=request, context=context, out=asyncio.Queue(),
                       tokens=list(request.token_ids),
                       num_prompt=len(request.token_ids))
        seq.pages = list(pages)
        seq.computed = seq.num_prompt
        loop = asyncio.get_running_loop()

        def _do():
            self.prompt_tokens_total += seq.num_prompt
            # decode-side hits were claimed by reserve_remote, not here;
            # window the admission with the same zero-hit accounting the
            # lifetime counters use for this path
            self._hit_window.append((0, seq.num_prompt))
            with self._pm_lock:
                self._commit_full_pages(seq)  # prefix-cache publish + events
                self._append_token(seq, int(first_token))

        await loop.run_in_executor(self._exec, _do)
        if seq.finished is None:
            self.running.append(seq)
            self._wake.set()
        return seq


@dataclass
class RemoteReservation:
    """Decode-side pages claimed ahead of a remote prefill."""

    pages: List[int]
    cached_tokens: int  # prompt tokens already covered by the prefix cache
    page_size: int

    @property
    def skip_pages(self) -> int:
        """Leading pages the prefill worker need not transfer (already
        valid on the decode side via prefix-cache hits)."""
        return self.cached_tokens // self.page_size


def _check_block_sizes(cfg: ModelConfig, ecfg: EngineConfig) -> None:
    """The sizes a model that generates by diffusion over blocks
    (cfg.block_length > 1) needs of the engine's configuration."""
    cfg.check_page_size(ecfg.page_size)
    if ecfg.decode_steps < cfg.block_length \
            or ecfg.decode_steps % cfg.block_length:
        raise ValueError(
            f"decode_steps ({ecfg.decode_steps}) must be a multiple of "
            f"block_length ({cfg.block_length}): a window generates whole "
            f"blocks, decode_steps / block_length of them a row")


def _span_ms(start: Optional[float], end: Optional[float]
             ) -> Optional[float]:
    if start is None or end is None:
        return None
    return round((end - start) * 1000.0, 3)


def _wants_count_state(s) -> bool:
    """True when the row needs ACCURATE token counts (the three
    count-driven penalties) — these force the pipelining barrier.
    logit_bias is static per request and needs neither counts nor the
    barrier."""
    return bool((getattr(s, "repetition_penalty", None) or 1.0) != 1.0
                or getattr(s, "frequency_penalty", None)
                or getattr(s, "presence_penalty", None))


@jax.jit
def _merge_carry(c_tok, c_pos, c_done, c_steps, c_rem, src, from_carry,
                 n_tok, n_pos, n_steps, n_rem):
    """Stitch window N+1's inputs: rows continuing from the in-flight
    window gather their state from its device carry (src indexes into the
    previous batch); fresh rows take the host-provided values. Runs as one
    tiny jitted program so no host sync enters the dispatch path."""
    src = jnp.clip(src, 0, c_tok.shape[0] - 1)
    # a block window's token carry is two blocks a row ([B, 2L])
    tok = jnp.where(from_carry if c_tok.ndim == 1 else from_carry[:, None],
                    c_tok[src], n_tok)
    pos = jnp.where(from_carry, c_pos[src], n_pos)
    done = jnp.where(from_carry, c_done[src], False)
    steps = jnp.where(from_carry, c_steps[src], n_steps)
    rem = jnp.where(from_carry, c_rem[src], n_rem)
    return tok, pos, done, steps, rem


@partial(jax.jit, donate_argnums=(0,))
def _inject_pages(pool: jax.Array, idx: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """pool: [L, num_pages, KV, ps, hd]; rows: [L, n, KV, ps, hd].
    Out-of-range idx entries are dropped (padding)."""
    return pool.at[:, idx].set(rows.astype(pool.dtype), mode="drop")


@jax.jit
def _gather_pages(pool: jax.Array, idx: jax.Array) -> jax.Array:
    """pool: [L, num_pages, KV, ps, hd] → [L, n, KV, ps, hd]."""
    return pool[:, idx]


def _pad_pow2(lst: List[int], fill: int) -> List[int]:
    """Pad to the next power of two so batched page copies compile
    O(log n) distinct shapes instead of one per length."""
    n = 1
    while n < len(lst):
        n *= 2
    return list(lst) + [fill] * (n - len(lst))
