"""KV-router wire protocols.

Reference lib/llm/src/kv_router/protocols.rs:18-97: ``ForwardPassMetrics``
(worker load snapshot), ``KvCacheEvent`` (Stored/Removed block updates),
and the hit-rate event emitted per routing decision (scheduler.rs:27-32).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

KV_EVENT_SUBJECT = "kv_events"       # published under <ns>.<component>.
KV_HIT_RATE_SUBJECT = "kv-hit-rate"  # router observability events


@dataclass
class ForwardPassMetrics:
    """Per-worker load snapshot (reference protocols.rs:18-30)."""

    # dynashard replica identity: the engine's stable per-replica label
    # (e.g. "r0") and submesh geometry. The label becomes the `replica`
    # Prometheus label when set — instance ids (lease hex) are unique
    # but change on every restart, so N-replicas-in-one-process dashboards
    # key on this instead (ISSUE 12 satellite: metric identity).
    worker_label: str = ""
    mesh_shape: str = ""
    mesh_devices: int = 1
    # dynaslo: the worker's serving role (prefill|decode|unified). The
    # KV scheduler never routes token requests to a prefill-role worker
    # (disagg prefill capacity is fed from the shared queue, not the
    # router), the planner's P/D rebalance policy counts roles, and the
    # aggregator labels every merged latency histogram with it.
    role: str = "unified"
    # dynaslo: per-role mergeable latency histograms
    # ({role: {ttft|itl|queue_wait|e2e: wire histogram}}) recorded by
    # the worker and MERGED by the metrics aggregator into the first
    # fleet-wide latency quantiles (runtime/slo.py fixed bucket grid:
    # lossless merge, nearest-bucket quantiles).
    latency_hist: dict = field(default_factory=dict)
    # dynarevive graceful drain: 1 while the worker is finishing its
    # in-flight sequences after withdrawing from discovery. Draining ≠
    # dead — the stats plane keeps answering (no breaker opens) and the
    # scheduler simply stops offering this worker new requests.
    draining: int = 0
    request_active_slots: int = 0
    request_total_slots: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0
    # dynacache: the headline hit rate is WINDOWED (recent admissions);
    # the lifetime ratio and the raw token totals ride alongside
    gpu_prefix_cache_hit_rate: float = 0.0
    gpu_prefix_cache_hit_rate_lifetime: float = 0.0
    prefix_hit_tokens_total: int = 0
    prompt_tokens_total: int = 0
    # dynacache lifecycle counters (engine PageManager.cache_stats()):
    # allocation prefix split, eviction fates + block age, host-tier
    # evictions, restore-queue depth and drain latency
    cache_device_hit_blocks_total: int = 0
    cache_host_restored_blocks_total: int = 0
    cache_fresh_blocks_total: int = 0
    cache_evict_offloaded_total: int = 0
    cache_evict_dropped_total: int = 0
    cache_evict_age_seconds_total: float = 0.0
    cache_host_evictions_total: int = 0
    cache_restore_queue_depth: int = 0
    cache_restores_drained_total: int = 0
    cache_restore_wait_seconds_total: float = 0.0
    # dynaheat restore batching: drained batches + pages per batch (mean
    # batch size = pages/batches — the coalescing win)
    cache_restore_batches_total: int = 0
    cache_restore_batch_pages_total: int = 0
    # self-speculative decoding observability (engine/spec_decode.py):
    # accepted/drafted tokens, and accepted drafts per verify step
    spec_decode_acceptance_rate: float = 0.0
    spec_decode_mean_accepted_len: float = 0.0
    # compile fence (engine/jit_fence.py): XLA compiles observed after
    # warmup() — any nonzero value means a worker broke the zero-compile
    # serving invariant and stalled its in-flight requests
    post_warmup_compiles_total: int = 0
    # disaggregation transfer plane (llm/disagg/transfer.py streaming
    # chunk pipeline): decode-side ingest volume/time + the remote-prefill
    # wait the decode engine accumulates (enqueue → KV committed)
    kv_transfer_bytes_total: int = 0
    kv_transfer_chunks_total: int = 0
    kv_transfer_inject_seconds_total: float = 0.0
    kv_transfer_streams_failed_total: int = 0
    remote_prefill_wait_seconds_total: float = 0.0
    # engine internals that existed in stats() but never reached
    # Prometheus before dynaprof: admission-queue wait, free/cached HBM
    # pages, the host offload tier, long-context prefills
    queue_wait_seconds_total: float = 0.0
    kv_free_blocks: int = 0
    kv_cached_blocks: int = 0
    host_free_blocks: int = 0
    host_cache_usage_perc: float = 0.0
    host_offload_pages_total: int = 0
    host_restore_pages_total: int = 0
    long_prefills_total: int = 0
    # dynaprof (runtime/profiling.py): event-loop lag percentiles, and
    # the attribution conservation counter
    loop_lag_p50_seconds: float = 0.0
    loop_lag_p99_seconds: float = 0.0
    batch_dispatches_total: int = 0
    # the window layers' K/V pool of a model with a pool a kind of layer
    # (engine/kv_manager.py WindowPagePool; all 0 for any other model):
    # its fill summed at every decode dispatch (held / seen), pages
    # handed to rows and pages given back while the row ran, and the
    # decode row-steps whose query had positions behind its window
    kv_window_pages_held_total: int = 0
    kv_window_pages_seen_total: int = 0
    kv_window_pages_allocated_total: int = 0
    kv_window_pages_released_total: int = 0
    decode_row_steps_total: int = 0
    decode_row_steps_past_window_total: int = 0
    # a row's prefill chunks, and those that start past position 0 (from
    # what the row's earlier chunks left: pages, and the recurrent state
    # in its slot where the model keeps one)
    prefill_row_chunks_total: int = 0
    prefill_row_chunks_carried_total: int = 0

    def to_dict(self) -> dict:
        return dict(self.__dict__)

    @classmethod
    def from_dict(cls, d: dict) -> "ForwardPassMetrics":
        known = {f for f in cls.__dataclass_fields__}  # type: ignore[attr-defined]
        return cls(**{k: v for k, v in d.items() if k in known})


# Engine ``stats()`` keys that deliberately do NOT ride ForwardPassMetrics
# into a Prometheus gauge, with the reason. The dynacache sync-gate test
# (tests/test_cache_obs.py) asserts every numeric stats() key is either an
# FPM field (and rendered by the aggregator) or listed here — so a new
# stats counter can never silently stop at the stats plane again (the
# drift class PR 10 found by hand).
STATS_PROMETHEUS_SKIP = {
    "spec_decode_steps":
        "raw counter folded into spec_decode_mean_accepted_len",
    "spec_decode_draft_tokens_total":
        "raw counter folded into spec_decode_acceptance_rate",
    "spec_decode_accepted_tokens_total":
        "raw counter folded into spec_decode_acceptance_rate",
    # the engine's account of its own time (docs/observability.md): read
    # as deltas from stats() by the benchmark's readers; no gauge yet
    **{key: "stats()-only counter of the engine's own time accounting"
       for key in (
           "step_iterations_total", "prefill_wait_seconds_total",
           "first_token_seconds_total", "engine_ttft_seconds_total",
           "first_tokens_total", "prefill_tokens_total",
           "prefill_slots_total", "prefill_dispatches_total",
           "prefill_logits_skipped_total",
           "prefill_window_topups_total", "prefill_runahead_total",
           "moe_grouped_programs_total",
           "moe_grouped_window_forwards_total",
           "prefill_rows_held_back_total", "prefill_bucket_narrowed_total",
           "decode_rows_total", "decode_slots_total",
           "decode_windows_total", "decode_windows_sampled_total",
           "warmup_seconds")},
    # the host's account beside it (PR 35; runtime/profiling.py
    # host_stats): process-wide, read as deltas, no gauge
    **{key: "stats()-only counter of the host threads' time accounting"
       for key in (
           "loop_lag_seconds_total", "loop_lag_samples_total",
           "intake_seconds_total", "intake_total",
           "emit_to_wire_seconds_total", "emit_to_wire_total",
           "first_emit_to_wire_seconds_total", "first_emit_to_wire_total",
           "gc_pause_seconds_total")},
    # the set-up ledger's (PR 52; runtime/profiling.py SetupLedger): a
    # start's own, on the process's /metrics as
    # dyn_engine_compile_cache_{hits,misses}_total; no aggregator gauge
    **{key: "stats()-only counter of the process's set-up ledger"
       for key in ("compile_cache_hits_total",
                   "compile_cache_misses_total")},
}


@dataclass
class KvCacheEventWire:
    """Stored/Removed event as published on the bus (reference
    protocols.rs KvCacheEvent + the worker id tag added on receive)."""

    worker_id: int
    kind: str                        # "stored" | "removed"
    block_hashes: List[int]
    parent_hash: Optional[int] = None

    def to_dict(self) -> dict:
        return {"worker_id": self.worker_id, "kind": self.kind,
                "block_hashes": self.block_hashes,
                "parent_hash": self.parent_hash}

    @classmethod
    def from_dict(cls, d: dict) -> "KvCacheEventWire":
        return cls(worker_id=d["worker_id"], kind=d["kind"],
                   block_hashes=list(d["block_hashes"]),
                   parent_hash=d.get("parent_hash"))


@dataclass
class KVHitRateEvent:
    """Per-decision observability event (reference scheduler.rs:27-32)."""

    worker_id: int
    isl_blocks: int
    overlap_blocks: int

    def to_dict(self) -> dict:
        return dict(self.__dict__)
