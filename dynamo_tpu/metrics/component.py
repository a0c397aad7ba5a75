"""Namespace-wide metrics aggregator component.

Reference components/metrics (src/main.rs:24-46 + lib.rs, ~1,000 LoC):
scrapes worker ForwardPassMetrics over the service-stats plane, subscribes
``kv-hit-rate`` events from the router, and exposes everything as
Prometheus text for Grafana (deploy/metrics/grafana.json).

Gauges mirror the reference's aggregator: per-worker slots/blocks/waiting/
cache-usage plus namespace aggregates (avg/min/max), and hit-rate counters
(isl blocks vs overlap blocks per routed request).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Callable, Dict, Optional

from ..llm.kv_router.protocols import KV_HIT_RATE_SUBJECT, ForwardPassMetrics
from ..runtime.component import Client, EndpointAddress
from ..runtime.config import env_str
from ..runtime import blackbox, wire
from ..runtime.dcp_client import unpack
from ..runtime.runtime import DistributedRuntime
from ..runtime.slo import (Histogram, SloEngine, SloRegistry, collapse_roles,
                           merge_latency_wire, render_role_histograms)
from ..runtime.tasks import backoff_interval, cancel_join, spawn_tracked

log = logging.getLogger("dynamo_tpu.metrics")


class MetricsAggregator:
    """Scrape + subscribe + render (one per namespace)."""

    def __init__(self, drt: DistributedRuntime, namespace: str,
                 component: str, endpoint: str = "generate_tokens",
                 interval: float = 2.0,
                 slo_registry: Optional[SloRegistry] = None,
                 slo_clock: Callable[[], float] = time.monotonic):
        self.drt = drt
        self.namespace = namespace
        self.address = EndpointAddress(namespace, component, endpoint)
        self.interval = interval
        # written by the scrape loop, read by every /metrics render;
        # single-statement accesses only (atomic under the event loop)
        self.worker_metrics: Dict[int, ForwardPassMetrics] = {}  # guarded-by: loop
        self.hit_rate_isl_blocks = 0
        self.hit_rate_overlap_blocks = 0
        self.hit_rate_events = 0
        # failed scrape attempts (the PR 3 backoff path, now visible in
        # the exposition instead of only the logs)
        self.scrape_failures_total = 0
        self.consecutive_scrape_failures = 0
        # dynaslo: fold each scraped worker's per-role latency histograms
        # into a run-long per-worker view (a drained worker's histogram
        # leaves worker_metrics with it, but its observations happened)
        # and evaluate the SLO registry over the fleet-merged result on
        # every scrape. The clock is injectable: wall time in serving,
        # virtual time in the fleet simulator.
        self._latency_seen: Dict[int, dict] = {}  # guarded-by: loop
        self.slo = SloEngine(
            slo_registry if slo_registry is not None
            else SloRegistry.from_env(),
            source=self.merged_latency_all_roles, clock=slo_clock)
        self._client: Optional[Client] = None
        self._task: Optional[asyncio.Task] = None
        self._sid: Optional[int] = None
        self._bb_sid: Optional[int] = None

    def last_scrape(self) -> dict:
        """The most recent fleet scrape as a JSON-safe dict — folded into
        dynablack incident bundles as the 'what did the aggregator see
        last' evidence."""
        return {
            "workers": {str(wid): m.to_dict()
                        for wid, m in sorted(self.worker_metrics.items())},
            "hit_rate_events": self.hit_rate_events,
            "scrape_failures_total": self.scrape_failures_total,
            "alerts": list(self.slo.alert_events[-20:]),
        }

    async def start(self, *, run_loop: bool = True) -> None:
        """``run_loop=False`` skips the periodic scrape task; drivers that
        step time themselves (the fleet simulator) call ``scrape_once``
        directly."""
        self._client = await self.drt.namespace(
            self.address.namespace).component(
            self.address.component).endpoint(self.address.endpoint).client()
        self._sid = await self.drt.dcp.subscribe(
            f"{self.namespace}.{KV_HIT_RATE_SUBJECT}", self._on_hit_rate)
        # dynablack: join the incident capture fan-out — the aggregator
        # contributes its last fleet scrape and receives sibling captures
        rec = blackbox.get_recorder()
        if rec.enabled:
            rec.add_source("fleet_scrape", self.last_scrape)
            self._bb_sid = await blackbox.attach_dcp(
                self.drt, self.namespace, rec,
                f"aggregator-{self.address.component}")
        if run_loop:
            self._task = spawn_tracked(self._loop(), name="metrics-scrape")

    async def stop(self) -> None:
        await cancel_join(self._task)
        for sid in (self._sid, self._bb_sid):
            if sid is None:
                continue
            try:
                await self.drt.dcp.unsubscribe(sid)
            except Exception:
                log.debug("unsubscribe failed during stop", exc_info=True)
        if self._client:
            await self._client.close()

    async def _on_hit_rate(self, msg) -> None:
        ev = unpack(msg.payload)
        self.hit_rate_events += 1
        self.hit_rate_isl_blocks += int(ev.get("isl_blocks", 0))
        self.hit_rate_overlap_blocks += int(ev.get("overlap_blocks", 0))

    async def _loop(self) -> None:
        failures = 0
        while True:
            try:
                await self.scrape_once()
                failures = 0
            except Exception:
                # bounded backoff: a persistently-down stats plane gets
                # polled gently instead of hammered every interval forever
                failures += 1
                self.scrape_failures_total += 1
                log.exception("metrics scrape failed "
                              "(%d consecutive failures)", failures)
            self.consecutive_scrape_failures = failures
            await asyncio.sleep(backoff_interval(self.interval, failures))

    async def scrape_once(self) -> None:
        stats = await self._client.collect_stats()
        live = set()
        for instance_id, payload in stats.items():
            payload = wire.decoded(wire.DCP_STATS_REPLY, payload)
            data = payload.get("data") or {}
            self.worker_metrics[instance_id] = ForwardPassMetrics.from_dict(
                data)
            live.add(instance_id)
        # drop metrics of departed workers (lease expiry) and of workers
        # quarantined off the stats plane (a crashed-but-leased worker
        # must not keep contributing its last-known load forever)
        evicted = set(self._client.evicted_ids())
        for wid in list(self.worker_metrics):
            if wid not in live and (wid not in self._client.instances
                                    or wid in evicted):
                del self.worker_metrics[wid]
        # dynaslo: per-worker histograms are monotonic counters, so the
        # newest scrape simply overwrites; departed workers keep their
        # last-seen contribution (fleet totals never regress on a drain)
        for wid, m in self.worker_metrics.items():
            if m.latency_hist:
                self._latency_seen[wid] = m.latency_hist
        self.slo.tick()

    # ----------------------------------------------------- dynaslo merging

    def merged_latency(self) -> Dict[str, Dict[str, Histogram]]:
        """Fleet-wide ``{role: {metric: Histogram}}`` — every worker's
        latency histograms losslessly merged (the first cross-worker
        latency view; per-worker gauges could never aggregate)."""
        return merge_latency_wire(self._latency_seen.values())

    def merged_latency_all_roles(self) -> Dict[str, Histogram]:
        """Role-collapsed merge — the SLO engine's evaluation source."""
        return collapse_roles(self.merged_latency())

    def slo_snapshot(self) -> dict:
        """The aggregator-side /debug/slo payload: registry, evaluation,
        pressures, alert timeline, plus merged per-role quantiles."""
        snap = self.slo.snapshot()
        snap["quantiles"] = {
            role: {metric: {"p50": h.quantile(0.5), "p95": h.quantile(0.95),
                            "p99": h.quantile(0.99), "count": h.count}
                   for metric, h in sorted(per.items())}
            for role, per in sorted(self.merged_latency().items())}
        return snap

    # ------------------------------------------------------------- render

    def render_prometheus(self) -> str:
        """Prometheus text exposition (reference lib.rs gauges +
        deploy/metrics Grafana dashboard feed)."""
        ns = self.namespace
        lines = []

        def gauge(name, help_, rows):
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} gauge")
            lines.extend(rows)

        def wlabels(wid, m) -> str:
            """Per-worker label set. The `replica` label (the engine's
            stable worker_label, dynashard) disambiguates N replicas in
            one process and survives restarts — the `worker` lease hex
            does neither."""
            extra = ""
            if getattr(m, "worker_label", ""):
                extra = f',replica="{m.worker_label}"'
            return f'namespace="{ns}",worker="{wid:x}"{extra}'

        per_worker = [
            ("dyn_engine_mesh_devices",
             "devices in this worker's submesh (1 = unsharded; dynashard)",
             lambda m: m.mesh_devices),
            ("dyn_worker_draining",
             "1 while the worker drains (discovery withdrawn, in-flight "
             "finishing; dynarevive — draining is not dead)",
             lambda m: m.draining),
            ("dyn_worker_request_active_slots", "active request slots",
             lambda m: m.request_active_slots),
            ("dyn_worker_request_total_slots", "total request slots",
             lambda m: m.request_total_slots),
            ("dyn_worker_kv_active_blocks", "active KV blocks",
             lambda m: m.kv_active_blocks),
            ("dyn_worker_kv_total_blocks", "total KV blocks",
             lambda m: m.kv_total_blocks),
            ("dyn_worker_requests_waiting", "queued requests",
             lambda m: m.num_requests_waiting),
            ("dyn_worker_cache_usage_perc", "KV cache usage fraction",
             lambda m: m.gpu_cache_usage_perc),
            ("dyn_worker_prefix_cache_hit_rate",
             "engine prefix hit rate (windowed over recent admissions)",
             lambda m: m.gpu_prefix_cache_hit_rate),
            # dynacache: cache-lifecycle plane (allocation prefix split,
            # eviction fates + block age, restore queue) — every counter
            # the engine's PageManager keeps, per worker
            ("dyn_engine_cache_hit_rate_lifetime",
             "engine prefix hit rate since start (cumulative)",
             lambda m: m.gpu_prefix_cache_hit_rate_lifetime),
            ("dyn_engine_cache_prefix_hit_tokens_total",
             "prompt tokens served from the prefix cache",
             lambda m: m.prefix_hit_tokens_total),
            ("dyn_engine_cache_prompt_tokens_total",
             "prompt tokens admitted", lambda m: m.prompt_tokens_total),
            ("dyn_engine_cache_device_hit_blocks_total",
             "allocated blocks reused directly from the HBM pool",
             lambda m: m.cache_device_hit_blocks_total),
            ("dyn_engine_cache_host_restored_blocks_total",
             "allocated blocks restored from the host-DRAM tier",
             lambda m: m.cache_host_restored_blocks_total),
            ("dyn_engine_cache_fresh_blocks_total",
             "allocated blocks computed fresh (no cache source)",
             lambda m: m.cache_fresh_blocks_total),
            ("dyn_engine_cache_evict_offloaded_total",
             "HBM evictions that spilled to the host tier",
             lambda m: m.cache_evict_offloaded_total),
            ("dyn_engine_cache_evict_dropped_total",
             "HBM evictions dropped entirely (no host slot)",
             lambda m: m.cache_evict_dropped_total),
            ("dyn_engine_cache_evict_age_seconds_total",
             "summed block age (commit to eviction) of evicted blocks",
             lambda m: m.cache_evict_age_seconds_total),
            ("dyn_engine_cache_host_evictions_total",
             "host-tier blocks evicted to make room",
             lambda m: m.cache_host_evictions_total),
            ("dyn_engine_cache_restore_queue_depth",
             "host->HBM restores queued but not yet dispatched",
             lambda m: m.cache_restore_queue_depth),
            ("dyn_engine_cache_restores_drained_total",
             "host->HBM restores dispatched",
             lambda m: m.cache_restores_drained_total),
            ("dyn_engine_cache_restore_wait_seconds_total",
             "summed queue wait of dispatched restores",
             lambda m: m.cache_restore_wait_seconds_total),
            ("dyn_engine_cache_restore_batches_total",
             "host->HBM restore batches dispatched (dynaheat batching)",
             lambda m: m.cache_restore_batches_total),
            ("dyn_engine_cache_restore_batch_pages_total",
             "pages across dispatched restore batches (mean batch size "
             "= pages / batches)",
             lambda m: m.cache_restore_batch_pages_total),
            ("dyn_engine_batch_dispatches_total",
             "dispatches that distributed a per-request step share "
             "(dynaprof attribution conservation denominator)",
             lambda m: m.batch_dispatches_total),
            ("dyn_worker_spec_decode_acceptance_rate",
             "speculative-draft tokens accepted / drafted",
             lambda m: m.spec_decode_acceptance_rate),
            ("dyn_worker_spec_decode_mean_accepted_len",
             "mean accepted draft length per verify step",
             lambda m: m.spec_decode_mean_accepted_len),
            ("dyn_engine_post_warmup_compiles_total",
             "XLA compiles after warmup (compile-fence counter; nonzero "
             "= a mid-serving compile stalled this worker)",
             lambda m: m.post_warmup_compiles_total),
            ("dyn_worker_kv_transfer_bytes_total",
             "disagg KV bytes ingested over the transfer plane",
             lambda m: m.kv_transfer_bytes_total),
            ("dyn_worker_kv_transfer_chunks_total",
             "disagg KV chunk frames ingested",
             lambda m: m.kv_transfer_chunks_total),
            ("dyn_worker_kv_transfer_inject_seconds_total",
             "seconds spent injecting transferred KV into the pool",
             lambda m: m.kv_transfer_inject_seconds_total),
            ("dyn_worker_kv_transfer_streams_failed_total",
             "KV transfer streams torn down before commit",
             lambda m: m.kv_transfer_streams_failed_total),
            ("dyn_worker_remote_prefill_wait_seconds_total",
             "decode-side wait for remote prefill (enqueue to KV commit)",
             lambda m: m.remote_prefill_wait_seconds_total),
            # dynaprof: engine internals that previously never left
            # stats()
            ("dyn_engine_inflight_sequences",
             "sequences holding engine batch slots (prefilling+running)",
             lambda m: m.request_active_slots),
            ("dyn_engine_admission_queue_depth",
             "requests waiting for engine admission",
             lambda m: m.num_requests_waiting),
            ("dyn_engine_queue_wait_seconds_total",
             "cumulative seconds requests spent waiting for admission",
             lambda m: m.queue_wait_seconds_total),
            ("dyn_engine_kv_free_blocks",
             "free HBM KV pages", lambda m: m.kv_free_blocks),
            ("dyn_engine_kv_cached_blocks",
             "reusable prefix-cache HBM KV pages",
             lambda m: m.kv_cached_blocks),
            ("dyn_engine_host_free_blocks",
             "free host-tier KV pages", lambda m: m.host_free_blocks),
            ("dyn_engine_host_cache_usage_perc",
             "host offload-tier usage fraction",
             lambda m: m.host_cache_usage_perc),
            ("dyn_engine_host_offload_pages_total",
             "pages evicted HBM->host tier",
             lambda m: m.host_offload_pages_total),
            ("dyn_engine_host_restore_pages_total",
             "pages restored host tier->HBM",
             lambda m: m.host_restore_pages_total),
            ("dyn_engine_long_prefills_total",
             "sequence-parallel ring prefills served",
             lambda m: m.long_prefills_total),
            # the window layers' K/V pool (a model with a pool a kind of
            # layer; 0 for any other)
            ("dyn_engine_kv_window_pages_held_total",
             "window-pool pages held, summed at every decode dispatch",
             lambda m: m.kv_window_pages_held_total),
            ("dyn_engine_kv_window_pages_seen_total",
             "window-pool pages there, summed at every decode dispatch",
             lambda m: m.kv_window_pages_seen_total),
            ("dyn_engine_kv_window_pages_allocated_total",
             "window-pool pages handed to rows",
             lambda m: m.kv_window_pages_allocated_total),
            ("dyn_engine_kv_window_pages_released_total",
             "window-pool pages given back while their row ran",
             lambda m: m.kv_window_pages_released_total),
            ("dyn_engine_decode_row_steps_total",
             "decode row-steps dispatched (window-pool models)",
             lambda m: m.decode_row_steps_total),
            ("dyn_engine_decode_row_steps_past_window_total",
             "decode row-steps with positions behind their window",
             lambda m: m.decode_row_steps_past_window_total),
            ("dyn_engine_prefill_row_chunks_total",
             "prefill chunks dispatched, a row each",
             lambda m: m.prefill_row_chunks_total),
            ("dyn_engine_prefill_row_chunks_carried_total",
             "of those, chunks that start past position 0",
             lambda m: m.prefill_row_chunks_carried_total),
        ]
        for name, help_, get in per_worker:
            rows = [
                f'{name}{{{wlabels(wid, m)}}} {get(m)}'
                for wid, m in sorted(self.worker_metrics.items())]
            gauge(name, help_, rows)
        # dynaprof labeled family: loop lag quantiles
        gauge("dyn_runtime_loop_lag_seconds",
              "per-worker event-loop sleep-drift percentiles (dynaprof)",
              [f'dyn_runtime_loop_lag_seconds{{{wlabels(wid, m)},'
               f'quantile="{q}"}} {val}'
               for wid, m in sorted(self.worker_metrics.items())
               for q, val in (("p50", m.loop_lag_p50_seconds),
                              ("p99", m.loop_lag_p99_seconds))])
        usages = [m.gpu_cache_usage_perc
                  for m in self.worker_metrics.values()]
        if usages:
            gauge("dyn_namespace_cache_usage_avg", "mean cache usage",
                  [f'dyn_namespace_cache_usage_avg{{namespace="{ns}"}} '
                   f'{sum(usages)/len(usages)}'])
        lines.append("# HELP dyn_kv_hit_rate_isl_blocks routed prompt "
                     "blocks total")
        lines.append("# TYPE dyn_kv_hit_rate_isl_blocks counter")
        lines.append(f'dyn_kv_hit_rate_isl_blocks{{namespace="{ns}"}} '
                     f'{self.hit_rate_isl_blocks}')
        lines.append("# HELP dyn_kv_hit_rate_overlap_blocks routed prompt "
                     "blocks served from cache")
        lines.append("# TYPE dyn_kv_hit_rate_overlap_blocks counter")
        lines.append(f'dyn_kv_hit_rate_overlap_blocks{{namespace="{ns}"}} '
                     f'{self.hit_rate_overlap_blocks}')
        lines.append("# HELP dyn_kv_hit_rate_events routing decisions seen")
        lines.append("# TYPE dyn_kv_hit_rate_events counter")
        lines.append(f'dyn_kv_hit_rate_events{{namespace="{ns}"}} '
                     f'{self.hit_rate_events}')
        lines.append("# HELP dyn_metrics_scrape_failures_total failed "
                     "stats-plane scrape attempts (backoff path)")
        lines.append("# TYPE dyn_metrics_scrape_failures_total counter")
        lines.append(f'dyn_metrics_scrape_failures_total{{namespace="{ns}"}} '
                     f'{self.scrape_failures_total}')
        lines.append("# HELP dyn_metrics_consecutive_scrape_failures "
                     "current failure streak driving the scrape backoff")
        lines.append("# TYPE dyn_metrics_consecutive_scrape_failures gauge")
        lines.append(
            f'dyn_metrics_consecutive_scrape_failures{{namespace="{ns}"}} '
            f'{self.consecutive_scrape_failures}')
        evicted = len(self._client.evicted_ids()) if self._client else 0
        lines.append("# HELP dyn_metrics_evicted_instances instances "
                     "whose stats-plane circuit breaker is open after "
                     "consecutive probe failures (stale-endpoint hygiene)")
        lines.append("# TYPE dyn_metrics_evicted_instances gauge")
        lines.append(f'dyn_metrics_evicted_instances{{namespace="{ns}"}} '
                     f'{evicted}')
        # dynaslo plane: fleet-merged per-role latency histograms (the
        # first cross-worker TTFT/ITL/queue-wait/e2e quantiles) plus the
        # SLO registry's attainment / error-budget / burn-rate / alert /
        # pressure gauges
        if getattr(self, "_latency_seen", None) is not None:
            nslabel = f'namespace="{ns}"'
            lines.extend(render_role_histograms(self.merged_latency(),
                                                labels=nslabel))
            lines.extend(self.slo.render_prom_lines(labels=nslabel))
        # dynaguard plane: per-endpoint breaker state gauges + counters
        from ..runtime import guard

        lines.extend(guard.render_prom_lines())
        return "\n".join(lines) + "\n"


async def serve_metrics(drt: DistributedRuntime, namespace: str,
                        component: str, *, endpoint: str = "generate_tokens",
                        host: str = "0.0.0.0", port: int = 9091,
                        interval: float = 2.0):
    """Run the aggregator + a /metrics HTTP endpoint. Returns
    (aggregator, site_runner) — call ``runner.cleanup()`` +
    ``agg.stop()`` to shut down."""
    from aiohttp import web

    agg = MetricsAggregator(drt, namespace, component, endpoint, interval)
    await agg.start()

    async def metrics_handler(_request):
        return web.Response(text=agg.render_prometheus(),
                            content_type="text/plain")

    app = web.Application()
    app.router.add_get("/metrics", metrics_handler)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    log.info("metrics aggregator on %s:%d/metrics", host, port)
    return agg, runner


def main(argv=None) -> int:
    """Standalone aggregator process (reference components/metrics
    src/main.rs)."""
    import argparse

    ap = argparse.ArgumentParser(prog="dynamo-metrics")
    ap.add_argument("--namespace", default="dynamo")
    ap.add_argument("--component", required=True)
    ap.add_argument("--endpoint", default="generate_tokens")
    ap.add_argument("--port", type=int, default=9091)
    ap.add_argument("--dcp", default=None)
    args = ap.parse_args(argv)

    async def amain():
        drt = await DistributedRuntime.attach(
            args.dcp or env_str("DYN_DCP_ADDRESS"))
        agg, runner = await serve_metrics(
            drt, args.namespace, args.component,
            endpoint=args.endpoint, port=args.port)
        try:
            await asyncio.Event().wait()
        finally:
            await agg.stop()
            await runner.cleanup()
            await drt.shutdown()

    import logging as _logging

    _logging.basicConfig(level="INFO")
    asyncio.run(amain())
    return 0


if __name__ == "__main__":
    main()
