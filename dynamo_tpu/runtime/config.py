"""Layered runtime configuration + the environment-variable registry.

Reference lib/runtime/src/config.rs: figment-layered settings from env
(``DYN_WORKER_*`` / ``DYN_RUNTIME_*``) + optional TOML. Here: env
(``DYN_*``) + optional YAML/JSON file named by ``DYN_CONFIG_PATH``.

This module is also the single place in the tree allowed to touch
``os.environ`` (enforced by dynalint rule ``untracked-env-read``): every
knob the fleet reads is declared in :data:`ENV_REGISTRY` with a default,
an owning component, and a description, and read through the typed
``env_*`` helpers. ``docs/env_vars.md`` is generated from the registry
(``python -m tools.dynalint --write-env-docs docs/env_vars.md``) and
tier-1 asserts it stays in sync — an undeclared knob fails the build.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, fields
from typing import Dict, Optional


@dataclass(frozen=True)
class EnvVar:
    """One registered environment knob (name, documented default, owning
    component, human description)."""

    name: str
    default: Optional[str]
    component: str
    description: str


ENV_REGISTRY: Dict[str, EnvVar] = {}


def register_env(name: str, default: Optional[str], component: str,
                 description: str) -> str:
    ENV_REGISTRY[name] = EnvVar(name, default, component, description)
    return name


# ------------------------------------------------------------- the registry
# Keep alphabetical within each component block; docs/env_vars.md renders
# straight from this table.

register_env("DYN_BLACKBOX_COOLDOWN_S", "60", "runtime",
             "dynablack incident flight recorder: debounce (seconds) "
             "between persisted captures — a trigger storm (breaker "
             "flapping, repeated stalls) produces one bundle per "
             "cooldown window, not one per event. Manual captures "
             "inside the window answer 409 with Retry-After.")
register_env("DYN_BLACKBOX_DIR", None, "runtime",
             "dynablack: directory incident bundles are persisted into "
             "(one incident-<id>.json per capture). Unset = bundles are "
             "kept in the bounded in-memory incident table only "
             "(GET /debug/incidents).")
register_env("DYN_BLACKBOX_TRIGGERS", "all", "runtime",
             "dynablack: comma-separated trigger allowlist out of "
             "slo_burn_rate,breaker_open,post_warmup_compile,"
             "watchdog_stall,failover_resume,deadline_storm,manual — "
             "'all' (default) arms every trigger; 'manual' keeps only "
             "POST /debug/incidents/capture.")
register_env("DYN_BLACKBOX_WINDOW_S", "30", "runtime",
             "dynablack: how many seconds of shadow-ring telemetry an "
             "incident bundle folds in (trace spans, step-timeline "
             "events and shadow-ring entries older than the window are "
             "dropped at capture time). 0 disables the flight recorder "
             "entirely — no shadow rings, no triggers, no captures "
             "(the hot-path A/B control arm).")
register_env("DYN_BREAKER_PROBE_EVERY", "5", "runtime",
             "Circuit breakers: an OPEN breaker offers a single half-open "
             "probe every Nth denied call (deterministic cadence; works "
             "on stepped virtual time).")
register_env("DYN_BREAKER_RESET_S", "0", "runtime",
             "Circuit breakers: additionally offer the half-open probe "
             "once this many seconds have passed since opening "
             "(0 = count-based cadence only).")
register_env("DYN_BREAKER_THRESHOLD", "3", "runtime",
             "Circuit breakers: consecutive failures that flip an "
             "endpoint's breaker closed→open.")
register_env("DYN_CHAOS", None, "runtime",
             "Chaos-injection scenario for the real transports, e.g. "
             "'seed=42;sever:kv.send@after=1;delay:tcp.send@ms=50,p=0.2' "
             "(grammar in docs/robustness.md). Unset = no chaos.")
register_env("DYN_CONFIG_PATH", None, "runtime",
             "Path to a YAML/JSON RuntimeConfig overlay file.")
register_env("DYN_DRAIN_TIMEOUT_MS", "10000", "runtime",
             "dynarevive graceful drain: bound (ms) on finishing "
             "in-flight sequences after a worker receives SIGTERM or "
             "POST /drain — discovery record deleted first (no new "
             "admissions), KV events flushed, then the lease releases. "
             "On expiry leftover requests are killed.")
register_env("DYN_DCP_ADDRESS", None, "runtime",
             "host:port of the DCP control plane. Unset: workers embed an "
             "in-process server; CLIs fall back to 127.0.0.1:6650.")
register_env("DYN_LEASE_TTL", "10.0", "runtime",
             "Primary-lease TTL in seconds (worker liveness).")
register_env("DYN_IO_TIMEOUT", "30.0", "runtime",
             "Bound (seconds) on single network IO steps: connects, "
             "handshakes, socket-buffer drains. A dead peer fails a hop "
             "in this long instead of wedging it forever.")
register_env("DYN_LOG", "INFO", "runtime",
             "Root log level (DEBUG/INFO/WARNING/...).")
register_env("DYN_LOGGING_JSONL", "0", "runtime",
             "Emit JSONL structured logs instead of text (1/true).")
register_env("DYN_PROF_ATTR_RING", "2048", "runtime",
             "dynaprof: per-request cost-attribution ring capacity "
             "(finished-request attribution dicts kept per process for "
             "/v1/traces/{request_id} and the usage extension block).")
register_env("DYN_PROF_LOOP_INTERVAL_MS", "100", "runtime",
             "dynaprof: event-loop lag-monitor sampling interval in ms "
             "(the sleep whose wakeup drift is measured).")
register_env("DYN_PROF_STACKS", "256", "runtime",
             "dynaprof: max distinct folded stacks the stall watchdog "
             "keeps (new shapes past the cap are counted as dropped).")
register_env("DYN_PROF_STALL_MS", "250", "runtime",
             "dynaprof: loop-callback overrun (ms) past which the stall "
             "watchdog captures the event-loop thread's Python stack "
             "into the flamegraph ring; 0 disables the watchdog thread.")
register_env("DYN_PROTO_VALIDATE", "0", "runtime",
             "Debug mode: validate every proto.step(...) lifecycle "
             "anchor against the runtime/proto.py protocol registry at "
             "transition time (1/true). Default off — the static "
             "dynaproto pass (DL019/DL020) and the model checker are "
             "the production gates.")
register_env("DYN_REQUEST_DEADLINE_MS", "0", "runtime",
             "Default end-to-end request deadline in milliseconds, "
             "applied at the HTTP frontend when the request carries "
             "neither a `timeout` body field nor an X-Request-Deadline-Ms "
             "header. 0 = no implicit deadline.")
register_env("DYN_REQUEST_TIMEOUT", "60.0", "runtime",
             "Default request-plane timeout in seconds.")
register_env("DYN_REVIVE_JOURNAL_TOKENS", "4096", "runtime",
             "dynarevive failover: per-request bound on journaled "
             "emitted tokens (the resume prompt is prompt + journal, so "
             "past this bound the request is marked non-resumable "
             "rather than resumed with a truncated prompt).")
register_env("DYN_REVIVE_MAX", "2", "runtime",
             "dynarevive failover: max mid-stream re-dispatches per "
             "request after an upstream worker dies before its finish "
             "chunk (0 disables failover; the stream errors like "
             "pre-revive).")
register_env("DYN_REVIVE_RING", "2048", "runtime",
             "dynarevive failover: max concurrent journal entries kept "
             "per process (one per in-flight request; eviction only "
             "costs the evicted request its resumability).")
register_env("DYN_RETRY_BASE_MS", "50", "runtime",
             "RetryPolicy: decorrelated-jitter backoff base in ms.")
register_env("DYN_RETRY_CAP_MS", "2000", "runtime",
             "RetryPolicy: backoff ceiling in ms.")
register_env("DYN_RETRY_MAX_ATTEMPTS", "3", "runtime",
             "RetryPolicy: total attempts (first try included) for route "
             "resolution, remote-prefill dispatch, and stats scrapes. "
             "Retries never run past the request deadline.")
register_env("DYN_SHED_KV_FREE_BLOCKS", "0", "runtime",
             "dynarevive admission control: shed (early 503) when the "
             "worst worker's free KV blocks drop to/below this floor. "
             "0 disables the signal.")
register_env("DYN_SHED_LOOP_LAG_MS", "0", "runtime",
             "dynarevive admission control: shed when the worst "
             "worker's event-loop lag p99 exceeds this many ms. "
             "0 disables the signal.")
register_env("DYN_SHED_QUEUE_DEPTH", "0", "runtime",
             "dynarevive admission control: shed when the summed "
             "admission-queue depth exceeds this many waiting requests "
             "PER live worker. 0 disables the signal (the default "
             "frontend sheds on nothing until configured).")
register_env("DYN_SHED_RETRY_CAP_S", "8", "runtime",
             "dynarevive admission control: ceiling (seconds) on the "
             "load-derived, jittered Retry-After answered with shed / "
             "no-capacity 503s.")
register_env("DYN_SLO_BURN_THRESHOLD", "2.0", "runtime",
             "dynaslo: error-budget burn rate BOTH the fast and slow "
             "windows must exceed before an objective's multi-window "
             "alert fires (1.0 = spending exactly the budget).")
register_env("DYN_SLO_FAST_FRACTION", "0.1", "runtime",
             "dynaslo: the fast alert window as a fraction of each "
             "objective's window (SRE multi-window burn-rate pattern: "
             "the fast window catches the spike, the slow window proves "
             "it is sustained).")
register_env("DYN_SLO_FILE", None, "runtime",
             "dynaslo: path to a file of SLO objectives, one per line "
             "('#' comments), same grammar as DYN_SLO_OBJECTIVES. "
             "Ignored when DYN_SLO_OBJECTIVES is set.")
register_env("DYN_SLO_OBJECTIVES", None, "runtime",
             "dynaslo: ';'-separated SLO objectives, grammar "
             "[name=]metric<=threshold_s@target/window_s over metrics "
             "ttft|itl|queue_wait|e2e — e.g. 'ttft<=0.5@0.95/300;"
             "itl<=0.05@0.99/300'. Unset = no objectives (latency "
             "histograms still recorded and rendered).")
register_env("DYN_STATS_TIMEOUT", "2.0", "runtime",
             "Per-instance stats-plane scrape probe timeout in seconds.")
register_env("DYN_STEP_TIMELINE", "512", "runtime",
             "Engine step-timeline ring capacity (events kept per engine "
             "for /v1/traces); 0 disables the timeline.")
register_env("DYN_TRACE_JSONL", None, "runtime",
             "Path to append one JSON line per finished trace span "
             "(dyntrace export; unset = in-memory ring only).")
register_env("DYN_TRACE_RING", "4096", "runtime",
             "dyntrace in-memory ring capacity (finished spans kept per "
             "process for /v1/traces).")
register_env("DYN_TRACE_SAMPLE", "1.0", "runtime",
             "dyntrace sampling rate in [0,1], decided per root span "
             "(children follow their parent). 0 disables all tracing "
             "instrumentation (no spans, no envelope fields).")
register_env("DYN_WIRE_VALIDATE", "0", "runtime",
             "Debug mode: validate every wire frame against the "
             "runtime/wire.py schema registry at encode/decode time "
             "(1/true). Default off — the static dynalint pass (DL009/"
             "DL010) is the production gate.")

register_env("DYN_ADMIN_TOKENS", None, "admin",
             "Inline JSON token map for the admin API (absent = open API).")

register_env("DYN_KV_TRANSFER_CHUNK_PAGES", "4", "llm/disagg",
             "KV pages per streamed transfer chunk frame; 0 = legacy "
             "single bulk frame.")
register_env("DYN_KV_TRANSFER_INT8", "0", "llm/disagg",
             "int8-compress shipped KV pages (~half the DCN bytes; "
             "lossy). 1/true enables.")
register_env("DYN_PREFILL_TIMEOUT", "120.0", "llm/disagg",
             "Decode-side cap (seconds) on one remote-prefill wait "
             "(enqueue to KV commit); the request deadline caps it "
             "further. On expiry the request falls back to local "
             "prefill.")
register_env("DYN_REDISPATCH_MAX", "2", "llm/disagg",
             "Max remote-prefill dispatches per request (first + hedged "
             "re-enqueues after a fast transfer-plane failure, e.g. a "
             "prefill worker dying mid-transfer). 1 disables hedging.")

register_env("DYN_CACHE_TOPK", "20", "engine",
             "dynacache: hot prefix chains reported per engine in "
             "GET /debug/cache (top-K cached block hashes by reuse "
             "count; internal tracking stays bounded regardless).")
register_env("DYN_CACHE_WINDOW", "256", "engine",
             "dynacache: admissions in the windowed prefix-hit-rate "
             "window. stats()['gpu_prefix_cache_hit_rate'] (and the "
             "dyn_worker_prefix_cache_hit_rate gauge) reflect the last "
             "N admissions; the lifetime ratio and raw token totals are "
             "exported alongside.")

register_env("DYN_EVICT_POLICY", "cost", "engine",
             "dynaheat: KV eviction policy for both cache tiers "
             "(EngineConfig.evict_policy=None reads this). 'cost' "
             "(default) runs GreedyDual over the dynacache hot-prefix "
             "hit table — a hot shared prefix outlives cold one-shot "
             "churn, O(log n) per eviction; 'lru' restores the original "
             "least-recently-freed order (the A/B control arm).")
register_env("DYN_RESTORE_OVERLAP", "1", "engine",
             "dynaheat: pipeline host-tier restores — a drained batch's "
             "H2D + dequantize dispatch on one drain and its page "
             "inject lands on the next, so the transfer overlaps the "
             "intervening device step instead of stalling it. 0 "
             "restores the serial same-drain inject (the A/B control "
             "arm). EngineConfig.restore_overlap=None reads this.")
register_env("DYN_HOST_TIER_FP16", "0", "engine",
             "dynaheat: keep the host KV tier at pool precision instead "
             "of the int8 default (engine/kv_compress.py). int8 halves "
             "the D2H/H2D bytes and doubles pages-per-GB but pages "
             "round-trip lossily; set 1 for the lossless fallback when "
             "bit-exact restores matter more than tier capacity. "
             "Explicit EngineConfig.host_tier_int8=True/False wins.")

register_env("DYN_JIT_FENCE", None, "engine",
             "Runtime compile fence: reaction to an XLA compile AFTER "
             "JaxEngine.warmup() (the zero-compile serving invariant). "
             "Unset = count only (always exported as "
             "dyn_engine_post_warmup_compiles_total); 'warn' logs each "
             "compile; 'raise' fails the offending jit call with "
             "PostWarmupCompileError (the CI mode).")

register_env("DYN_ROUTER_AUTOTUNE", "1", "llm",
             "dynaheat: self-tune KvScheduler.load_balance_weight from "
             "the dynacache predicted-vs-realized overlap calibration "
             "error. Systematic over-prediction (stale/optimistic index) "
             "shifts weight toward load; under-prediction shifts it "
             "toward overlap. Bounded to [0.1, 0.9] and exported as the "
             "dyn_kv_router_load_balance_weight gauge; 0 pins the "
             "configured weight (the A/B control arm).")
register_env("DYN_ROUTER_AUTOTUNE_GAIN", "0.05", "llm",
             "dynaheat: per-window step size for the load_balance_weight "
             "autotuner (fraction of the bounded range moved per "
             "calibration window at full bias). Small values converge "
             "slowly but never oscillate; 0 observes without adjusting.")

register_env("DYN_PROF_USAGE", "0", "llm",
             "dynaprof: attach the per-request cost-attribution block "
             "to OpenAI usage payloads (stream_options.include_usage) "
             "as a `cost` extension field (1/true).")

register_env("DYN_FLEET_DISCOVERY_TIMEOUT", "10.0", "fleet",
             "Fleet simulator: wall-clock seconds to wait for spawned/"
             "stopped workers to propagate through discovery watches "
             "before a step proceeds.")
register_env("DYN_FLEET_MAX_WORKERS", "64", "fleet",
             "Fleet simulator: hard cap on workers the in-process fleet "
             "controller will run, regardless of planner advisories.")
register_env("DYN_FLEET_REPORT_DIR", None, "fleet",
             "Fleet simulator CLI: also write each run's JSON report "
             "into this directory (unset = stdout only).")

register_env("DYN_DP_REPLICAS", "1", "parallel",
             "dynashard: data-parallel engine replicas per process. Each "
             "replica gets its own submesh of the local device set, its "
             "own DistributedRuntime lease (= worker instance id) and its "
             "own KV-event publisher behind the KV router.")
register_env("DYN_FORCE_HOST_DEVICES", None, "parallel",
             "CPU bring-up: force this many virtual host devices by "
             "appending --xla_force_host_platform_device_count to "
             "XLA_FLAGS. Must be applied BEFORE the jax backend "
             "initializes (parallel.serving.apply_forced_host_devices; "
             "the tier-1 sharded tests run in a subprocess for exactly "
             "this reason).")
register_env("DYN_MESH_SHAPE", None, "parallel",
             "dynashard: per-replica device mesh as 'axis=N' pairs, e.g. "
             "'model=2' or 'data=2,model=4' (axes: data/model/expert/"
             "seq/stage — parallel/mesh.py). Unset = unsharded engines.")

register_env("DYN_DISABLE_PALLAS", None, "models",
             "Any non-empty value forces the XLA gather attention path "
             "everywhere (Pallas kill switch).")
register_env("DYN_PALLAS_INTERPRET", None, "models",
             "CPU test hook: any non-empty value runs Pallas kernels in "
             "interpret mode (never on a real TPU backend).")

register_env("DYN_DISABLE_NATIVE", None, "utils",
             "Any non-empty value disables building/loading the native "
             "C++ helper library.")
register_env("DYN_PROFILE_DIR", None, "run",
             "Capture a JAX/XLA profiler trace of the serving session "
             "into this directory.")

register_env("DYN_BENCH_REQ_TIMEOUT", "600", "bench",
             "bench.py: per-request timeout in seconds.")

register_env("DYN_TEST_TPU", None, "tests",
             "Set to run the test suite against real TPU hardware instead "
             "of the forced-CPU 8-device virtual mesh.")

register_env("DYNAMO_SERVICE_CONFIG", None, "sdk",
             "Inline JSON ServiceConfig ({service: {key: value}}) "
             "injected into @service workers by `dynamo serve`.")

# Externally-defined variables the tree reads (documented here so the
# full environment surface is one table; defaults are the upstream ones).
register_env("HF_HUB_OFFLINE", "1", "external",
             "Set by dynamo_tpu.llm.tokenizer unless already present: "
             "never hit the HuggingFace hub at serve time.")
register_env("TRANSFORMERS_OFFLINE", "1", "external",
             "Set alongside HF_HUB_OFFLINE for the transformers library.")
register_env("KUBERNETES_SERVICE_HOST", None, "external",
             "In-cluster apiserver host (set by kubelet); required by the "
             "operator's InClusterClient.")
register_env("KUBERNETES_SERVICE_PORT", "443", "external",
             "In-cluster apiserver port.")
register_env("JAX_COMPILATION_CACHE_DIR", None, "external",
             "JAX's persistent compile cache directory. Set: JAX reads "
             "it and the code sets no directory. Unset: "
             "runtime.compile_cache uses <checkout>/.jax_cache.")
register_env("JAX_PLATFORMS", None, "external",
             "JAX backend selector; the SDK/bench pin control-plane "
             "processes to cpu so only TPU workers touch the chip.")
register_env("XLA_FLAGS", None, "external",
             "XLA runtime flags; read (never clobbered) by "
             "parallel.serving.apply_forced_host_devices when appending "
             "the DYN_FORCE_HOST_DEVICES device-count override.")


class UnregisteredEnvVar(KeyError):
    """Reading an env var that is not in ENV_REGISTRY: register it in
    runtime/config.py so it lands in docs/env_vars.md."""


def _lookup(name: str) -> EnvVar:
    var = ENV_REGISTRY.get(name)
    if var is None:
        raise UnregisteredEnvVar(
            f"env var {name!r} is not registered; declare it in "
            f"dynamo_tpu/runtime/config.py (register_env) so it is "
            f"documented in docs/env_vars.md")
    return var


def env_str(name: str, default: Optional[str] = None, *,
            required: bool = False) -> Optional[str]:
    """The registered variable's value, else the explicit ``default``,
    else the registry default. ``required=True`` raises when unset."""
    var = _lookup(name)
    val = os.environ.get(name)
    if val is None:
        val = default if default is not None else var.default
    if val is None and required:
        raise KeyError(f"required env var {name} is not set")
    return val


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    val = env_str(name, None if default is None else str(default))
    return None if val is None else int(val)


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    val = env_str(name, None if default is None else str(default))
    return None if val is None else float(val)


def env_bool(name: str, default: bool = False) -> bool:
    """Truthy string values: 1/true/yes/on (case-insensitive)."""
    val = env_str(name)
    if val is None or val == "":
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def env_flag(name: str) -> bool:
    """Reference semantics for DYN_DISABLE_* style switches: ANY non-empty
    value (even '0') enables the flag."""
    _lookup(name)
    return bool(os.environ.get(name))


def env_set_default(name: str, value: str) -> None:
    """Registered setdefault (import-time offline pins and the like)."""
    _lookup(name)
    os.environ.setdefault(name, value)


def render_env_docs() -> str:
    """docs/env_vars.md content, generated from the registry."""
    lines = [
        "# Environment variables",
        "",
        "Generated from `dynamo_tpu/runtime/config.py` — do not edit by "
        "hand. Regenerate with:",
        "",
        "```",
        "python -m tools.dynalint --write-env-docs docs/env_vars.md",
        "```",
        "",
        "Every env read in the tree goes through this registry's typed "
        "helpers (`env_str`/`env_int`/`env_float`/`env_bool`/`env_flag`); "
        "dynalint rule `untracked-env-read` rejects direct `os.environ` "
        "access anywhere else, so this table is the complete knob surface.",
        "",
        "| Variable | Default | Component | Description |",
        "|---|---|---|---|",
    ]
    for var in sorted(ENV_REGISTRY.values(),
                      key=lambda v: (v.component, v.name)):
        default = "(unset)" if var.default is None else f"`{var.default}`"
        lines.append(f"| `{var.name}` | {default} | {var.component} "
                     f"| {var.description} |")
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------- RuntimeConfig

@dataclass
class RuntimeConfig:
    dcp_address: Optional[str] = None       # DYN_DCP_ADDRESS; None → embedded
    lease_ttl: float = 10.0                 # DYN_LEASE_TTL
    request_timeout: float = 60.0           # DYN_REQUEST_TIMEOUT
    log_level: str = "INFO"                 # DYN_LOG
    log_jsonl: bool = False                 # DYN_LOGGING_JSONL

    @classmethod
    def from_settings(cls) -> "RuntimeConfig":
        cfg = cls()
        path = env_str("DYN_CONFIG_PATH")
        if path and os.path.exists(path):
            with open(path) as f:
                if path.endswith((".yaml", ".yml")):
                    import yaml

                    data = yaml.safe_load(f) or {}
                else:
                    data = json.load(f)
            for f_ in fields(cls):
                if f_.name in data:
                    setattr(cfg, f_.name, data[f_.name])
        env_map = {
            "DYN_DCP_ADDRESS": ("dcp_address", str),
            "DYN_LEASE_TTL": ("lease_ttl", float),
            "DYN_REQUEST_TIMEOUT": ("request_timeout", float),
            "DYN_LOG": ("log_level", str),
            "DYN_LOGGING_JSONL": ("log_jsonl",
                                  lambda v: v.lower() in ("1", "true")),
        }
        for env, (name, conv) in env_map.items():
            if env in os.environ:
                setattr(cfg, name, conv(os.environ[env]))
        return cfg
