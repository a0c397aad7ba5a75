"""dynashard: mesh-sharded serving — data-parallel engine replicas on
partitioned submeshes behind the KV router.

The multichip machinery (``parallel/mesh.py`` sharding specs, ring
attention, the sharded Pallas wrappers) existed only as kernels and
dryruns; this module is the subsystem that serves REAL requests through
it:

- :func:`parse_mesh_shape` / :data:`DYN_MESH_SHAPE` — one string knob
  (``"model=2"``, ``"data=2,model=4"``) naming the per-replica mesh.
- :class:`DevicePool` — deterministic submesh assignment over the local
  device set: replicas acquire contiguous device groups lowest-index
  first, drained replicas return theirs, and joins re-partition onto the
  freed devices. Pure bookkeeping (devices are opaque), shared by the
  real replica set below and the fleet simulator's sharded scenario.
- :class:`ShardedReplicaSet` — N data-parallel :class:`JaxEngine`
  replicas, each pjit-sharded over its own submesh, each attached to the
  control plane as its OWN worker (own ``DistributedRuntime`` → own
  lease → own instance id, exactly like a separate worker process) with
  its own KV-event publisher — so the real HTTP frontend + KV router see
  N workers of one component and overlap-route between them.

Reference: SURVEY §2.4's parallelism inventory (vLLM
``--tensor-parallel-size`` + Ray bootstrap; SGLang per-rank
subprocesses) made real behind the frontend. On TPU one replica = one
SPMD program over its submesh; GSPMD inserts the collectives.

This module imports jax lazily: the pure partitioning pieces are used by
the (jax-free) fleet simulator.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ..runtime.config import env_int, env_str

log = logging.getLogger("dynamo_tpu.parallel.serving")

MESH_AXES = ("data", "model", "expert", "seq", "stage")


def parse_mesh_shape(spec: Optional[str]) -> Dict[str, int]:
    """``"data=2,model=4"`` → ``{"data": 2, "model": 4}``. Empty/None →
    ``{}`` (single-device). Unknown axes and non-positive sizes raise —
    a typo'd DYN_MESH_SHAPE must fail loudly, not serve unsharded."""
    axes: Dict[str, int] = {}
    if not spec:
        return axes
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"mesh shape entry {part!r} is not axis=N "
                f"(axes: {', '.join(MESH_AXES)})")
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in MESH_AXES:
            raise ValueError(f"unknown mesh axis {name!r} "
                             f"(axes: {', '.join(MESH_AXES)})")
        n = int(val)
        if n < 1:
            raise ValueError(f"mesh axis {name}={n} must be >= 1")
        axes[name] = n
    return axes


def mesh_shape_str(axes: Dict[str, int]) -> str:
    """Canonical wire/report form: ``"data=2,model=4"`` (axis order fixed,
    size-1 axes elided); ``"single"`` for the unsharded case."""
    parts = [f"{a}={axes[a]}" for a in MESH_AXES if axes.get(a, 1) > 1]
    return ",".join(parts) if parts else "single"


def devices_per_replica(axes: Dict[str, int]) -> int:
    n = 1
    for a in MESH_AXES:
        n *= axes.get(a, 1)
    return n


class NoFreeDevices(RuntimeError):
    """The pool cannot satisfy a submesh acquisition."""


class DevicePool:
    """Deterministic submesh assignment over an ordered device list.

    Acquisition hands out the ``n`` lowest-index free devices (contiguous
    groups when the pool is unfragmented — neighbouring devices share the
    fastest ICI links); release returns a replica's devices to the free
    set, so a later join re-partitions onto them. Devices are opaque
    objects (real ``jax.Device``s, or plain ints in the fleet sim)."""

    def __init__(self, devices: Sequence):
        self.devices = list(devices)
        self.assigned: Dict[str, List] = {}

    @property
    def free(self) -> List:
        taken = {id(d) for devs in self.assigned.values() for d in devs}
        return [d for d in self.devices if id(d) not in taken]

    def acquire(self, name: str, n: int) -> List:
        if name in self.assigned:
            raise ValueError(f"replica {name!r} already holds devices")
        free = self.free
        if len(free) < n:
            raise NoFreeDevices(
                f"replica {name!r} needs {n} devices; only {len(free)} of "
                f"{len(self.devices)} free")
        devs = free[:n]
        self.assigned[name] = devs
        return devs

    def release(self, name: str) -> List:
        return self.assigned.pop(name, [])

    def assignment(self) -> Dict[str, List[int]]:
        """Per-replica device INDEX lists (stable, report-friendly)."""
        index = {id(d): i for i, d in enumerate(self.devices)}
        return {name: [index[id(d)] for d in devs]
                for name, devs in sorted(self.assigned.items())}


@dataclass
class ReplicaSpec:
    """One planned replica: name, its devices, the per-replica mesh."""

    index: int
    name: str
    devices: List
    mesh_axes: Dict[str, int] = field(default_factory=dict)

    @property
    def mesh_shape(self) -> str:
        return mesh_shape_str(self.mesh_axes)


def plan_replicas(mesh_axes: Dict[str, int], replicas: int,
                  devices: Sequence) -> List[ReplicaSpec]:
    """Partition ``devices`` into ``replicas`` submeshes of
    ``devices_per_replica(mesh_axes)`` each (lowest-index-first)."""
    per = devices_per_replica(mesh_axes)
    pool = DevicePool(devices)
    return [ReplicaSpec(index=i, name=f"r{i}",
                        devices=pool.acquire(f"r{i}", per),
                        mesh_axes=dict(mesh_axes))
            for i in range(replicas)]


def apply_forced_host_devices() -> Optional[int]:
    """CPU bring-up: honor ``DYN_FORCE_HOST_DEVICES=N`` by appending
    ``--xla_force_host_platform_device_count=N`` to ``XLA_FLAGS``.

    MUST run before the jax backend initializes (the flag is read once at
    backend init — setting it later is silently ignored, which is why the
    tier-1 sharded tests run in a subprocess). Returns N when applied."""
    import os

    n = env_int("DYN_FORCE_HOST_DEVICES")
    if not n or n <= 1:
        return None
    flags = env_str("XLA_FLAGS") or ""
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    return n


def build_replica_engine(model_cfg, engine_cfg, spec: ReplicaSpec, *,
                         params=None, seed: int = 0, quant=None,
                         warmup: bool = True):
    """Build (and warm) one replica's :class:`JaxEngine` on its submesh.

    ``params=None`` + a shared ``seed`` gives every replica an identical
    host-side init (the data-parallel contract: same weights, disjoint
    devices); a provided host params tree is device_put onto the submesh
    by the engine's ``shard_params``. Blocking (XLA compiles) — callers
    on an event loop run this in a thread."""
    from ..engine.jax_engine import JaxEngine
    from .mesh import MeshSpec

    # always a mesh over the replica's OWN devices: a one-chip replica
    # gets a one-device mesh, which is how the engine learns which chip
    # is its (mesh=None would put every replica on the default device)
    mesh = MeshSpec(**spec.mesh_axes).build(spec.devices)
    engine = JaxEngine(model_cfg, engine_cfg, params=params, seed=seed,
                       mesh=mesh, quant=quant, worker_label=spec.name)
    if warmup:
        engine.warmup()
    return engine


class ShardedReplica:
    """One live replica: engine + its own runtime attachment + endpoint +
    KV-event publisher. The per-replica ``DistributedRuntime`` is what
    gives each replica its own lease → instance id → stats subject, so
    N replicas in one process look exactly like N worker processes to
    the router, the metrics aggregator and discovery."""

    def __init__(self, spec: ReplicaSpec, engine, namespace: str,
                 component: str, mdc):
        self.spec = spec
        self.name = spec.name
        self.engine = engine
        self.namespace = namespace
        self.component = component
        self.mdc = mdc
        self.drt = None
        self._handle = None
        self._publisher = None

    @property
    def instance_id(self) -> int:
        return self.drt.instance_id if self.drt else 0

    async def start(self, dcp_address: str) -> None:
        from ..llm.worker import serve_token_model
        from ..runtime.runtime import DistributedRuntime

        self.drt = await DistributedRuntime.attach(dcp_address)
        self._handle, self._publisher = await serve_token_model(
            self.drt, self.mdc, self.engine, namespace=self.namespace,
            component=self.component)
        log.info("replica %s serving as instance %x on %d device(s) "
                 "(mesh %s)", self.name, self.instance_id,
                 len(self.spec.devices), self.spec.mesh_shape)

    async def drain(self) -> None:
        """Withdraw from discovery and cancel in-flight streams
        (ServeHandle.stop kills their contexts; the processor's
        round-robin fallback re-routes the callers). Claim-before-await
        so concurrent drain/stop never double-stops."""
        handle, self._handle = self._handle, None
        if handle is not None:
            await handle.stop()

    async def drain_graceful(self, timeout_s=None) -> bool:
        """dynarevive graceful drain: discovery withdrawn first, then
        in-flight sequences finish (bounded by DYN_DRAIN_TIMEOUT_MS /
        ``timeout_s``), KV events flush, and only then does the handle
        stop. Returns True when everything finished inside the budget."""
        from ..runtime import revive

        handle, self._handle = self._handle, None
        if handle is None:
            return True
        return await revive.drain_worker(
            handle, engine=self.engine, publisher=self._publisher,
            timeout_s=timeout_s)

    async def stop(self) -> None:
        # lifecycle drain (discovery withdrawal), not a socket drain
        await self.drain()  # dynalint: disable=unbounded-await
        publisher, self._publisher = self._publisher, None
        if publisher is not None:
            await publisher.stop()
        if self.engine is not None:
            await self.engine.stop()
        drt, self.drt = self.drt, None
        if drt is not None:
            await drt.shutdown()


class ShardedReplicaSet:
    """N data-parallel sharded engine replicas behind one component.

    Each replica: a :class:`JaxEngine` pjit-sharded over its own submesh
    of the local device set, attached to the control plane as its own
    worker instance serving ``generate_tokens``, with its own KV-event
    publisher feeding the router's radix index. ``scale_to`` joins and
    drains replicas at runtime, re-partitioning the submesh assignment
    through the shared :class:`DevicePool` (drained replicas' devices are
    what the next join builds on)."""

    def __init__(self, model_cfg, engine_cfg, *,
                 mesh_axes: Optional[Dict[str, int]] = None,
                 replicas: Optional[int] = None,
                 namespace: str = "dynamo", component: str = "sharded",
                 mdc=None, dcp_address: Optional[str] = None,
                 params=None, seed: int = 0, quant=None,
                 warmup: bool = True):
        if mesh_axes is None:
            mesh_axes = parse_mesh_shape(env_str("DYN_MESH_SHAPE"))
        if replicas is None:
            replicas = env_int("DYN_DP_REPLICAS") or 1
        if replicas < 1:
            raise ValueError(f"replicas ({replicas}) must be >= 1")
        self.model_cfg = model_cfg
        self.engine_cfg = engine_cfg
        self.mesh_axes = dict(mesh_axes)
        self.initial_replicas = replicas
        self.namespace = namespace
        self.component = component
        self.mdc = mdc
        self.dcp_address = dcp_address
        self.params = params
        self.seed = seed
        self.quant = quant
        self.warmup = warmup
        self.pool: Optional[DevicePool] = None
        self.replicas: List[ShardedReplica] = []
        self._spawned = 0
        self._anchor = None  # embedded DCP server owner when no address

    @property
    def mesh_shape(self) -> str:
        return mesh_shape_str(self.mesh_axes)

    @property
    def per_replica_devices(self) -> int:
        return devices_per_replica(self.mesh_axes)

    async def start(self) -> None:
        import jax

        if self.mdc is None:
            from ..llm.model_card import ModelDeploymentCard

            self.mdc = ModelDeploymentCard(
                name="sharded", tokenizer_kind="byte",
                kv_block_size=self.engine_cfg.page_size,
                model_type="completions")
        if self.dcp_address is None:
            # single-process bring-up: embed a DCP server; every replica
            # still attaches separately (own lease each)
            from ..runtime.runtime import DistributedRuntime

            anchor = await DistributedRuntime.detached()
            if self.dcp_address is None:  # re-check: concurrent start()
                self._anchor = anchor
                self.dcp_address = anchor.dcp.address
            else:
                await anchor.shutdown()
        self.pool = DevicePool(jax.devices())
        per = self.per_replica_devices
        need = per * self.initial_replicas
        if len(self.pool.devices) < need:
            raise NoFreeDevices(
                f"{self.initial_replicas} replicas x {per} devices "
                f"(mesh {self.mesh_shape}) need {need} devices, have "
                f"{len(self.pool.devices)} (CPU: set "
                f"DYN_FORCE_HOST_DEVICES before jax initializes)")
        for _ in range(self.initial_replicas):
            await self._join()

    async def _join(self) -> ShardedReplica:
        name = f"r{self._spawned}"
        self._spawned += 1
        spec = ReplicaSpec(
            index=self._spawned - 1, name=name,
            devices=self.pool.acquire(name, self.per_replica_devices),
            mesh_axes=dict(self.mesh_axes))
        # the compile fence is process-global (engine/jit_fence.py): the
        # joining replica's warmup compiles would count against every
        # LIVE replica's armed fence. A join is an intentional, visible
        # compile phase — mask the siblings' fences for its duration so
        # per-replica post_warmup_compiles keeps meaning "THIS replica's
        # serving path compiled mid-flight".
        live_fences = [r.engine.fence for r in self.replicas]
        for fence in live_fences:
            fence.disarm()
        try:
            # build + warmup are blocking XLA work; keep the loop serving
            engine = await asyncio.to_thread(
                build_replica_engine, self.model_cfg, self.engine_cfg,
                spec, params=self.params, seed=self.seed, quant=self.quant,
                warmup=self.warmup)
        except BaseException:
            self.pool.release(name)
            raise
        finally:
            for fence in live_fences:
                fence.arm()
        replica = ShardedReplica(spec, engine, self.namespace,
                                 self.component, self.mdc)
        await replica.start(self.dcp_address)
        self.replicas.append(replica)
        return replica

    async def scale_to(self, n: int) -> Dict[str, List[str]]:
        """Converge to ``n`` live replicas: joins build fresh engines on
        free (possibly previously-released) devices; drains retire the
        newest replicas first and return their submeshes to the pool.
        Returns {"joined": [...], "drained": [...]} replica names."""
        if n < 0:
            raise ValueError("scale_to needs n >= 0")
        joined: List[str] = []
        drained: List[str] = []
        while len(self.replicas) > n:
            replica = self.replicas.pop()  # newest-first
            await replica.stop()
            self.pool.release(replica.name)
            drained.append(replica.name)
        while len(self.replicas) < n:
            joined.append((await self._join()).name)
        return {"joined": joined, "drained": drained}

    async def flush_kv_events(self) -> None:
        """Push every replica's pending stored-block events onto the bus
        NOW (the publishers run on an interval) — wave-boundary settling
        for benches/tests that need the router's index current before the
        next wave routes."""
        for replica in self.replicas:
            if replica._publisher is not None:
                await replica._publisher.flush()

    # ------------------------------------------------------ observability

    def assignment(self) -> Dict[str, List[int]]:
        return self.pool.assignment() if self.pool else {}

    def stats_by_replica(self) -> Dict[str, dict]:
        return {r.name: r.engine.stats() for r in self.replicas}

    def post_warmup_compiles(self) -> Dict[str, int]:
        return {r.name: r.engine.fence.post_warmup_compiles
                for r in self.replicas}

    def describe(self) -> dict:
        """Report block: mesh shape, the live submesh assignment, and the
        per-replica instance ids (the KV router's worker ids)."""
        return {
            "mesh_shape": self.mesh_shape,
            "devices_per_replica": self.per_replica_devices,
            "replicas": len(self.replicas),
            "assignment": self.assignment(),
            "instances": {r.name: f"{r.instance_id:x}"
                          for r in self.replicas},
        }

    async def drain(self, timeout_s=None) -> bool:
        """dynarevive graceful shutdown (the SIGTERM path): every replica
        withdraws from discovery, finishes in-flight sequences bounded by
        DYN_DRAIN_TIMEOUT_MS, flushes KV events, then the set stops and
        leases release. Returns True when every replica drained clean."""
        results = []
        for replica in self.replicas:
            # lifecycle drain (state machine in runtime/revive.py), not
            # a socket drain
            results.append(  # dynalint: disable=unbounded-await
                await replica.drain_graceful(timeout_s))
        await self.stop()
        return all(results)

    async def stop(self) -> None:
        while self.replicas:
            replica = self.replicas.pop()
            try:
                await replica.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                log.exception("replica %s stop failed", replica.name)
            if self.pool is not None:
                self.pool.release(replica.name)
        anchor, self._anchor = self._anchor, None
        if anchor is not None:
            await anchor.shutdown()
