"""Mesh-sharded continuous batching: one slab shard per device.

The continuous engine (``repro.serve.continuous``) made serving fast on
one chip; this module is the ROADMAP's next step — "shard the serve
runtime across a device mesh" — built from the same parts:

* the slot slab grows to ``mesh_devices × slab_capacity`` slots and its
  chunk program runs under ``shard_map`` over a 1-D ``("serve",)`` mesh
  (:func:`repro.solvers.batched.make_chunk_stepper` with the slab's
  ``n_devices``; at one device it is the continuous engine's plain
  program): device d owns the contiguous slot block
  ``[d·S_dev, (d+1)·S_dev)`` and advances it with the *identical*
  per-slot math — the chunk core is collective-free, so sharding adds
  no communication;
* admission becomes two-level: the engine's shared policy-ordered
  :class:`~repro.serve.continuous.AdmissionQueue` feeds per-device
  queues through a routing policy (``ServeConfig.mesh_routing``), and
  each device backfills its own slots from its own queue;
* at the drain tail, a device with a free slot and an *empty* local
  queue **steals** from the longest other queue holding at least
  ``ServeConfig.steal_threshold`` entries — so one device's backlog of
  hard instances cannot idle the rest of the mesh, and a steal can only
  ever *move up* a request's admission tick;
* telemetry is a :class:`~repro.serve.metrics.MeshTelemetry`: chunk
  counters recorded per device, rolled up so the global view is the sum
  of the parts by construction (property-tested), plus steal/route
  counters and a ``steal_log`` audit trail.

Determinism contract (pinned by ``tests/test_serve_mesh.py``):

* at a **fixed device count**, a fixed seed + submission order
  reproduces responses, audit log, steal log and telemetry counts
  bitwise — routing and stealing are pure functions of queue state,
  and each request's PRNG stream is keyed by its request id alone;
* **across device counts**, results match the single-device continuous
  engine to ≤1e-5 (the freeze-on-convergence merge makes a request's
  final state its state at first convergence — independent of which
  device block it lands in, what shares the slab, and when it was
  admitted; only fp32 reduction-order noise remains);
* every request is serviced **exactly once**, stealing included — a
  steal moves a queue entry between host-side queues before admission,
  never a live slot.

Host→device discipline: the mesh slab inherits the staged-admission
buffers of ``_SlotSlab`` unchanged, including the ``.copy()`` on every
staging-buffer→device crossing — ``jnp.asarray`` zero-copies aligned
host buffers on CPU, and with per-device queues *partial* slab
re-stages are the common case, so an aliased buffer mutated by the next
tick's routing would race the still-in-flight sharded dispatch (a race
regression-tested under multi-device admission load).  An admitted
request's data rows go to the device that owns its slot and are written
into that device's shard of the slab alone (:meth:`_MeshSlab.
_write_rows`): no all-gather, and no copy through device 0.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.config.base import ServeConfig, SolverConfig
from repro.obs import trace as obs
from repro.serve.continuous import (AdmissionQueue, ContinuousSolverEngine,
                                    QueueEntry, _SlotSlab)
from repro.launch.mesh import make_mesh
from repro.serve.metrics import MeshTelemetry
from repro.solvers.batched import BatchedProblemSpec

#: Shared-queue → device-queue routing policies (``ServeConfig.
#: mesh_routing``).
ROUTING_POLICIES = ("least_loaded", "round_robin")


# ------------------------------------------------------------------ #
# Routing / stealing decisions as pure functions (property-testable   #
# with no devices, no engine, no jax)                                 #
# ------------------------------------------------------------------ #
def route_device(routing: str, loads, cursor: int) -> tuple[int, int]:
    """Pick the device for the next routed entry; returns
    ``(device, new_cursor)``.

    ``least_loaded`` minimizes ``loads[d]`` (live slots + queued
    entries) with the lowest device index as tie-break — total and
    deterministic.  ``round_robin`` ignores loads and cycles the
    cursor.
    """
    if routing == "round_robin":
        return cursor % len(loads), cursor + 1
    if routing == "least_loaded":
        return min(range(len(loads)),
                   key=lambda d: (loads[d], d)), cursor
    raise ValueError(
        f"unknown mesh routing {routing!r}; pick from {ROUTING_POLICIES}")


def steal_victim(queue_lens, thief: int, threshold: int) -> int | None:
    """The queue an idle device steals from: the longest queue other
    than the thief's own holding at least ``threshold`` entries (lowest
    device index on ties); ``None`` if no queue qualifies."""
    best = None
    for d, qlen in enumerate(queue_lens):
        if d == thief or qlen < threshold:
            continue
        if best is None or qlen > queue_lens[best]:
            best = d
    return best


# ------------------------------------------------------------------ #
# Sharded slab                                                        #
# ------------------------------------------------------------------ #
class _MeshSlab(_SlotSlab):
    """One sharded slab: ``n_devices × per-device capacity`` slots,
    per-device admission queues, work stealing, per-device telemetry.

    Device d owns slots ``[d·S_dev, (d+1)·S_dev)`` — the contiguous
    block ``shard_map`` places on mesh device d — so every host-side
    per-device view is a constant-stride slice of the inherited
    mirrors.  Everything else (staging buffers, the fused step, the
    eviction readback) is the parent's, byte for byte, but for where an
    admitted request's rows are written (:meth:`_write_rows`).
    """

    def __init__(self, spec: BatchedProblemSpec, cfg: SolverConfig,
                 serve: ServeConfig, telemetry: MeshTelemetry,
                 resolve_x0=None, deadline_of=None, *,
                 n_devices: int, steal_log: list):
        # The hooks below read these, and super().__init__ calls them.
        self.n_devices = int(n_devices)
        self.per_device_capacity = int(serve.slab_capacity)
        self._rows = NamedSharding(make_mesh((self.n_devices,), ("serve",)),
                                   PartitionSpec("serve"))
        super().__init__(spec, cfg, serve, telemetry,
                         resolve_x0=resolve_x0, deadline_of=deadline_of)
        self.routing = serve.mesh_routing
        self.steal_threshold = int(serve.steal_threshold)
        self.dev_queues = [AdmissionQueue(serve.policy)
                           for _ in range(self.n_devices)]
        self._route_rr = 0
        self.steal_log = steal_log

    # -- hook overrides ------------------------------------------- #
    def _to_device(self, tree):
        # Straight to each device's slot block: no full-slab copy on
        # device 0 on the way.
        return jax.device_put(tree, self._rows)

    def _write_rows(self, slot: int, rows: tuple) -> None:
        # Slot s lives on device s // S_dev: the rows go to that device
        # alone, and the single-device row writer runs on its shard of
        # the slab (local slot s % S_dev).  The other shards are handed
        # back untouched when the global arrays are reassembled.
        d, local = divmod(slot, self.per_device_capacity)
        dev = self._rows.mesh.devices.flat[d]
        leaves, tree = jax.tree_util.tree_flatten(self.slab)
        shards = [{s.device: s.data for s in leaf.addressable_shards}
                  for leaf in leaves]
        out = self._row_writer(
            tree.unflatten([sh[dev] for sh in shards]),
            jax.device_put(np.int32(local), dev),
            *jax.device_put(rows, dev))
        for sh, new in zip(shards, jax.tree_util.tree_leaves(out)):
            sh[dev] = new
        self.slab = tree.unflatten([
            jax.make_array_from_single_device_arrays(
                leaf.shape, leaf.sharding, list(sh.values()))
            for leaf, sh in zip(leaves, shards)])

    def _slab_capacity(self, serve: ServeConfig) -> int:
        return self.n_devices * self.per_device_capacity

    def _record_chunk(self, wall: float, gated) -> None:
        per = self.per_device_capacity
        for d in range(self.n_devices):
            self.telemetry.device(d).record_chunk(
                live=self._live_on(d), capacity=per,
                chunk_iters=self.chunk_iters,
                wall_s=wall / self.n_devices,
                flops=self._chunk_flops(per),
                gated=None if gated is None
                else int(gated[d * per:(d + 1) * per].sum()))

    def _record_advanced(self, slot: int, iters: int) -> None:
        self.telemetry.device(
            slot // self.per_device_capacity).record_advanced(iters)

    def _record_quarantine(self, slot: int, status: str) -> None:
        # Record on the owning device's telemetry child: slot s lives on
        # device s // per_device_capacity.  MeshTelemetry.rollup() sums
        # the children back into the global counters, so health events
        # obey the same per-device conservation law as chunk counters.
        d = slot // self.per_device_capacity
        self.telemetry.device(d).record_quarantine(status)

    def _migration_allowed(self) -> bool:
        # Slot s lives on device s // per_device_capacity: the slot
        # layout IS the mesh placement, so drain-tail resizing (which
        # repacks live rows to the low slots) would re-home requests
        # across devices.  Mesh slabs keep their geometry.
        return False

    # -- per-device views ------------------------------------------ #
    def _live_on(self, d: int) -> int:
        per = self.per_device_capacity
        return int(self.active[d * per:(d + 1) * per].sum())

    def _free_on(self, d: int) -> list[int]:
        per = self.per_device_capacity
        block = self.active[d * per:(d + 1) * per]
        return [d * per + int(s) for s in np.flatnonzero(~block)]

    @property
    def pending(self) -> int:
        return super().pending + sum(len(q) for q in self.dev_queues)

    def _queues(self) -> list[AdmissionQueue]:
        # The timeout sweep must see requests already routed to a
        # device queue, not just the shared front queue.
        return [self.queue, *self.dev_queues]

    # -- two-level admission --------------------------------------- #
    def backfill(self, audit: list, tick: int) -> None:
        """Route → per-device backfill → steal, all host-side.

        1. **Route**: the shared queue drains completely, every entry
           assigned a device by :func:`route_device` (loads counted as
           live slots + already-queued entries, updated as routing
           proceeds — so one tick's burst spreads out).
        2. **Backfill**: each device fills its free slots from its own
           queue in policy order; ``warm_from`` entries whose dependency
           is still in flight are deferred back to the *shared* queue —
           re-routed next tick, when the load picture may have changed.
        3. **Steal**: devices that still have a free slot AND an empty
           local queue take one entry at a time from the victim
           :func:`steal_victim` picks, until no thief or no victim
           remains.  Each steal lands in ``steal_log`` with the
           invariant data the property tests check (a thief's local
           queue length is 0 by construction).
        """
        # 1. route
        held: list[QueueEntry] = []
        loads = [self._live_on(d) + len(self.dev_queues[d])
                 for d in range(self.n_devices)]
        while len(self.queue):
            entry = self.queue.pop()
            d, self._route_rr = route_device(self.routing, loads,
                                             self._route_rr)
            self.dev_queues[d].push(entry)
            loads[d] += 1
            self.telemetry.record_route()
            obs.instant("mesh.route", cat="mesh", tick=tick,
                        req_id=entry.req_id, device=d)

        # 2. per-device backfill
        for d in range(self.n_devices):
            free = self._free_on(d)
            q = self.dev_queues[d]
            while free and len(q):
                entry = q.pop()
                x0, ok = self._entry_x0(entry)
                if not ok:
                    held.append(entry)
                    continue
                self._stage(free.pop(0), entry, x0, audit, tick)
                audit[-1].update(device=d, stolen_from=None)

        # 3. steal at the drain tail
        while True:
            progressed = False
            for d in range(self.n_devices):
                if len(self.dev_queues[d]):
                    continue                    # has local work: not idle
                free = self._free_on(d)
                if not free:
                    continue
                qlens = [len(q) for q in self.dev_queues]
                victim = steal_victim(qlens, d, self.steal_threshold)
                if victim is None:
                    continue
                entry = self.dev_queues[victim].pop()
                progressed = True
                x0, ok = self._entry_x0(entry)
                if not ok:
                    held.append(entry)
                    continue
                self._stage(free[0], entry, x0, audit, tick)
                audit[-1].update(device=d, stolen_from=victim)
                self.steal_log.append({
                    "tick": tick, "victim": victim, "thief": d,
                    "req_id": entry.req_id,
                    "thief_queue_len": len(self.dev_queues[d]),
                    "victim_queue_len_before": qlens[victim],
                })
                self.telemetry.record_steal()
                obs.instant("mesh.steal", cat="mesh", tick=tick,
                            req_id=entry.req_id, victim=victim, thief=d)
            if not progressed:
                break

        # deferred warm_from entries: back to the shared queue
        for entry in held:
            self.queue.push(entry)


# ------------------------------------------------------------------ #
# Engine                                                              #
# ------------------------------------------------------------------ #
class MeshServeEngine(ContinuousSolverEngine):
    """Continuous batching sharded over a 1-D device mesh.

    Usage (behind the client: ``FlexaClient(backend="mesh")``)::

        eng = MeshServeEngine(SolverConfig(tol=1e-6),
                              ServeConfig(slab_capacity=4,   # per device
                                          mesh_devices=4,
                                          steal_threshold=1))
        ids = [eng.submit(r) for r in requests]
        responses = eng.drain()

    The scheduling loop, path protocol, warm starts and eviction are the
    parent's verbatim; only the slab factory changes (sharded slabs with
    two-level admission).  ``serve.mesh_devices = 0`` takes every
    visible jax device; on CPU, force a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes.
    """

    _LEGACY_NAME = "repro.serve.MeshServeEngine"
    _LEGACY_HINT = 'FlexaClient(backend="mesh").submit(...)'

    def __init__(self, cfg: SolverConfig | None = None,
                 serve: ServeConfig | None = None, *,
                 telemetry: MeshTelemetry | None = None):
        serve = serve or ServeConfig()
        avail = len(jax.devices())
        n = int(serve.mesh_devices) or avail
        if n < 1:
            raise ValueError(f"mesh_devices must be >= 0, got {n}")
        if n > avail:
            raise ValueError(
                f"mesh_devices={n} but only {avail} jax device(s) are "
                "visible; on CPU, set XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={n} in the environment BEFORE "
                "jax is imported (benchmarks/serve_load.py --devices "
                "does this for you)")
        if serve.mesh_routing not in ROUTING_POLICIES:
            raise ValueError(
                f"unknown mesh routing {serve.mesh_routing!r}; pick "
                f"from {ROUTING_POLICIES}")
        if serve.steal_threshold < 1:
            raise ValueError("steal_threshold must be >= 1 (a steal "
                             "needs at least one queued entry to take)")
        if telemetry is None:
            telemetry = MeshTelemetry(n_devices=n)
        elif isinstance(telemetry, MeshTelemetry):
            telemetry.configure(n)
        else:
            raise TypeError(
                "MeshServeEngine records chunk counters per device and "
                "needs a repro.serve.metrics.MeshTelemetry, got "
                f"{type(telemetry).__name__} — FlexaClient(backend="
                "'mesh') constructs the right one")
        self.n_devices = n
        #: Flat audit of every steal (tick, victim, thief, req_id and
        #: the queue-length facts the steal-only-when-idle property
        #: test checks).
        self.steal_log: list[dict] = []
        super().__init__(cfg, serve, telemetry=telemetry)

    def _make_slab(self, spec: BatchedProblemSpec) -> _MeshSlab:
        return _MeshSlab(spec, self.cfg, self.serve, self.telemetry,
                         resolve_x0=self._warm_solution,
                         deadline_of=self._deadlines.get,
                         n_devices=self.n_devices,
                         steal_log=self.steal_log)
