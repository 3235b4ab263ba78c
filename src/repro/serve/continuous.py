"""Continuous-batching solver runtime: slot slabs + admission scheduling.

This is the solver's serving engine (the mesh engine,
``repro.serve.mesh``, shards it over devices).  Requests do not wait for
a batch to finish: a request leaves the device the tick it converges,
and a queued one takes its slot on the next tick.

* a **slot slab** per (family × shape) signature
  (:class:`repro.solvers.batched.SlabState`) holds a fixed-capacity stack
  of live instances — the static shape XLA compiles against never
  changes;
* a compiled, buffer-donated **chunk step**
  (:func:`repro.solvers.batched.make_chunk_stepper`) advances every live
  slot by K FLEXA iterations; a slot that converges mid-chunk freezes
  exactly as in the batched run-to-convergence program, so its answer
  is independent of K and identical to a solo ``solve()``;
* after each chunk the host reads one (S,) mask, **evicts** converged
  slots and **backfills** them in place from an **admission queue**
  with FIFO / priority / earliest-deadline policies — so throughput is
  bounded by slot occupancy, not by the slowest request in the slab.
  An admitted request's data rows (its A and b) go to the device alone
  and are written in place into the donated slab at its slot
  (:func:`repro.solvers.batched.make_row_writer`: one program per
  signature, a traced slot index); the small per-slot vectors (c, x0,
  freeze mask, tol, request ids, admit mask) ride with the chunk call,
  whose fused admit phase splices the rest of the row (column norms,
  base τ, a fresh state) from the slab's own data.  So a tick ships the
  admitted rows and nothing else of the data, and stays one chunk
  program however many requests enter.

Per-request PRNG streams fold the *request id* (not the slot) into
``PRNGKey(cfg.seed)``, so a request's randomized-selection trajectory is
a pure function of (request, seed) — independent of which slot it lands
in, what else shares the slab, or when it was admitted.  That is what
makes the whole runtime deterministic under a fixed seed and arrival
trace (property-tested in ``tests/test_serve_continuous.py``).

Telemetry (latency percentiles, chunk throughput, slot occupancy, padding
waste, compile-cache counters) flows into ``repro.serve.metrics``;
``benchmarks/serve_load.py`` replays seeded arrival traces through this
runtime and writes ``results/bench/BENCH_serve.json``.
"""
from __future__ import annotations

import heapq
import itertools
import math
import time
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.config.base import ServeConfig, SolverConfig
from repro.deprecation import warn_legacy
from repro.obs import trace as obs
from repro.obs.health import (STATUS_LABELS, STATUS_RUNNING, HealthConfig,
                              SolveFailure)
from repro.serve.engine import SolveRequest, SolveResponse, validate_request
from repro.serve.pathstate import PathRequest, PathState
from repro.serve.metrics import ServeTelemetry
from repro.solvers.batched import (BatchedProblemSpec, make_chunk_stepper,
                                   make_row_writer, shipped_row_bytes,
                                   slab_alloc, slab_migrate)
from repro.solvers.compaction import bucket_capacity


@dataclass
class QueueEntry:
    """One queued request plus the scheduling facts the policies read."""
    req_id: int
    request: SolveRequest
    arrival: float
    priority: int = 0
    deadline: float | None = None


class AdmissionQueue:
    """Policy-ordered admission: FIFO, priority, or earliest-deadline.

    All three are heaps with a monotonically increasing sequence number as
    the final tie-break, so ordering is total and deterministic:

    * ``fifo``     — arrival order;
    * ``priority`` — higher ``SolveRequest.priority`` first (FIFO within
      a priority class);
    * ``deadline`` — earliest ``SolveRequest.deadline`` first (EDF);
      deadline-less requests sort after every dated one, FIFO among
      themselves.
    """

    POLICIES = ("fifo", "priority", "deadline")

    def __init__(self, policy: str = "fifo"):
        if policy not in self.POLICIES:
            raise ValueError(
                f"unknown admission policy {policy!r}; pick from "
                f"{self.POLICIES}")
        self.policy = policy
        self._heap: list = []
        self._seq = itertools.count()

    def _key(self, e: QueueEntry) -> tuple:
        if self.policy == "priority":
            return (-e.priority, e.arrival)
        if self.policy == "deadline":
            return (math.inf if e.deadline is None else float(e.deadline),
                    e.arrival)
        return (e.arrival,)

    def push(self, entry: QueueEntry) -> None:
        heapq.heappush(self._heap,
                       (self._key(entry), next(self._seq), entry))

    def pop(self) -> QueueEntry:
        return heapq.heappop(self._heap)[-1]

    def remove_if(self, pred) -> list[QueueEntry]:
        """Remove every queued entry for which ``pred(entry)`` is true;
        returns them in heap (policy) order.  An O(len) heap rebuild —
        used by policy sweeps (deadline expiry rejecting overdue entries
        before they ever touch a slot), never on the per-tick hot path.
        """
        kept, removed = [], []
        for item in self._heap:
            (removed if pred(item[-1]) else kept).append(item)
        if removed:
            heapq.heapify(kept)
            self._heap = kept
        return [item[-1] for item in sorted(removed, key=lambda t: t[:2])]

    def __len__(self) -> int:
        return len(self._heap)


class _SlotSlab:
    """Host-side bookkeeping around one device slab (one signature).

    Admissions are *staged*: :meth:`backfill` keeps each admitted
    request's data rows by reference, writes its per-slot vectors into
    reusable host buffers and flags the slot in an admit mask; the next
    :meth:`step` writes each admitted slot's rows into the donated slab
    (one small program per admission), ships the per-slot vectors with
    the chunk call, and the fused program splices + iterates in one
    dispatch.  A tick therefore costs one chunk program + one (S,) mask
    readback, plus one row write per admitted request.
    """

    #: Devices the slot axis is sharded over (the mesh slab sets more).
    n_devices = 1

    def __init__(self, spec: BatchedProblemSpec, cfg: SolverConfig,
                 serve: ServeConfig, telemetry: ServeTelemetry,
                 resolve_x0=None, deadline_of=None):
        self.spec = spec
        self.cfg = cfg
        self.capacity = int(self._slab_capacity(serve))
        self._base_capacity = self.capacity
        self._compact_drain = bool(getattr(serve, "compact_drain", False))
        self.chunk_iters = int(serve.chunk_iters)
        # Numerical-health watchdog (None = off ⇒ the chunk program
        # without the health pass).  Must be set before _make_chunk() —
        # it keys the stepper compile cache.
        self._health_cfg = HealthConfig.of(serve)
        self.telemetry = telemetry
        self.queue = AdmissionQueue(serve.policy)
        self.slab = self._to_device(slab_alloc(spec, cfg, self.capacity))
        self._health_carry = self._fresh_health(self.capacity)
        self._chunk = self._make_chunk()
        self._row_writer = make_row_writer(spec)
        # warm_from resolver: req_id -> finished solution (None = still
        # in flight, defer admission).  Injected by the engine.
        self._resolve_x0 = resolve_x0 or (lambda req_id: None)
        # Absolute-deadline resolver for the timeout sweep
        # (:meth:`expire_overdue`): req_id -> deadline or None.
        self._deadline_of = deadline_of or (lambda req_id: None)
        # Host mirrors: stop == "do not advance" (empty or finished slot).
        self.stop = np.ones(self.capacity, bool)
        self.active = np.zeros(self.capacity, bool)
        self.slot_req = np.full(self.capacity, -1, np.int64)
        # Per-slot stopping tolerance mirror — the eviction loop's
        # ``converged`` verdict must use the tolerance the slot was
        # admitted with, not the engine default.
        self.slot_tol = np.full(self.capacity, cfg.tol, np.float32)
        self._open_audit: dict = {}          # req_id -> its audit record
        self._alloc_staging()

    def _alloc_staging(self) -> None:
        """(Re)allocate the admission staging buffers at the current
        capacity — called once at construction and again by
        :meth:`_resize` whenever a drain-tail migration changes S.

        The per-slot vector buffers are reused across ticks; stale rows
        are fine — the chunk program masks them out.  Data rows are not
        staged in a buffer: ``_stage_rows`` holds each admitted slot's
        rows by reference until :meth:`step` writes them into the slab.
        """
        S = self.capacity
        spec = self.spec
        self._stage_rows: dict[int, tuple] = {}
        self._stage_nnz: dict[int, int] = {}     # sparse slabs only
        self._stage_c = np.zeros(S, np.float32)
        self._stage_x0 = np.zeros((S, spec.n), np.float32)
        self._stage_active = np.ones((S, spec.n), np.float32)
        self._stage_tol = np.full(S, self.cfg.tol, np.float32)
        self._stage_ids = np.zeros(S, np.int32)
        self._admit = np.zeros(S, bool)
        # Bytes of one admission's data rows (a sparse design padded to
        # the slab's nnz capacity), and of the per-slot vectors an
        # admitting tick ships: serve.stage's ``bytes`` is the former,
        # serve.upload's is rows × the former + the latter.
        self._row_bytes = shipped_row_bytes(spec)
        self._vector_bytes = sum(
            b.nbytes for b in (self._stage_c, self._stage_x0,
                               self._stage_ids, self._stage_active,
                               self._stage_tol, self._admit))
        # Device-resident copy of the last shipped per-slot vectors,
        # reused on ticks without admissions (no re-upload).  The
        # .copy() matters even here: jnp.asarray zero-copies aligned
        # host buffers on CPU, so without it these device arrays alias
        # the staging buffers _stage() mutates — same race class as the
        # per-tick payload below, just waiting for a code path that
        # reads the initial payload after an admission.
        self._payload = self._stage_payload()
        self._no_admit = self._to_device(np.zeros(S, bool))

    def _stage_payload(self):
        """The per-slot vector buffers as device arrays (copies — see
        the .copy() note in :meth:`step`)."""
        return self._to_device((
            self._stage_c.copy(), self._stage_x0.copy(),
            self._stage_ids.copy(), self._stage_active.copy(),
            self._stage_tol.copy()))

    def _fresh_health(self, capacity: int) -> tuple:
        """Device-resident per-slot health carry ``(prev_stat, stall)``
        at quarantine rest: +inf previous stat (any finite first-chunk
        stat counts as a decrease), zero stall count.  Empty when the
        watchdog is off: the chunk then takes and returns no carry."""
        if self._health_cfg is None:
            return ()
        return self._to_device((np.full((capacity,), np.inf, np.float32),
                                np.zeros((capacity,), np.int32)))

    # -- subclass hooks (the mesh slab reshapes and places) --------- #
    def _to_device(self, tree):
        """Place host (or device) arrays where the chunk program reads
        them: the default device.  The mesh slab shards the slot axis
        over its devices instead."""
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def _write_rows(self, slot: int, rows: tuple) -> None:
        """Ship one admitted slot's data rows to the device and write
        them into the donated slab.  The mesh slab overrides this to
        write into the owning device's shard alone."""
        self.slab = self._row_writer(self.slab, np.int32(slot),
                                     *self._to_device(rows))

    def _slab_capacity(self, serve: ServeConfig) -> int:
        return serve.slab_capacity

    def _make_chunk(self):
        return make_chunk_stepper(self.spec, self.cfg, self.chunk_iters,
                                  self.n_devices, self._health_cfg)

    def _record_chunk(self, wall: float, gated) -> None:
        """``gated``: the (S,) mask of the slots that entered the chunk
        stopped, on a sparse slab (``None`` on a dense one)."""
        self.telemetry.record_chunk(
            live=self.live, capacity=self.capacity,
            chunk_iters=self.chunk_iters, wall_s=wall,
            flops=self._chunk_flops(self.capacity),
            gated=None if gated is None else int(gated.sum()))

    def _record_advanced(self, slot: int, iters: int) -> None:
        """Iterations an evicted request advanced — the ledger's live
        work.  The mesh slab overrides this to record on the owning
        device's telemetry child."""
        self.telemetry.record_advanced(iters)

    def _record_quarantine(self, slot: int, status: str) -> None:
        """Watchdog quarantine counter — the mesh slab overrides this to
        record on the owning device's telemetry child so the per-device
        rollup conserves health events."""
        self.telemetry.record_quarantine(status)

    def _chunk_flops(self, capacity: int) -> int:
        """Matvec currency of one chunk dispatch: every slot (live or
        padding) advances ``chunk_iters`` rows at the slab's dense
        program width — the same ``row × m × n`` pricing as
        ``PathResult.device_flops``."""
        return self.chunk_iters * capacity * self.spec.m * self.spec.n

    def _migration_allowed(self) -> bool:
        """Drain-tail capacity migration opt-in.  The mesh slab
        overrides this to ``False``: its slot layout IS the device
        layout (slot s lives on device s // S_dev), so resizing would
        silently re-home requests across devices."""
        return self._compact_drain

    # ------------------------------------------------------------- #
    # Drain-tail slab compaction (ServeConfig.compact_drain)
    # ------------------------------------------------------------- #
    def _resize(self, target: int, tick: int) -> None:
        """Migrate the live slots into a slab of capacity ``target``.

        Row moves are bitwise (``slab_migrate`` copies solver state
        verbatim); what changes is the chunk *program* — jit retraces at
        the new (S, ·) shapes — so post-migration trajectories agree
        with the fixed-capacity run to solver tolerance, not bitwise
        (the determinism caveat documented in ``docs/serving.md``).
        Precondition: no staged admissions in flight (callers only
        resize when ``_admit`` is all-False), so the staging buffers can
        be reallocated without losing payloads.
        """
        old = self.capacity
        live_slots = [int(s) for s in np.flatnonzero(self.active)]
        self.slab = slab_migrate(self.slab, live_slots, self.spec,
                                 self.cfg, target)
        if self._health_carry:
            # The health carry migrates with its slots: a stalling
            # straggler keeps its stall count across a drain-tail
            # resize (conservation pinned in tests/test_health.py).
            prev_stat, stall = self._health_carry
            fresh_ps, fresh_st = self._fresh_health(int(target))
            if live_slots:
                sel = jnp.asarray(np.asarray(live_slots, np.int32))
                k = len(live_slots)
                fresh_ps = fresh_ps.at[:k].set(
                    jnp.take(prev_stat, sel, axis=0))
                fresh_st = fresh_st.at[:k].set(
                    jnp.take(stall, sel, axis=0))
            self._health_carry = (fresh_ps, fresh_st)
        self.capacity = int(target)
        self._chunk = self._make_chunk()
        stop = np.ones(self.capacity, bool)
        active = np.zeros(self.capacity, bool)
        slot_req = np.full(self.capacity, -1, np.int64)
        slot_tol = np.full(self.capacity, self.cfg.tol, np.float32)
        for new_slot, old_slot in enumerate(live_slots):
            stop[new_slot] = self.stop[old_slot]
            active[new_slot] = True
            slot_req[new_slot] = self.slot_req[old_slot]
            slot_tol[new_slot] = self.slot_tol[old_slot]
            rec = self._open_audit.get(int(self.slot_req[old_slot]))
            if rec is not None:
                rec["slot"] = new_slot
                rec.setdefault("migrations", []).append(
                    {"tick": tick, "from_slot": old_slot,
                     "to_slot": new_slot, "from_capacity": old,
                     "to_capacity": self.capacity})
        self.stop, self.active, self.slot_req = stop, active, slot_req
        self.slot_tol = slot_tol
        self._alloc_staging()
        self.telemetry.record_migration(from_capacity=old,
                                        to_capacity=self.capacity)
        obs.instant("serve.migrate", cat="continuous", tick=tick,
                    from_capacity=old, to_capacity=self.capacity,
                    live=len(live_slots))

    def _maybe_shrink(self, tick: int) -> None:
        """Shrink to the live-count capacity bucket at the drain tail:
        queue empty, nothing staged, and the stragglers fit a bucket at
        most half the current capacity (full bucket drops only — no
        thrash on ±1 fluctuations)."""
        if not self._migration_allowed():
            return
        live = self.live
        if (live > 0 and self.capacity > 1 and len(self.queue) == 0
                and not self._admit.any()):
            target = bucket_capacity(live, self._base_capacity)
            if target <= self.capacity // 2:
                self._resize(target, tick)

    def _maybe_grow(self, tick: int) -> None:
        """Grow back toward the base capacity when arrivals outnumber
        the free slots of a previously shrunk slab."""
        if not self._migration_allowed() \
                or self.capacity >= self._base_capacity:
            return
        free = int((~self.active).sum())
        if len(self.queue) > free and not self._admit.any():
            target = min(
                bucket_capacity(self.live + len(self.queue),
                                self._base_capacity),
                self._base_capacity)
            if target > self.capacity:
                self._resize(target, tick)

    # ------------------------------------------------------------- #
    @property
    def live(self) -> int:
        return int(self.active.sum())

    @property
    def pending(self) -> int:
        return len(self.queue) + self.live

    def _queues(self) -> list[AdmissionQueue]:
        """Every queue a request of this slab can wait in — the timeout
        sweep (:meth:`expire_overdue`) walks all of them.  The mesh slab
        overrides this to include its per-device queues."""
        return [self.queue]

    def _stage(self, slot: int, entry: QueueEntry, x0, audit: list,
               tick: int) -> None:
        r = entry.request
        with obs.span("serve.stage", cat="continuous", req_id=entry.req_id,
                      slot=slot, bytes=self._row_bytes):
            self._stage_rows[slot] = r.data_arrays(self.spec)
            if self.spec.layout != "dense":
                nnz = r.A.nnz
                self._stage_nnz[slot] = nnz
                self.telemetry.record_nnz(stored=nnz,
                                          capacity=self.spec.nnz_cap)
            self._stage_c[slot] = r.c
            self._stage_x0[slot] = 0.0 if x0 is None \
                else np.asarray(x0, np.float32)
            self._stage_active[slot] = 1.0 if r.active_mask is None \
                else np.asarray(r.active_mask, np.float32)
        tol = self.cfg.tol if r.tol is None else float(r.tol)
        self._stage_tol[slot] = tol
        self._stage_ids[slot] = entry.req_id
        self._admit[slot] = True
        self.active[slot] = True
        self.slot_req[slot] = entry.req_id
        self.slot_tol[slot] = tol
        self.telemetry.record_admit(entry.req_id)
        obs.instant("serve.admit", cat="continuous", tick=tick,
                    req_id=entry.req_id, slot=slot)
        rec = {"req_id": entry.req_id, "slot": slot,
               "signature": repr(self.spec), "admit_tick": tick,
               "evict_tick": None}
        audit.append(rec)
        self._open_audit[entry.req_id] = rec

    def _entry_x0(self, entry: QueueEntry):
        """``(x0, admissible)`` for one queued entry: a ``warm_from``
        dependency still in flight makes the entry inadmissible this
        tick (the caller defers it).  ``warm_from`` always references an
        earlier request id, so the dependency graph is acyclic and
        deferral can never deadlock."""
        r = entry.request
        if r.warm_from is not None:
            x0 = self._resolve_x0(r.warm_from)
            return x0, x0 is not None
        return r.x0, True

    def backfill(self, audit: list, tick: int) -> None:
        """Admit queued requests into free slots.

        A request with ``warm_from`` pointing at a still-running request
        is *deferred*: held aside for this tick and re-queued, so later
        admissible requests can take the slot (no head-of-line blocking).
        """
        self._maybe_grow(tick)
        free = [int(s) for s in np.flatnonzero(~self.active)]
        held: list[QueueEntry] = []
        while free and len(self.queue):
            entry = self.queue.pop()
            x0, ok = self._entry_x0(entry)
            if not ok:                  # dependency still in flight
                held.append(entry)
                continue
            self._stage(free.pop(0), entry, x0, audit, tick)
        for entry in held:
            self.queue.push(entry)

    def step(self, tick: int) -> list[tuple[int, SolveResponse]]:
        """One fused tick (admit + chunk); returns evictions."""
        self._maybe_shrink(tick)
        if not self.active.any():
            return []
        t0 = time.perf_counter()
        # Slots that enter the chunk stopped (empty or finished): on a
        # sparse slab the products skip them (gate_designs).
        gated = None if self.spec.layout == "dense" \
            else self.stop & ~self._admit
        # NOTE the .copy() on every staging-buffer→device crossing:
        # jnp.asarray zero-copies aligned host buffers on CPU, and these
        # staging buffers are mutated on later ticks — an alias would
        # race the async chunk dispatch (observed as admissions silently
        # reading all-False masks under load).  Data rows need none: the
        # row writer copies them into the slab, and the engine never
        # writes to a request's arrays.
        if self._admit.any():
            slots = [int(s) for s in np.flatnonzero(self._admit)]
            sparse = {} if self.spec.layout == "dense" else {
                "nnz": sum(self._stage_nnz.pop(s) for s in slots),
                "nnz_cap": len(slots) * self.spec.nnz_cap}
            with obs.span("serve.upload", cat="continuous", tick=tick,
                          rows=len(slots),
                          bytes=len(slots) * self._row_bytes
                          + self._vector_bytes, **sparse):
                for slot in slots:
                    self._write_rows(slot, self._stage_rows.pop(slot))
                self._payload = self._stage_payload()
                admit = self._to_device(self._admit.copy())
            self._admit[:] = False
        else:
            admit = self._no_admit
        with obs.span("serve.chunk", cat="continuous", tick=tick,
                      live=self.live, capacity=self.capacity,
                      chunk_iters=self.chunk_iters,
                      **({} if gated is None
                         else {"gated": int(gated.sum())})):
            self.slab, flags, *carry = self._chunk(
                self.slab, self._to_device(self.stop.copy()), admit,
                *self._payload, *self._health_carry)
            self._health_carry = tuple(carry)
            # The one per-chunk host sync (copy: host mirror is
            # mutated): the bool stop mask, or with the watchdog on the
            # int32 verdict vector (0=running / 1=stopped / 2=diverged /
            # 3=stalled) while the health carry stays device-resident.
            flags = np.array(flags)
        status = flags if self._health_carry else None
        stop = flags if status is None else status != STATUS_RUNNING
        wall = time.perf_counter() - t0
        self._record_chunk(wall, gated)

        if self.telemetry.sample_progress:
            # Opt-in residual sampling for dashboard sparklines — one
            # extra (S,) readback pair per tick, gated so the default
            # run never pays it.
            state = self.slab.state
            ks_all = np.asarray(state.k)
            stats_all = np.asarray(state.stat)
            for slot in np.flatnonzero(self.active):
                self.telemetry.record_progress(
                    int(self.slot_req[slot]), iters=int(ks_all[slot]),
                    stat=float(stats_all[slot]))

        finished = np.flatnonzero(stop & self.active)
        out = []
        if finished.size:
            # Pull the whole (S, ·) result arrays and index on the host:
            # device-side fancy indexing would compile a fresh gather per
            # distinct eviction count.
            with obs.span("serve.collect", cat="continuous", tick=tick,
                          evicted=int(finished.size)):
                state = self.slab.state
                xs = np.asarray(state.x)[finished]
                ks = np.asarray(state.k)[finished]
                stats = np.asarray(state.stat)[finished]
            for j, slot in enumerate(finished):
                req_id = int(self.slot_req[slot])
                # Quarantine verdicts ("diverged"/"stalled") ride the
                # same eviction path as healthy completions, so the
                # exactly-once-service audit invariants hold unchanged.
                verdict = "ok" if status is None else \
                    STATUS_LABELS.get(int(status[slot]), "ok")
                resp = SolveResponse(
                    x=xs[j], iters=int(ks[j]),
                    converged=bool(stats[j] <= self.slot_tol[slot]),
                    stat=float(stats[j]), bucket=self.capacity,
                    status=verdict)
                out.append((req_id, resp))
                self.telemetry.record_completion(
                    req_id, iters=resp.iters, converged=resp.converged,
                    status=verdict)
                self._record_advanced(int(slot), resp.iters)
                if verdict != "ok":
                    self._record_quarantine(int(slot), verdict)
                    obs.instant("serve.quarantine", cat="continuous",
                                tick=tick, req_id=req_id,
                                slot=int(slot), status=verdict,
                                iters=resp.iters)
                obs.instant("serve.evict", cat="continuous", tick=tick,
                            req_id=req_id, slot=int(slot),
                            iters=resp.iters, converged=resp.converged)
                rec = self._open_audit.pop(req_id)
                rec["evict_tick"] = tick
                rec["status"] = verdict
                self.active[slot] = False
                self.slot_req[slot] = -1
        self.stop = stop
        return out

    def expire_overdue(self, now: float,
                       tick: int) -> list[tuple[int, SolveResponse]]:
        """Evict every request whose absolute deadline has passed.

        Opt-in: nothing fires unless the caller (the remote server's
        tick loop, or a test) invokes it — inline ``drain()`` users see
        identical behavior to before the sweep existed.  Two kinds of
        victims, both surfaced as ``status="timeout"`` responses:

        * **queued** entries (never admitted): removed from the
          admission queue(s) and answered with their own ``x0`` (or
          zeros) at ``iters=0`` — no audit record exists to close, by
          the exactly-once-service invariant (audit rows are created at
          admission).
        * **live** slots: the slot's current iterate is read back and
          returned (best effort so far), the open audit record is
          closed with ``status="timeout"``, and the slot is freed
          through the same host-mirror path as a normal eviction.
        """
        out: list[tuple[int, SolveResponse]] = []

        def overdue(e: QueueEntry) -> bool:
            return e.deadline is not None and float(e.deadline) <= now

        for q in self._queues():
            for entry in q.remove_if(overdue):
                r = entry.request
                x = np.zeros(self.spec.n, np.float32) if r.x0 is None \
                    else np.asarray(r.x0, np.float32)
                resp = SolveResponse(
                    x=x, iters=0, converged=False, stat=float("inf"),
                    bucket=self.capacity, status="timeout")
                out.append((entry.req_id, resp))
                self.telemetry.record_completion(
                    entry.req_id, iters=0, converged=False,
                    status="timeout")
                self.telemetry.record_timeout()
                obs.instant("serve.timeout", cat="continuous", tick=tick,
                            req_id=entry.req_id, queued=True)

        live_overdue = [int(s) for s in np.flatnonzero(self.active)
                        if (d := self._deadline_of(int(self.slot_req[s])))
                        is not None and float(d) <= now]
        if live_overdue:
            state = self.slab.state
            xs = np.asarray(state.x)
            ks = np.asarray(state.k)
            stats = np.asarray(state.stat)
            for slot in live_overdue:
                req_id = int(self.slot_req[slot])
                if self._admit[slot]:
                    # Staged but not yet shipped to the device: the slab
                    # row still holds a previous request's state, so
                    # answer with the staged x0 and cancel the admit.
                    self._admit[slot] = False
                    self._stage_rows.pop(slot, None)
                    self._stage_nnz.pop(slot, None)
                    resp = SolveResponse(
                        x=self._stage_x0[slot].copy(), iters=0,
                        converged=False, stat=float("inf"),
                        bucket=self.capacity, status="timeout")
                else:
                    resp = SolveResponse(
                        x=xs[slot], iters=int(ks[slot]), converged=False,
                        stat=float(stats[slot]), bucket=self.capacity,
                        status="timeout")
                    self._record_advanced(slot, resp.iters)
                out.append((req_id, resp))
                self.telemetry.record_completion(
                    req_id, iters=resp.iters, converged=False,
                    status="timeout")
                self.telemetry.record_timeout()
                obs.instant("serve.timeout", cat="continuous", tick=tick,
                            req_id=req_id, slot=slot, queued=False,
                            iters=resp.iters)
                rec = self._open_audit.pop(req_id)
                rec["evict_tick"] = tick
                rec["status"] = "timeout"
                self.active[slot] = False
                self.slot_req[slot] = -1
                self.stop[slot] = True
        return out


class ContinuousSolverEngine:
    """Serve solve requests through slot slabs with continuous batching.

    Usage::

        eng = ContinuousSolverEngine(SolverConfig(tol=1e-6),
                                     ServeConfig(slab_capacity=8,
                                                 chunk_iters=16))
        ids = [eng.submit(r) for r in requests]
        responses = eng.drain()            # {req_id: SolveResponse}

    ``submit`` only enqueues (cheap, host-side); device work happens in
    :meth:`step` — one scheduler tick: backfill free slots from the
    admission queue, advance every slab one chunk, evict what converged.
    :meth:`drain` ticks until nothing is queued or live.  Interleaving
    ``submit`` and ``step`` is the online mode the load generator drives.

    Determinism: with a fixed ``cfg.seed`` and a fixed submission order,
    responses, audit log and telemetry iteration counts are reproducible
    — admission order is a pure function of the queue policy, and each
    request's PRNG stream is keyed by its request id alone.
    """

    #: Legacy-warning identity; subclasses (the mesh engine) announce
    #: themselves under their own name, still once per process each.
    _LEGACY_NAME = "repro.serve.ContinuousSolverEngine"
    _LEGACY_HINT = 'FlexaClient(backend="continuous").submit(...)'

    def __init__(self, cfg: SolverConfig | None = None,
                 serve: ServeConfig | None = None, *,
                 telemetry: ServeTelemetry | None = None):
        warn_legacy(self._LEGACY_NAME, self._LEGACY_HINT)
        self.cfg = cfg or SolverConfig()
        self.serve = serve or ServeConfig()
        if self.serve.slab_capacity < 1:
            raise ValueError("slab_capacity must be >= 1")
        if self.serve.chunk_iters < 1:
            raise ValueError("chunk_iters must be >= 1")
        AdmissionQueue(self.serve.policy)    # validate policy eagerly
        self.telemetry = telemetry or ServeTelemetry()
        self._slabs: dict[BatchedProblemSpec, _SlotSlab] = {}
        self._responses: dict[int, SolveResponse] = {}
        self._spec_of: dict[int, BatchedProblemSpec] = {}
        #: Flat audit log of slot assignments (one record per admission,
        #: closed at eviction) — the substrate of the no-double-booking
        #: and determinism property tests.
        self.audit: list[dict] = []
        #: Typed quarantine outcomes, in eviction order (empty unless
        #: ``ServeConfig.watchdog`` is on and a solve went unhealthy).
        self.failures: list[SolveFailure] = []
        self._tick = 0
        # Round-robin cursor over slabs (multi-signature fairness).
        self._rr = 0
        # req_id -> absolute deadline, for the opt-in timeout sweep
        # (:meth:`expire_overdue`); slabs resolve through .get.
        self._deadlines: dict[int, float] = {}
        # In-flight regularization paths (PathRequest).
        self._paths: dict[int, PathState] = {}
        self._path_of_req: dict[int, int] = {}
        self._path_ids = itertools.count()

    # ------------------------------------------------------------- #
    @property
    def pending(self) -> int:
        """Requests submitted but not yet completed."""
        return sum(s.pending for s in self._slabs.values())

    @property
    def queued(self) -> int:
        """Requests waiting in admission queues (not yet in a slot) —
        the dashboard's queue-depth signal."""
        return sum(len(s.queue) for s in self._slabs.values())

    def submit(self, request: SolveRequest, *,
               arrival: float | None = None) -> int:
        """Enqueue one request; returns its request id."""
        spec = request.spec
        validate_request(request, spec)
        if request.warm_from is not None:
            ref_spec = self._spec_of.get(request.warm_from)
            if ref_spec is None:
                raise ValueError(
                    f"warm_from={request.warm_from}: unknown request id "
                    "(must reference an earlier request of this engine)")
            if ref_spec != spec:
                raise ValueError(
                    f"warm_from={request.warm_from}: signature mismatch "
                    f"({ref_spec} vs {spec}) — a warm start only makes "
                    "sense within one (family × shape) signature")
        # Ids come from the telemetry so a telemetry shared between
        # engines (apples-to-apples comparisons) never collides.
        req_id = self.telemetry.next_request_id()
        t = self.telemetry.now() if arrival is None else arrival
        self.telemetry.record_arrival(req_id, spec.family, "continuous",
                                      t=t)
        self._spec_of[req_id] = spec
        if request.deadline is not None:
            self._deadlines[req_id] = float(request.deadline)
        slab = self._slabs.get(spec)
        if slab is None:
            slab = self._slabs[spec] = self._make_slab(spec)
        slab.queue.push(QueueEntry(
            req_id=req_id, request=request, arrival=t,
            priority=request.priority, deadline=request.deadline))
        return req_id

    def _make_slab(self, spec: BatchedProblemSpec) -> _SlotSlab:
        """Slab factory — the mesh engine overrides this to hand out
        sharded slabs with per-device queues."""
        return _SlotSlab(spec, self.cfg, self.serve, self.telemetry,
                         resolve_x0=self._warm_solution,
                         deadline_of=self._deadlines.get)

    def _warm_solution(self, req_id: int):
        """x0 for a ``warm_from`` admission (None = still in flight)."""
        resp = self._responses.get(req_id)
        return None if resp is None else resp.x

    def submit_path(self, preq: PathRequest, *,
                    arrival: float | None = None) -> int:
        """Enqueue a whole λ-path; returns its *path id*.

        Only the first λ-point is submitted now; each completion triggers
        the KKT recheck and then the next point's warm-started, screened
        admission (all inside :meth:`step`).  Progress/result:
        :meth:`path_result`.
        """
        path_id = next(self._path_ids)
        st = PathState(path_id, preq)
        self._paths[path_id] = st
        req_id = self.submit(st.next_request(), arrival=arrival)
        st.req_ids.append(req_id)
        self._path_of_req[req_id] = path_id
        return path_id

    def path_result(self, path_id: int) -> dict:
        """Snapshot of one path's progress (``done``, per-λ solutions,
        iterations, screening counters, request ids)."""
        return self._paths[path_id].result()

    def step(self) -> list[int]:
        """One scheduler tick: backfill → chunk → evict, over the slabs
        this tick services.

        Slabs are visited in round-robin rotation; with
        ``ServeConfig.slabs_per_tick = k > 0`` only k slabs are serviced
        per tick (every slab is reached within ⌈n_slabs/k⌉ ticks — the
        fairness guarantee the starvation test pins).  Completions
        belonging to a :class:`PathRequest` trigger the KKT recheck and
        the next point's admission before the tick returns.

        Returns the request ids completed this tick (their responses are
        available in :attr:`responses`).
        """
        self._tick += 1
        done = []
        slabs = list(self._slabs.values())
        if slabs:
            per_tick = self.serve.slabs_per_tick or len(slabs)
            start = self._rr % len(slabs)
            order = slabs[start:] + slabs[:start]
            serviced = order[:per_tick]
            self._rr = (start + per_tick) % len(slabs)
            with obs.span("serve.tick", cat="continuous",
                          tick=self._tick, slabs=len(serviced),
                          queued=self.queued):
                for slab in serviced:
                    slab.backfill(self.audit, self._tick)
                    for req_id, resp in slab.step(self._tick):
                        self._responses[req_id] = resp
                        done.append(req_id)
                        if resp.status != "ok":
                            self.failures.append(SolveFailure(
                                req_id=req_id, status=resp.status,
                                iters=resp.iters, stat=resp.stat,
                                tick=self._tick))
        # Path advancement happens after the slab sweep: it may submit
        # follow-up requests (possibly creating new slabs), which must
        # not mutate the dict mid-iteration.
        for req_id in done:
            path_id = self._path_of_req.get(req_id)
            if path_id is None:
                continue
            st = self._paths[path_id]
            follow_up = st.on_completion(self._responses[req_id])
            if follow_up is not None:
                new_id = self.submit(follow_up)
                st.req_ids.append(new_id)
                self._path_of_req[new_id] = path_id
        return done

    def expire_overdue(self, now: float | None = None) -> list[int]:
        """Evict every request whose absolute ``deadline`` has passed
        (``status="timeout"`` through the normal eviction path — audit
        closed, telemetry counted, a :class:`SolveFailure` appended).

        Opt-in: deadlines are inert until something calls this — the
        remote server's tick loop does, between :meth:`step` calls.  A
        timed-out request that belongs to a path terminates the whole
        path (its remaining points would warm-start from a solution that
        never arrived).  Returns the expired request ids.
        """
        now = self.telemetry.now() if now is None else float(now)
        expired = []
        for slab in list(self._slabs.values()):
            for req_id, resp in slab.expire_overdue(now, self._tick):
                self._responses[req_id] = resp
                self._deadlines.pop(req_id, None)
                expired.append(req_id)
                self.failures.append(SolveFailure(
                    req_id=req_id, status="timeout", iters=resp.iters,
                    stat=resp.stat, tick=self._tick))
                path_id = self._path_of_req.get(req_id)
                if path_id is not None:
                    self._paths[path_id].done = True
        return expired

    def drain(self) -> dict[int, SolveResponse]:
        """Tick until every submitted request has completed."""
        while self.pending:
            self.step()
        return dict(self._responses)

    @property
    def responses(self) -> dict[int, SolveResponse]:
        return self._responses
