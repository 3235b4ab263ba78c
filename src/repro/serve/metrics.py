"""Serve telemetry: one recorder per serving session.

The ROADMAP's serving goal is latency/throughput under heavy concurrent
traffic.  :class:`ServeTelemetry` records

* the **request lifecycle** — arrival (submit), admission (first device
  iteration), completion — from which queue wait, service time and
  end-to-end latency (p50/p99/mean) derive;
* **chunk-level** counters of the serving engines — chunks executed,
  FLEXA iterations per second of device wall, slot occupancy (live slots /
  slab capacity, weighted per chunk), padding waste (idle-slot row
  iterations), and the iterations evicted requests actually advanced,
  which split the occupied slots' row iterations into live work and
  freeze (a slot held after it converged inside a chunk);
* the **compile caches** (``repro.solvers.cache``) — hit/miss/eviction/
  size per cache, so a serving process can see whether its signatures fit
  the ``REPRO_COMPILE_CACHE_SIZE`` budget.

Timestamps come from an injectable ``clock`` (default
``time.perf_counter``); the load generator swaps in a simulated clock so
latency percentiles are reproducible under a virtual arrival timeline.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field

import numpy as np

from repro.obs.ledger import CostLedger
from repro.solvers.cache import cache_stats


#: Wire-format version of :meth:`ServeTelemetry.snapshot`.  Bump it
#: whenever a snapshot key changes meaning or disappears (additions are
#: compatible); consumers (``repro.obs.dashboard --snapshot/--follow``,
#: the remote server's ``/snapshot`` endpoint) reject snapshots whose
#: schema they do not understand instead of mis-rendering them.
SNAPSHOT_SCHEMA = 1


def percentile(values, q: float):
    """Linear-interpolation percentile; ``None`` on an empty sample."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclass
class RequestTrace:
    """Lifecycle timestamps and outcome of one solve request."""
    req_id: int
    family: str
    arrival: float
    admitted: float | None = None
    completed: float | None = None
    iters: int = 0
    converged: bool = False
    engine: str = ""    # "inline" | "continuous" (mesh too) | "remote"
    #: "ok" | "diverged" | "stalled" (watchdog quarantine verdicts) |
    #: "timeout" (deadline eviction via ``expire_overdue``).
    status: str = "ok"
    samples: list = field(default_factory=list)  # (t, iters, stat) triples

    @property
    def queue_wait(self) -> float | None:
        if self.admitted is None:
            return None
        return self.admitted - self.arrival

    @property
    def latency(self) -> float | None:
        if self.completed is None:
            return None
        return self.completed - self.arrival

    def as_dict(self) -> dict:
        """Plain-dict view for dashboards / ticket diagnostics."""
        return {
            "req_id": self.req_id, "family": self.family,
            "engine": self.engine, "arrival": self.arrival,
            "admitted": self.admitted, "completed": self.completed,
            "queue_wait": self.queue_wait, "latency": self.latency,
            "iters": self.iters, "converged": self.converged,
            "status": self.status,
            "samples": list(self.samples),
        }


def _chunk_summary(t: "ServeTelemetry") -> dict:
    """The continuous-engine chunk counters of one telemetry as a
    snapshot dict.  Used both for the global ``"continuous"`` section
    and for each per-device entry of :class:`MeshTelemetry`, so the two
    views can never drift: the raw counters (``chunks``,
    ``chunk_iters``, ``row_iters``, ``live_iters`` (the occupied slots'
    rows), ``advanced_iters``, ``chunk_wall_s``) are additive across
    devices — the conservation law the mesh rollup
    property tests pin — while the occupancy/waste ratios derive from
    them per view."""
    row = t.chunk_row_iters
    return {
        "chunks": t.chunks,
        "chunk_iters": t.chunk_iters,
        "row_iters": row,
        "live_iters": t.chunk_live_iters,
        "advanced_iters": t.chunk_advanced_iters,
        "device_flops": t.chunk_flops,
        "occupancy_mean": t.chunk_live_iters / row if row else 0.0,
        "padding_waste": ((row - t.chunk_live_iters) / row
                          if row else 0.0),
        "freeze_waste": ((t.chunk_live_iters - t.chunk_advanced_iters)
                         / row if row else 0.0),
        "chunk_wall_s": t.chunk_wall,
        "iters_per_s": (t.chunk_live_iters / t.chunk_wall
                        if t.chunk_wall > 0 else None),
        "migrations": t.migrations,
    }


@dataclass
class ServeTelemetry:
    """Mutable counters an engine appends to as it serves."""
    clock: object = time.perf_counter
    requests: dict = field(default_factory=dict)    # req_id -> RequestTrace
    _req_ids: object = field(default_factory=itertools.count)
    # continuous-engine chunk counters
    chunks: int = 0
    chunk_iters: int = 0            # Σ K over chunks (per-slot iterations)
    chunk_row_iters: int = 0        # Σ K·capacity (device row iterations)
    chunk_live_iters: int = 0       # Σ K·live     (occupied-slot rows)
    chunk_advanced_iters: int = 0   # Σ iters of evicted requests
    chunk_flops: int = 0            # Σ K·capacity·m·n (matvec currency)
    chunk_wall: float = 0.0
    migrations: int = 0             # drain-tail slab capacity changes
    # sparse-design admissions: nonzeros stored, and the slab capacity
    # they occupied (their nnz bucket); 1 − stored/capacity is the
    # layout's padding
    nnz_stored: int = 0
    nnz_capacity: int = 0
    # sparse-slab chunks: slots that entered a chunk stopped (empty or
    # finished), whose products the kernel skips, and all slots (S a
    # chunk); a lower bound on the slot-iterations skipped, since a slot
    # that stops inside a chunk is skipped for the rest of it too
    gated_slot_chunks: int = 0
    slot_chunks: int = 0
    # opt-in per-chunk residual sampling (dashboard sparklines); off by
    # default so no extra device readback happens unless requested
    sample_progress: bool = False
    # numerical-health watchdog quarantine counters (repro.obs.health)
    quarantined_diverged: int = 0
    quarantined_stalled: int = 0
    # deadline evictions (ContinuousSolverEngine.expire_overdue)
    timeouts: int = 0
    # sliding-window SLO metrics (repro.obs.windows): horizon in clock
    # seconds; 0 = disabled.  Opt-in because feeding windows costs
    # extra clock reads, which would perturb byte-reproducible traces
    # under injected clocks.
    window_s: float = 0.0
    _windows: object = None

    def now(self) -> float:
        return float(self.clock())

    def windows(self):
        """The lazily created :class:`repro.obs.windows.MetricWindows`
        (``None`` when ``window_s`` is 0/unset)."""
        if not self.window_s or self.window_s <= 0:
            return None
        if self._windows is None:
            from repro.obs.windows import MetricWindows
            self._windows = MetricWindows(horizon=self.window_s)
        return self._windows

    def next_request_id(self) -> int:
        """Allocate a request id unique within this telemetry.

        Engines draw their ids from here so that a telemetry shared
        between engines (the apples-to-apples comparison mode) never
        sees two requests under one id; with a per-engine telemetry the
        ids count from 0 exactly as before.
        """
        return next(self._req_ids)

    # ------------------------------------------------------------- #
    # request lifecycle
    # ------------------------------------------------------------- #
    def record_arrival(self, req_id: int, family: str, engine: str,
                       t: float | None = None) -> None:
        self.requests[req_id] = RequestTrace(
            req_id=req_id, family=family, engine=engine,
            arrival=self.now() if t is None else t)

    def record_admit(self, req_id: int, t: float | None = None) -> None:
        self.requests[req_id].admitted = self.now() if t is None else t

    def record_completion(self, req_id: int, *, iters: int, converged: bool,
                          status: str = "ok",
                          t: float | None = None) -> None:
        r = self.requests[req_id]
        r.completed = self.now() if t is None else t
        r.iters = int(iters)
        r.converged = bool(converged)
        r.status = str(status)
        w = self.windows()
        if w is not None:
            # Completion timestamp doubles as the window sample time —
            # no extra clock read on the completion path.
            w.add("completions", r.completed, 1.0)
            if r.latency is not None:
                w.add("latency", r.completed, r.latency)
            if r.queue_wait is not None:
                w.add("queue_wait", r.completed, r.queue_wait)

    def record_quarantine(self, status: str, t: float | None = None) -> None:
        """One watchdog quarantine event ("diverged" or "stalled")."""
        if status == "diverged":
            self.quarantined_diverged += 1
        elif status == "stalled":
            self.quarantined_stalled += 1
        else:
            raise ValueError(f"unknown quarantine status {status!r}")
        w = self.windows()
        if w is not None:
            w.add("health_events", self.now() if t is None else t, 1.0)

    def record_timeout(self, t: float | None = None) -> None:
        """One deadline eviction (``status="timeout"``).  Distinct from
        :meth:`record_quarantine` — a timeout is a *policy* outcome, not
        a numerical-health verdict, so it gets its own counter."""
        self.timeouts += 1
        w = self.windows()
        if w is not None:
            w.add("timeouts", self.now() if t is None else t, 1.0)

    def record_progress(self, req_id: int, *, iters: int, stat: float,
                        t: float | None = None) -> None:
        """One sampled (time, iters, residual-stat) point for a request.

        No-op unless :attr:`sample_progress` is on — engines gate the
        device readback on the same flag, so the default run does not
        pay for sampling it never records."""
        if not self.sample_progress:
            return
        r = self.requests.get(req_id)
        if r is not None:
            r.samples.append((self.now() if t is None else t,
                              int(iters), float(stat)))

    # ------------------------------------------------------------- #
    # engine-side counters
    # ------------------------------------------------------------- #
    def record_chunk(self, *, live: int, capacity: int, chunk_iters: int,
                     wall_s: float, flops: int = 0,
                     gated: int | None = None) -> None:
        """One chunk; ``gated`` (sparse slabs only) is the number of its
        slots that entered it stopped."""
        if gated is not None:
            self.gated_slot_chunks += int(gated)
            self.slot_chunks += capacity
        self.chunks += 1
        self.chunk_iters += chunk_iters
        self.chunk_row_iters += chunk_iters * capacity
        self.chunk_live_iters += chunk_iters * live
        self.chunk_flops += int(flops)
        self.chunk_wall += wall_s
        w = self.windows()
        if w is not None:
            # One clock read per chunk, paid only with windows enabled.
            w.add("occupancy", self.now(),
                  live / capacity if capacity else 0.0)

    def record_advanced(self, iters: int) -> None:
        """Iterations one evicted request advanced (its ``k``)."""
        self.chunk_advanced_iters += int(iters)

    def record_nnz(self, *, stored: int, capacity: int) -> None:
        """One sparse design admitted: its nonzeros, and the nnz
        capacity of the slot it occupies."""
        self.nnz_stored += int(stored)
        self.nnz_capacity += int(capacity)

    def record_migration(self, *, from_capacity: int,
                         to_capacity: int) -> None:
        """One drain-tail slab migration (capacities for dashboards only;
        the counter is what the conservation tests use)."""
        self.migrations += 1

    # ------------------------------------------------------------- #
    # aggregation
    # ------------------------------------------------------------- #
    def latencies(self) -> list:
        return [r.latency for r in self.requests.values()
                if r.latency is not None]

    def ledger(self) -> CostLedger:
        """Unified :class:`~repro.obs.ledger.CostLedger` over everything
        this telemetry recorded.

        Continuous chunks: ``live_iters`` is what evicted requests
        advanced (Σ of their ``k``), ``freeze_iters`` the rest of the
        occupied slots' rows (K·occupied − advanced: slots held after
        converging inside a chunk), ``padding_iters`` the empty slots'
        rows (K·(capacity − occupied)).  A request still in a slot has
        its rows in ``freeze_iters`` until its eviction moves them to
        ``live_iters``, so the split is exact once the engine has
        drained.  ``compiles`` counts
        the process-wide compile-cache misses (``cache_stats``) — the
        same source the snapshot's ``compile_cache`` section reports."""
        led = CostLedger()
        occupied = self.chunk_live_iters
        led.add(row_iters=self.chunk_row_iters,
                live_iters=self.chunk_advanced_iters,
                freeze_iters=occupied - self.chunk_advanced_iters,
                padding_iters=self.chunk_row_iters - occupied,
                device_flops=self.chunk_flops)
        led.add(compiles=sum(c["misses"]
                             for c in cache_stats().values()))
        return led

    def snapshot(self) -> dict:
        """Everything a dashboard (or ``BENCH_serve.json``) wants."""
        lats = self.latencies()
        waits = [r.queue_wait for r in self.requests.values()
                 if r.queue_wait is not None]
        completed = [r for r in self.requests.values()
                     if r.completed is not None]
        out = {
            "schema": SNAPSHOT_SCHEMA,
            "requests": len(self.requests),
            "completed": len(completed),
            "in_flight": len(self.requests) - len(completed),
            "converged": sum(r.converged for r in completed),
            "iters_total": sum(r.iters for r in completed),
            "latency_p50": percentile(lats, 50),
            "latency_p99": percentile(lats, 99),
            "latency_mean": (float(np.mean(lats)) if lats else None),
            "latency_max": (float(np.max(lats)) if lats else None),
            "queue_wait_p50": percentile(waits, 50),
            "queue_wait_p99": percentile(waits, 99),
            "ledger": self.ledger().as_dict(),
            "compile_cache": cache_stats(),
        }
        if (self.quarantined_diverged or self.quarantined_stalled
                or self.timeouts):
            out["health"] = {
                "quarantined": (self.quarantined_diverged
                                + self.quarantined_stalled),
                "diverged": self.quarantined_diverged,
                "stalled": self.quarantined_stalled,
                "timeouts": self.timeouts,
            }
        w = self.windows()
        if w is not None:
            out["windows"] = w.snapshot(self.now())
        if self.chunks:
            out["continuous"] = _chunk_summary(self)
        if self.nnz_capacity:
            out["sparse"] = {
                "nnz_stored": self.nnz_stored,
                "nnz_capacity": self.nnz_capacity,
                "nnz_pad_share": 1.0 - self.nnz_stored / self.nnz_capacity,
                "gated_slot_chunks": self.gated_slot_chunks,
                "slot_chunks": self.slot_chunks,
                "gate_share": (self.gated_slot_chunks / self.slot_chunks
                               if self.slot_chunks else 0.0)}
        return out


@dataclass
class MeshTelemetry(ServeTelemetry):
    """Telemetry of the mesh-sharded engine: one child
    :class:`ServeTelemetry` per mesh device plus mesh-only counters.

    The request lifecycle (arrival / admit / completion) stays global —
    a request is one request however many devices exist — while chunk
    counters are recorded *per device* (``engine → telemetry.device(d).
    record_chunk(...)``) and rolled up into the inherited global fields
    by :meth:`rollup`.  The rollup is literally ``sum over devices`` for
    every raw counter, so the global view is the sum of the parts *by
    construction*; the property tests re-derive the sums independently
    from the snapshot to pin it.

    ``n_devices=0`` defers sizing until the engine knows its mesh
    (:meth:`configure`); the children share the parent's clock so all
    timestamps live on one timeline.
    """
    n_devices: int = 0
    steals: int = 0                 # queue entries moved by work stealing
    routed: int = 0                 # entries routed shared → device queue
    per_device: list = field(default_factory=list)

    def __post_init__(self):
        if self.n_devices:
            self.configure(self.n_devices)

    def configure(self, n_devices: int) -> None:
        """Size the per-device children (idempotent at the same size)."""
        n = int(n_devices)
        if self.per_device:
            if len(self.per_device) != n:
                raise ValueError(
                    f"telemetry already configured for "
                    f"{len(self.per_device)} devices, engine wants {n} — "
                    "one MeshTelemetry serves one mesh size")
            return
        self.n_devices = n
        self.per_device = [ServeTelemetry(clock=self.clock)
                           for _ in range(n)]

    def device(self, d: int) -> ServeTelemetry:
        """The chunk-counter recorder of mesh device ``d``."""
        return self.per_device[d]

    def record_steal(self, n: int = 1) -> None:
        self.steals += int(n)

    def record_route(self, n: int = 1) -> None:
        self.routed += int(n)

    def rollup(self) -> None:
        """Global chunk counters := Σ per-device chunk counters."""
        self.chunks = sum(t.chunks for t in self.per_device)
        self.chunk_iters = sum(t.chunk_iters for t in self.per_device)
        self.chunk_row_iters = sum(t.chunk_row_iters
                                   for t in self.per_device)
        self.chunk_live_iters = sum(t.chunk_live_iters
                                    for t in self.per_device)
        self.chunk_advanced_iters = sum(t.chunk_advanced_iters
                                        for t in self.per_device)
        self.chunk_flops = sum(t.chunk_flops for t in self.per_device)
        self.chunk_wall = sum(t.chunk_wall for t in self.per_device)
        self.gated_slot_chunks = sum(t.gated_slot_chunks
                                     for t in self.per_device)
        self.slot_chunks = sum(t.slot_chunks for t in self.per_device)
        # Health events are recorded on the owning device's child (the
        # mesh slab's _record_quarantine hook), so the global counters
        # are the per-device sum — same conservation law as the chunk
        # counters above.
        self.quarantined_diverged = sum(t.quarantined_diverged
                                        for t in self.per_device)
        self.quarantined_stalled = sum(t.quarantined_stalled
                                       for t in self.per_device)

    def ledger(self) -> CostLedger:
        self.rollup()
        return super().ledger()

    def snapshot(self) -> dict:
        self.rollup()
        out = super().snapshot()
        out["mesh"] = {
            "devices": self.n_devices,
            "steals": self.steals,
            "routed": self.routed,
            "per_device": [_chunk_summary(t) for t in self.per_device],
        }
        return out
