"""Pluggable execution backends behind the one client front door.

A backend is *how* a normalized :class:`~repro.client.specs.WorkItem`
gets executed — never *what* it computes.  Every registered backend
runs the same Algorithm-1 mathematics, so switching
``ClientConfig.backend`` changes scheduling, latency and device
utilization, but results agree with the inline reference (≤1e-5 under
tol-stopping; bit-identical where the very same compiled program runs —
the equivalence matrix in ``tests/test_client.py`` pins this):

* ``inline``     — in-process: the method registry for solos, the
  batched vmap+while_loop program for batches, the homotopy driver for
  paths/CV.  No admission control.
* ``continuous`` — :class:`~repro.serve.continuous.
  ContinuousSolverEngine`: slot-slab continuous batching with
  eviction/backfill; paths/CV ride the engine's point-by-point
  admission (:class:`~repro.serve.pathstate.PathState`).  The backend
  for sustained concurrent traffic.
* ``mesh``       — :class:`~repro.serve.mesh.MeshServeEngine`: the
  continuous runtime sharded over a 1-D device mesh (one slab shard +
  admission queue per device, shared-queue routing, work stealing).
  Same WorkItem capabilities as ``continuous``
  (``ServeConfig.mesh_devices``).
* ``remote``     — a ``repro.remote`` solver service over HTTP
  (registered by ``repro.remote.backend`` on first use); it validates
  like the serving backends, since the server runs one.

Backends construct the legacy engines under
:func:`repro.deprecation.internal_use`, so the client never triggers
the legacy-entry-point FutureWarnings it exists to retire.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from repro.client.errors import (UnknownBackendError,
                                 UnsupportedWorkloadError)
from repro.client.specs import (SERVE_PATH_FAMILIES, BatchResult, CVResult,
                                SoloResult, WorkItem, mse_score,
                                solve_request_of)
from repro.config.base import ClientConfig, SolverConfig
from repro.deprecation import internal_use
from repro.obs.ledger import CostLedger
from repro.path.driver import (PathResult, _problem_at, _solve_path,
                               _solve_path_batched)
from repro.path.grid import geometric_grid, lambda_max, validate_grid
from repro.path.screening import ScreenReport
from repro.problems.families import get_family, infer_family
from repro.serve.metrics import ServeTelemetry


# ------------------------------------------------------------------ #
# Shared result plumbing                                             #
# ------------------------------------------------------------------ #
def _dims(problem) -> tuple[int, int]:
    """(m, n) pricing dims of a registry-family instance — the matvec
    currency every ledger uses.  (0, 0) for ad-hoc problems whose
    leading data array is not a 2-D operator (their device cost is not
    expressible in the shared currency, so it is reported as zero
    rather than guessed).  Read from the design's shape alone: a
    device-resident design is not copied, a sparse one not densified."""
    try:
        fam = infer_family(problem)
        A = problem.data[get_family(fam).data_keys[0]]
    except (ValueError, KeyError):
        return 0, 0
    shape = A.shape if hasattr(A, "shape") else np.shape(A)
    return (int(shape[0]), int(shape[1])) if len(shape) == 2 else (0, 0)


def _request_ledger(iter_counts, problems) -> CostLedger:
    """Per-request useful-work pricing: each request's own iterations at
    its own (m, n).  Slab/bucket *waste* (padding + freeze rows) is a
    scheduling property, accounted once in the session telemetry ledger
    — pricing it per request would double-count it across tickets."""
    led = CostLedger()
    for it, p in zip(iter_counts, problems):
        it = int(it)
        m, n = _dims(p)
        led.add(row_iters=it, live_iters=it, device_flops=it * m * n)
    return led


def _solo_result(resp, backend: str, problem=None) -> SoloResult:
    """Normalize a serve ``SolveResponse`` onto the client contract."""
    led = (None if problem is None
           else _request_ledger([resp.iters], [problem]))
    return SoloResult(x=np.asarray(resp.x), iters=int(resp.iters),
                      converged=bool(resp.converged),
                      stat=float(resp.stat), backend=backend, raw=resp,
                      ledger=led,
                      status=str(getattr(resp, "status", "ok")))


def _batch_result(resps, backend: str, problems=None) -> BatchResult:
    led = (None if problems is None
           else _request_ledger([r.iters for r in resps], problems))
    return BatchResult(
        x=np.stack([np.asarray(r.x) for r in resps]),
        iters=np.asarray([int(r.iters) for r in resps], np.int64),
        converged=np.asarray([bool(r.converged) for r in resps], bool),
        stat=np.asarray([float(r.stat) for r in resps]),
        backend=backend, raw=list(resps), ledger=led,
        status=[str(getattr(r, "status", "ok")) for r in resps])


def _path_result_from_serve(problem, d: dict, backend: str) -> PathResult:
    """Assemble the shared :class:`PathResult` contract from the serve
    path protocol's progress dict (``PathState.result()``)."""
    lambdas = np.asarray(d["lambdas"], np.float64)
    xs = np.asarray(d["x"], np.float32)
    P = lambdas.shape[0]
    n_blocks, bs = problem.n_blocks, problem.block_size
    V = np.array([float(_problem_at(problem, float(lambdas[k])).v(
        jnp.asarray(xs[k]))) for k in range(P)])
    support = np.array([
        int(np.count_nonzero(np.linalg.norm(
            xs[k].reshape(n_blocks, bs), axis=-1)))
        for k in range(P)], np.int64)
    screened_out = np.asarray(d["screened_out"], np.int64)
    kkt_rounds = np.asarray(d["kkt_rounds"], np.int64)
    iters = np.asarray(d["iters"], np.int64)
    led = _request_ledger([int(iters.sum())], [problem])
    return PathResult(
        lambdas=lambdas, x=xs, V=V,
        iters=iters,
        converged=np.asarray(d["converged"], bool),
        support=support,
        active_blocks=n_blocks - screened_out,
        screened=[ScreenReport(n_blocks=n_blocks,
                               screened_out=int(screened_out[k]),
                               kkt_rounds=int(kkt_rounds[k]))
                  for k in range(P)],
        # Per-request iteration total; slab/bucket device accounting
        # (padding + freeze waste) lives in the session telemetry.
        row_iters=int(iters.sum()),
        device_flops=led.device_flops,
        lam_max=float(d["lam_max"]),
        meta={"backend": backend, "source": "serve"},
        ledger=led)


def _scorer(spec):
    if spec.score is not None:
        return spec.score
    if spec.validation is not None:
        return mse_score(spec.validation)
    return None


def _cv_select(item: WorkItem, folds: list) -> dict:
    """Score a finished sweep; returns scores/best or empties."""
    score = _scorer(item.spec)
    if score is None:
        return {"scores": None, "scores_mean": None, "best_index": None,
                "best_lambda": None}
    K, P = len(folds), int(folds[0].lambdas.shape[0])
    scores = np.array([[score(i, k, folds[i].x[k]) for k in range(P)]
                       for i in range(K)])
    mean = scores.mean(axis=0)
    best = int(np.argmin(mean))
    return {"scores": scores, "scores_mean": mean, "best_index": best,
            "best_lambda": float(folds[0].lambdas[best])}


def _resolve_cv_grid(item: WorkItem) -> np.ndarray:
    """The shared fold grid (anchored at the largest fold λ_max), the
    same resolution rule as the lockstep driver."""
    spec = item.spec
    if spec.lambdas is not None:
        return validate_grid(spec.lambdas)
    lam = max(lambda_max(p) for p in item.problems)
    return geometric_grid(lam, n_points=spec.n_points,
                          lam_min_ratio=spec.lam_min_ratio)


def _winner_problems(item: WorkItem, best_lambda: float) -> list:
    return [_problem_at(p, best_lambda) for p in item.problems]


def _finish_cv(item: WorkItem, folds: list, backend: str,
               x_best: np.ndarray | None, select: dict,
               meta: dict, ledger: CostLedger | None = None) -> CVResult:
    if select["best_index"] is not None and x_best is None:
        # Full-tolerance sweep: the winner column IS the answer.
        x_best = np.stack([f.x[select["best_index"]] for f in folds])
    return CVResult(folds=folds, lambdas=folds[0].lambdas,
                    backend=backend, x_best=x_best,
                    meta={**meta,
                          "tol_coarse": item.spec.tol_coarse},
                    ledger=ledger, **select)


def _cv_ledger(folds: list, resolve_led: CostLedger | None,
               shared: bool = False) -> CostLedger:
    """Sweep cost + (optional) winner re-solve cost.

    Serve-side folds each carry their own per-request ledger (sum them);
    the inline lockstep sweep attaches one *sweep-wide* ledger copy to
    every fold (``shared=True``), where summing would K-fold overcount —
    take one copy instead.
    """
    leds = [f.ledger for f in folds if f.ledger is not None]
    led = CostLedger()
    if shared and leds:
        led = leds[0].copy()
    else:
        for fold_led in leds:
            led.merge(fold_led)
    if resolve_led is not None:
        led.merge(resolve_led)
    return led


# ------------------------------------------------------------------ #
# Backend protocol + registry                                        #
# ------------------------------------------------------------------ #
class Backend:
    """Execution strategy for normalized work items.

    Contract: ``submit`` may complete eagerly (returns the tickets it
    finished); ``step`` advances asynchronous work one scheduler round
    and returns the tickets completed by that round; ``pending`` counts
    accepted-but-unfinished tickets; ``result`` returns a completed
    ticket's normalized result (``None`` while in flight).  ``validate``
    rejects workloads this strategy cannot execute — *before* any state
    changes.
    """

    name = "?"

    def __init__(self, config: ClientConfig, telemetry: ServeTelemetry):
        self.config = config
        self.telemetry = telemetry
        self._results: dict[int, object] = {}

    # -- protocol -------------------------------------------------- #
    def validate(self, item: WorkItem) -> None:
        pass

    def submit(self, item: WorkItem, arrival=None) -> list[int]:
        raise NotImplementedError

    def step(self) -> list[int]:
        return []

    @property
    def pending(self) -> int:
        return 0

    def result(self, ticket: int):
        return self._results.get(ticket)

    def request_ids(self, ticket: int) -> list[int]:
        """Engine request ids a ticket spawned (diagnostics feed).

        Backends with no per-ticket request mapping report ``[]`` —
        their aggregate view is ``stats()``/telemetry.
        """
        return []

    def stats(self) -> dict:
        return {"backend": self.name}

    def close(self) -> None:
        pass

    # -- shared validation helpers --------------------------------- #
    def _validate_served(self, item: WorkItem) -> None:
        """The capability envelope of the serving engines: what the
        ``continuous`` and ``mesh`` backends accept, and the ``remote``
        one, whose server runs a serving engine."""
        if item.kind == "solo":
            self._require_flexa_solo(item)
            self._require_registry_family(item)
        elif item.kind == "batch":
            self._require_registry_family(item)
            if item.spec.record_history:
                raise UnsupportedWorkloadError(
                    "record_history is an inline-backend feature (the "
                    "serving engines never sync per iteration)")
        else:
            self._require_serveable_path(item)

    def _require_registry_family(self, item: WorkItem) -> None:
        if item.family is None:
            raise UnsupportedWorkloadError(
                f"the {self.name!r} backend serves registered problem "
                "families only (its payload is the raw family data "
                "arrays); ad-hoc or mixed-family problems run on the "
                "'inline' backend")

    def _require_flexa_solo(self, item: WorkItem) -> None:
        spec = item.spec
        if spec.method != "flexa" or spec.options:
            raise UnsupportedWorkloadError(
                f"the {self.name!r} backend executes the paper's FLEXA "
                f"solver; method={spec.method!r} with options="
                f"{spec.options!r} runs on the 'inline' backend")

    def _require_serveable_path(self, item: WorkItem) -> None:
        self._require_registry_family(item)
        if item.family not in SERVE_PATH_FAMILIES:
            raise UnsupportedWorkloadError(
                f"the serve-side path protocol covers the quadratic "
                f"screenable families {SERVE_PATH_FAMILIES}; family "
                f"{item.family!r} paths run on the 'inline' backend")
        spec = item.spec
        if getattr(spec, "lam_batch", 1) != 1:
            raise UnsupportedWorkloadError(
                "lam_batch chunking is an inline-backend feature (the "
                "serving engines admit paths point by point)")
        if spec.tol_schedule is not None:
            raise UnsupportedWorkloadError(
                "per-point tol_schedule is an inline-backend feature; "
                "serve backends support the tol_coarse continuation "
                "(CVSpec) instead")
        if getattr(spec, "compact", False):
            raise UnsupportedWorkloadError(
                "compact active-set packing is an inline-backend path "
                "feature (the serve engines compact at the slab level "
                "via ServeConfig.compact_drain instead)")


_BACKENDS: dict[str, type] = {}


def register_backend(cls: type) -> type:
    """Register a :class:`Backend` subclass under ``cls.name``."""
    if cls.name in _BACKENDS:
        raise ValueError(f"backend {cls.name!r} already registered")
    _BACKENDS[cls.name] = cls
    return cls


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def make_backend(config: ClientConfig,
                 telemetry: ServeTelemetry) -> Backend:
    if config.backend == "remote" and "remote" not in _BACKENDS:
        # The remote backend lives in its own package (repro.remote) so
        # the client core never imports networking code; load it on
        # first use — the import registers the backend.
        import repro.remote.backend  # noqa: F401
    try:
        cls = _BACKENDS[config.backend]
    except KeyError:
        raise UnknownBackendError(
            f"unknown backend {config.backend!r}; available: "
            f"{available_backends()}") from None
    return cls(config, telemetry)


# ------------------------------------------------------------------ #
# Inline backend                                                     #
# ------------------------------------------------------------------ #
@register_backend
class InlineBackend(Backend):
    """In-process execution: the reference semantics every other
    backend is measured against."""

    name = "inline"

    def __init__(self, config, telemetry):
        super().__init__(config, telemetry)
        self._ticket_rids: dict[int, list[int]] = {}

    def _begin_requests(self, item: WorkItem, arrival) -> list[int]:
        """Synthesize the request lifecycle the serve engines record
        natively, so ``FlexaClient.diagnostics()`` has per-request
        traces on this backend too.  Inline admits instantly: arrival
        and admit share one timestamp (one per-problem request; a path
        ticket is one request — its per-λ fan-out is an engine-side
        notion)."""
        tele = self.telemetry
        n = 1 if item.kind in ("solo", "path") else len(item.problems)
        family = item.family or "adhoc"
        rids = []
        for _ in range(n):
            rid = tele.next_request_id()
            t = tele.now() if arrival is None else arrival
            tele.record_arrival(rid, family, self.name, t=t)
            tele.record_admit(rid, t=t)
            rids.append(rid)
        self._ticket_rids[item.ticket] = rids
        return rids

    def _finish_requests(self, item: WorkItem, rids: list[int]) -> None:
        res = self._results[item.ticket]
        if item.kind == "solo":
            stats = [(int(res.iters),
                      bool(np.asarray(res.converged).all()))]
        elif item.kind == "batch":
            stats = [(int(i), bool(c))
                     for i, c in zip(np.ravel(res.iters),
                                     np.ravel(res.converged))]
        elif item.kind == "path":
            stats = [(int(np.asarray(res.iters).sum()),
                      bool(np.asarray(res.converged).all()))]
        else:                                   # cv: one trace per fold
            stats = [(int(np.asarray(f.iters).sum()),
                      bool(np.asarray(f.converged).all()))
                     for f in res.folds]
        for rid, (iters, conv) in zip(rids, stats):
            self.telemetry.record_completion(rid, iters=iters,
                                             converged=conv)

    def request_ids(self, ticket: int) -> list[int]:
        return list(self._ticket_rids.get(ticket, []))

    def submit(self, item: WorkItem, arrival=None) -> list[int]:
        cfg = self.config.solver
        spec = item.spec
        rids = self._begin_requests(item, arrival)
        if item.kind == "solo":
            from repro.solvers.api import _solve
            r = _solve(spec.problem, method=spec.method, cfg=cfg,
                       x0=spec.x0, **spec.options)
            stat = getattr(r, "state", None)
            self._results[item.ticket] = SoloResult(
                x=np.asarray(r.x), iters=int(r.iters),
                converged=bool(np.asarray(r.converged).all()),
                stat=None if stat is None or not hasattr(stat, "stat")
                else float(np.asarray(stat.stat)),
                backend=self.name, raw=r,
                ledger=_request_ledger([r.iters], [spec.problem]))
        elif item.kind == "batch":
            from repro.solvers.batched import _solve_batched
            r = _solve_batched(item.problems, x0=spec.x0, cfg=cfg,
                               record_history=spec.record_history,
                               active=spec.active)
            self._results[item.ticket] = BatchResult(
                x=np.asarray(r.x), iters=np.asarray(r.iters),
                converged=np.asarray(r.converged),
                stat=np.asarray(r.state.stat) if r.state is not None
                else None,
                backend=self.name, raw=r,
                ledger=self._batch_ledger(item, np.asarray(r.iters)))
        elif item.kind == "path":
            self._results[item.ticket] = _solve_path(
                spec.problem, spec.lambdas, n_points=spec.n_points,
                lam_min_ratio=spec.lam_min_ratio, cfg=cfg,
                warm=spec.warm, screen=spec.screen,
                kkt_slack=spec.kkt_slack, lam_batch=spec.lam_batch,
                tol_schedule=spec.tol_schedule, compact=spec.compact,
                clock=self.telemetry.clock)
        elif item.kind == "cv":
            self._results[item.ticket] = self._run_cv(item, cfg)
        self._finish_requests(item, rids)
        return [item.ticket]

    @staticmethod
    def _batch_ledger(item: WorkItem, iters: np.ndarray) -> CostLedger:
        """Lockstep vmap pricing: the device runs every instance for the
        slowest instance's iteration count (frozen rows thereafter)."""
        B = len(item.problems)
        row = int(iters.max()) * B if B else 0
        live = int(iters.sum())
        m, n = _dims(item.problems[0]) if B else (0, 0)
        led = CostLedger()
        led.add(row_iters=row, live_iters=live, freeze_iters=row - live,
                device_flops=row * m * n)
        return led

    def _run_cv(self, item: WorkItem, cfg: SolverConfig) -> CVResult:
        spec = item.spec
        sweep_cfg = (cfg if spec.tol_coarse is None
                     else dataclasses.replace(cfg, tol=spec.tol_coarse))
        folds = _solve_path_batched(
            item.problems, spec.lambdas, n_points=spec.n_points,
            lam_min_ratio=spec.lam_min_ratio, cfg=sweep_cfg,
            warm=spec.warm, screen=spec.screen,
            kkt_slack=spec.kkt_slack, tol_schedule=spec.tol_schedule,
            clock=self.telemetry.clock)
        select = _cv_select(item, folds)
        x_best = None
        resolve_led = None
        if select["best_index"] is not None \
                and spec.tol_coarse is not None:
            # Coarse-to-fine continuation: only the winner gets the
            # full-accuracy re-solve, warm-started from its coarse
            # solution (unscreened, so exactness needs no KKT loop).
            from repro.solvers.batched import _solve_batched
            probs = _winner_problems(item, select["best_lambda"])
            x0 = np.stack([f.x[select["best_index"]] for f in folds])
            r = _solve_batched(probs, x0=x0, cfg=cfg)
            x_best = np.asarray(r.x)
            resolve_led = self._batch_ledger(item, np.asarray(r.iters))
        return _finish_cv(item, folds, self.name, x_best, select,
                          meta={"mode": "lockstep"},
                          ledger=_cv_ledger(folds, resolve_led,
                                            shared=True))


# ------------------------------------------------------------------ #
# Continuous backend                                                 #
# ------------------------------------------------------------------ #
class _ContTicket:
    """Per-ticket progress over the continuous engine."""

    def __init__(self, item: WorkItem):
        self.item = item
        self.req_ids: list[int] = []        # solo/batch requests
        self.path_ids: list[int] = []       # path/cv paths
        self.grid = None
        self.phase = "run"                  # "run" | "resolve"
        self.folds = None
        self.select = None
        self.resolve_ids: list[int] = []


@register_backend
class ContinuousBackend(Backend):
    """Slot-slab continuous batching over
    :class:`ContinuousSolverEngine` — admit on submit, advance on
    ``step``, results as slots converge and are evicted.

    ONE engine serves everything this backend runs.  The CV coarse
    sweep used to demand a second engine at the coarse tolerance; slabs
    now carry a per-slot tolerance vector, so the sweep simply submits
    its path requests with ``tol=tol_coarse`` and shares slots (and the
    compiled chunk program) with full-accuracy traffic — which is also
    what lets a remote server multiplex tenants with different
    tolerances onto one engine."""

    name = "continuous"

    def __init__(self, config, telemetry):
        super().__init__(config, telemetry)
        self._eng = None
        self._live: dict[int, _ContTicket] = {}
        self._done: dict[int, _ContTicket] = {}     # diagnostics feed

    def _make_engine(self):
        from repro.serve.continuous import ContinuousSolverEngine
        return ContinuousSolverEngine(self.config.solver,
                                      self.config.serve,
                                      telemetry=self.telemetry)

    def _engine(self):
        if self._eng is None:
            with internal_use():
                self._eng = self._make_engine()
        return self._eng

    validate = Backend._validate_served

    @staticmethod
    def _path_request(spec, problem, grid, tol=None, priority=0,
                      deadline=None):
        """The serve path protocol's request for one instance.  ``tol``
        is the per-request stopping tolerance (the CV coarse sweep)."""
        from repro.serve.pathstate import PathRequest
        return PathRequest(
            A=np.asarray(problem.data["A"], np.float32),
            b=np.asarray(problem.data["b"], np.float32),
            lambdas=grid, n_points=spec.n_points,
            lam_min_ratio=spec.lam_min_ratio,
            block_size=int(problem.block_size), warm=spec.warm,
            screen=spec.screen, kkt_slack=spec.kkt_slack, tol=tol,
            priority=priority, deadline=deadline)

    def submit(self, item: WorkItem, arrival=None) -> list[int]:
        rec = _ContTicket(item)
        eng = self._engine()
        pr, dl = item.priority, item.deadline
        if item.kind == "solo":
            rec.req_ids = [eng.submit(
                solve_request_of(item.problems[0], x0=item.spec.x0,
                                 priority=pr, deadline=dl),
                arrival=arrival)]
        elif item.kind == "batch":
            x0, act = item.spec.x0, item.spec.active
            rec.req_ids = [eng.submit(solve_request_of(
                p, x0=None if x0 is None else x0[i],
                active=None if act is None else act[i],
                priority=pr, deadline=dl),
                arrival=arrival) for i, p in enumerate(item.problems)]
        else:
            spec = item.spec
            grid = (_resolve_cv_grid(item) if item.kind == "cv"
                    else spec.lambdas)
            rec.grid = grid
            tol = getattr(spec, "tol_coarse", None)
            rec.path_ids = [eng.submit_path(
                self._path_request(spec, p, grid, tol=tol,
                                   priority=pr, deadline=dl),
                arrival=arrival)
                for p in item.problems]
        self._live[item.ticket] = rec
        return []

    @property
    def pending(self) -> int:
        return len(self._live)

    def step(self) -> list[int]:
        if self._eng is not None and self._eng.pending:
            self._eng.step()
        done = []
        for ticket in list(self._live):
            rec = self._live[ticket]
            result = self._advance(rec)
            if result is not None:
                self._results[ticket] = result
                self._done[ticket] = self._live.pop(ticket)
                done.append(ticket)
        return done

    def expire_overdue(self, now: float | None = None) -> list[int]:
        """Deadline sweep passthrough (the remote server calls this
        between ticks); returns the expired engine request ids.  Their
        tickets complete — with ``status="timeout"`` entries — on the
        next :meth:`step`."""
        if self._eng is None:
            return []
        return self._eng.expire_overdue(now)

    def request_ids(self, ticket: int) -> list[int]:
        rec = self._live.get(ticket) or self._done.get(ticket)
        if rec is None:
            return []
        ids = list(rec.req_ids)
        if rec.path_ids:
            eng = self._engine()
            for pid in rec.path_ids:
                ids.extend(eng.path_result(pid)["req_ids"])
        ids.extend(rec.resolve_ids)
        return ids

    def _advance(self, rec: _ContTicket):
        item = rec.item
        eng = self._engine()
        if item.kind in ("solo", "batch"):
            resps = [eng.responses.get(r) for r in rec.req_ids]
            if any(r is None for r in resps):
                return None
            if item.kind == "solo":
                return _solo_result(resps[0], self.name,
                                    item.problems[0])
            return _batch_result(resps, self.name, item.problems)

        if rec.phase == "run":
            results = [eng.path_result(pid) for pid in rec.path_ids]
            if not all(r["done"] for r in results):
                return None
            folds = [_path_result_from_serve(item.problems[i],
                                             results[i], self.name)
                     for i in range(len(results))]
            if item.kind == "path":
                return folds[0]
            select = _cv_select(item, folds)
            if select["best_index"] is None \
                    or item.spec.tol_coarse is None:
                return _finish_cv(item, folds, self.name, None, select,
                                  meta={"mode": "continuous"},
                                  ledger=_cv_ledger(folds, None))
            # Phase 2: winner re-solve at the engine's default (full)
            # tolerance — same engine, the requests just omit tol.
            rec.phase, rec.folds, rec.select = "resolve", folds, select
            best = select["best_index"]
            probs = _winner_problems(item, select["best_lambda"])
            rec.resolve_ids = [eng.submit(solve_request_of(
                p, x0=folds[i].x[best])) for i, p in enumerate(probs)]
            return None
        resps = [eng.responses.get(r) for r in rec.resolve_ids]
        if any(r is None for r in resps):
            return None
        x_best = np.stack([np.asarray(r.x) for r in resps])
        return _finish_cv(item, rec.folds, self.name, x_best,
                          rec.select, meta={"mode": "continuous"},
                          ledger=_cv_ledger(rec.folds, _request_ledger(
                              [r.iters for r in resps], item.problems)))

    def stats(self) -> dict:
        return {"backend": self.name,
                "pending": self.pending,
                "queued": (0 if self._eng is None
                           else getattr(self._eng, "queued", 0))}


# ------------------------------------------------------------------ #
# Mesh backend                                                        #
# ------------------------------------------------------------------ #
@register_backend
class MeshBackend(ContinuousBackend):
    """Device-mesh continuous batching over
    :class:`~repro.serve.mesh.MeshServeEngine` — the continuous
    backend's protocol verbatim (admit on submit, advance on ``step``),
    with the slabs sharded one block per mesh device.

    The engine requires a :class:`~repro.serve.metrics.MeshTelemetry`;
    :class:`~repro.client.session.FlexaClient` constructs one when the
    backend is ``"mesh"``, so per-device occupancy and steal counters
    surface through ``client.stats()`` like every other telemetry
    field.
    """

    name = "mesh"

    def _make_engine(self):
        from repro.serve.mesh import MeshServeEngine
        return MeshServeEngine(self.config.solver, self.config.serve,
                               telemetry=self.telemetry)
