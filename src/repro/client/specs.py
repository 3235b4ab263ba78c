"""Typed workload specs and the one internal :class:`WorkItem` they
normalize onto.

The paper's framework is one algorithm family over "virtually all"
scheduling regimes; the client mirrors that: one *spec* per workload
kind —

* :class:`SoloSpec`  — one instance, any registered method;
* :class:`BatchSpec` — B same-signature instances, one compiled program;
* :class:`PathSpec`  — a warm-started, screened λ-path over one instance;
* :class:`CVSpec`    — K folds down one λ-grid, optionally scored and
  λ-selected (the cross-validation workload), with coarse-to-fine tol
  continuation;

— and every spec validates + normalizes into the same :class:`WorkItem`
shape, which is all an execution backend ever sees.  Specs are plain
data (no jax imports at construction), so building one never touches
device state.

Result contracts: solo → :class:`SoloResult`, batch →
:class:`BatchResult`, path → :class:`~repro.path.driver.PathResult`
(shared with the legacy driver on purpose), cv → :class:`CVResult` —
identical fields whichever backend executed the work.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

from repro.client.errors import SpecError, UnsupportedWorkloadError
from repro.obs.ledger import CostLedger
from repro.path.driver import PathResult
from repro.path.screening import DEFAULT_KKT_SLACK
from repro.problems.base import Problem
from repro.problems.families import get_family, infer_family
from repro.problems.sparse import is_sparse
from repro.serve.engine import SolveRequest

#: Families a *serving* backend can carry (its request payload is the
#: raw data arrays).  Ad-hoc F closures are inline-only.
KINDS = ("solo", "batch", "path", "cv")

#: Families the serve-side path protocol (``repro.serve.pathstate``)
#: supports: the screenable quadratic ones with an (A, b) payload.
SERVE_PATH_FAMILIES = ("lasso", "group_lasso")


# ------------------------------------------------------------------ #
# Specs                                                              #
# ------------------------------------------------------------------ #
@dataclass
class SoloSpec:
    """One composite-minimization instance, any registered method.

    ``method``/``options`` reach the solver registry exactly as the old
    facade's arguments did; non-FLEXA methods and method-specific
    options are inline-backend-only (the serving engines run the paper's
    Algorithm 1).
    """
    problem: Problem
    method: str = "flexa"
    x0: np.ndarray | None = None
    options: dict = field(default_factory=dict)


@dataclass
class BatchSpec:
    """B independent instances sharing one shape signature."""
    problems: Sequence[Problem] = ()
    x0: np.ndarray | None = None        # (B, n) warm starts
    active: np.ndarray | None = None    # (B, n) freeze masks
    record_history: bool = False        # inline-only (host-stepped driver)


@dataclass
class PathSpec:
    """A warm-started, strong-rule-screened regularization path."""
    problem: Problem
    lambdas: object = None              # explicit decreasing grid or None
    n_points: int = 20
    lam_min_ratio: float = 0.01
    warm: bool = True
    screen: bool = True
    kkt_slack: float = DEFAULT_KKT_SLACK
    lam_batch: int = 1                  # inline-only λ-chunking
    tol_schedule: object = None         # per-point stopping tolerances
    compact: bool = False               # capacity-bucketed active-set
                                        # packing (inline-only; needs
                                        # screen=True)


@dataclass
class CVSpec:
    """K folds swept down one shared λ-grid, optionally scored.

    Scoring: ``score(fold_index, lambda_index, x) -> float`` (lower is
    better), or ``validation`` — a list of K ``(A_val, b_val)`` pairs
    scored by mean squared error (the quadratic-family default).  With
    neither, the result is a pure lockstep fold sweep (``best_*`` fields
    are ``None``) — exactly the legacy ``solve_path_batched`` contract.

    ``tol_coarse`` is the continuation knob: the sweep runs at this
    loose tolerance and only the *selected* λ is re-solved at the full
    ``SolverConfig.tol`` (warm-started from the coarse winner), so model
    selection pays full accuracy once instead of P times.  Requires
    scoring (without a winner there is nothing to re-solve), and is
    mutually exclusive with an explicit ``tol_schedule`` (which would
    silently override the coarse sweep).
    """
    problems: Sequence[Problem] = ()
    lambdas: object = None
    n_points: int = 20
    lam_min_ratio: float = 0.01
    warm: bool = True
    screen: bool = True
    kkt_slack: float = DEFAULT_KKT_SLACK
    tol_schedule: object = None         # sweep schedule (advanced)
    tol_coarse: float | None = None     # coarse sweep + full-tol winner
    score: Callable | None = None       # (i_fold, i_lambda, x) -> float
    validation: Sequence | None = None  # K (A_val, b_val) pairs


# ------------------------------------------------------------------ #
# Results                                                            #
# ------------------------------------------------------------------ #
@dataclass
class SoloResult:
    """One solved instance, backend-independent fields first."""
    x: np.ndarray
    iters: int
    converged: bool
    stat: float | None              # final ‖x̂−x‖∞ (None: method w/o it)
    backend: str
    raw: object = None              # SolverResult (inline) / SolveResponse
    ledger: CostLedger | None = None    # unified per-request accounting
    status: str = "ok"              # "ok" | "diverged" | "stalled"

    @property
    def history(self):
        """Trajectory dict when the executing driver recorded one."""
        h = getattr(self.raw, "history", None)
        return h or {}


@dataclass
class BatchResult:
    """B solved instances (leading axis B everywhere)."""
    x: np.ndarray                   # (B, n)
    iters: np.ndarray               # (B,)
    converged: np.ndarray           # (B,)
    stat: np.ndarray | None         # (B,)
    backend: str
    raw: object = None              # SolverResult (inline) / responses
    ledger: CostLedger | None = None    # unified batch-wide accounting
    status: list | None = None      # per-instance "ok"/"diverged"/"stalled"

    def __len__(self) -> int:
        return int(self.x.shape[0])


@dataclass
class CVResult:
    """K fold paths + (optionally) the selected λ and its solutions."""
    folds: list                     # K PathResult
    lambdas: np.ndarray             # (P,) shared grid
    backend: str
    scores: np.ndarray | None = None        # (K, P) per-fold scores
    scores_mean: np.ndarray | None = None   # (P,)
    best_index: int | None = None
    best_lambda: float | None = None
    x_best: np.ndarray | None = None        # (K, n) full-tol winners
    meta: dict = field(default_factory=dict)
    ledger: CostLedger | None = None        # unified sweep accounting


@dataclass
class TicketDiagnostics:
    """Per-request lifecycle view of one client ticket — the dashboard's
    sparkline feed (``FlexaClient.diagnostics``).

    ``requests`` holds one :meth:`RequestTrace.as_dict` per engine
    request the ticket spawned (solo/batch requests, every λ-point of a
    path, CV winner re-solves); the ``samples`` lists inside are
    populated when ``telemetry.sample_progress`` is on.  Every backend
    (serve, inline) keeps the ticket → request-id mapping, so the
    feed is populated regardless of execution mode.
    """
    ticket: int
    kind: str
    backend: str
    done: bool
    requests: list = field(default_factory=list)

    def as_dict(self) -> dict:
        return {"ticket": self.ticket, "kind": self.kind,
                "backend": self.backend, "done": self.done,
                "requests": list(self.requests)}


# ------------------------------------------------------------------ #
# Normalization                                                      #
# ------------------------------------------------------------------ #
@dataclass
class WorkItem:
    """What a backend executes: kind + validated spec + derived facts.

    ``priority``/``deadline`` are service-policy annotations (SLO class
    mapped by the remote server, defaults for direct use): the serve
    backends thread them into every engine request the item spawns, so
    the admission heaps and the timeout sweep see them; the inline
    backend ignores them.
    """
    ticket: int
    kind: str                       # one of KINDS
    spec: object
    problems: list                  # the instances (1 / B / 1 / K)
    family: str | None              # registry family, None for ad-hoc F
    priority: int = 0
    deadline: float | None = None   # absolute telemetry-clock time


def _family_of(problem: Problem) -> str | None:
    try:
        family = infer_family(problem)
    except ValueError:
        return None
    missing = [k for k in get_family(family).data_keys
               if k not in problem.data]
    return None if missing else family


def solve_request_of(problem: Problem, *, x0=None, active=None,
                     priority: int = 0,
                     deadline: float | None = None) -> SolveRequest:
    """The serve-engine payload of a registry-family :class:`Problem`.

    The leading family data array rides in ``SolveRequest.A`` whatever
    the family calls it (the engines' convention); quadratic families
    add ``b``.
    """
    family = infer_family(problem)
    keys = get_family(family).data_keys
    arrays = [problem.data[k] if is_sparse(problem.data[k])
              else np.asarray(problem.data[k], np.float32) for k in keys]
    return SolveRequest(
        A=arrays[0], b=arrays[1] if len(arrays) > 1 else None,
        c=float(problem.g_weight), block_size=int(problem.block_size),
        family=family,
        x0=None if x0 is None else np.asarray(x0, np.float32),
        active_mask=None if active is None
        else np.asarray(active, np.float32),
        priority=priority, deadline=deadline)


def mse_score(validation: Sequence) -> Callable:
    """The quadratic-family default scorer: per-fold validation MSE."""
    def score(i_fold: int, i_lambda: int, x) -> float:
        Av, bv = validation[i_fold]
        r = np.asarray(Av) @ np.asarray(x) - np.asarray(bv)
        return float(r @ r) / np.asarray(Av).shape[0]
    return score


def _require_dense(problems, kind: str) -> None:
    """Paths and CV sweeps screen and rescale dense designs: a sparse
    design is served as a solo or a batch."""
    if any(is_sparse(v) for p in problems for v in p.data.values()):
        raise UnsupportedWorkloadError(
            f"a {kind} over a sparse design is not supported; sparse "
            "designs run as SoloSpec or BatchSpec")


def normalize(spec, ticket: int) -> WorkItem:
    """Validate a user spec and fold it onto the internal representation.

    Raises :class:`SpecError` on malformed input — always before any
    device work, so rejection is atomic whatever the backend.
    """
    if isinstance(spec, SoloSpec):
        if not isinstance(spec.problem, Problem):
            raise SpecError(f"SoloSpec.problem must be a Problem, got "
                            f"{type(spec.problem).__name__}")
        return WorkItem(ticket=ticket, kind="solo", spec=spec,
                        problems=[spec.problem],
                        family=_family_of(spec.problem))
    if isinstance(spec, BatchSpec):
        probs = list(spec.problems)
        if not probs:
            raise SpecError("BatchSpec needs at least one problem")
        fams = {_family_of(p) for p in probs}
        return WorkItem(ticket=ticket, kind="batch", spec=spec,
                        problems=probs,
                        family=fams.pop() if len(fams) == 1 else None)
    if isinstance(spec, PathSpec):
        if not isinstance(spec.problem, Problem):
            raise SpecError(f"PathSpec.problem must be a Problem, got "
                            f"{type(spec.problem).__name__}")
        _require_dense([spec.problem], "path")
        return WorkItem(ticket=ticket, kind="path", spec=spec,
                        problems=[spec.problem],
                        family=_family_of(spec.problem))
    if isinstance(spec, CVSpec):
        probs = list(spec.problems)
        if not probs:
            raise SpecError("CVSpec needs at least one fold")
        if spec.validation is not None \
                and len(spec.validation) != len(probs):
            raise SpecError(
                f"CVSpec.validation must align with the folds: "
                f"{len(spec.validation)} pairs for {len(probs)} folds")
        if spec.score is not None and spec.validation is not None:
            raise SpecError("CVSpec.score and CVSpec.validation are "
                            "mutually exclusive scoring routes")
        if spec.tol_coarse is not None and spec.score is None \
                and spec.validation is None:
            raise SpecError(
                "CVSpec.tol_coarse needs a scoring route (score= or "
                "validation=): without a selected λ there is nothing "
                "to re-solve at full tolerance")
        if spec.tol_coarse is not None and spec.tol_schedule is not None:
            raise SpecError(
                "CVSpec.tol_coarse and CVSpec.tol_schedule are mutually "
                "exclusive: an explicit per-point schedule would "
                "silently override the coarse sweep tolerance")
        _require_dense(probs, "CV sweep")
        fams = {_family_of(p) for p in probs}
        return WorkItem(ticket=ticket, kind="cv", spec=spec,
                        problems=probs,
                        family=fams.pop() if len(fams) == 1 else None)
    raise SpecError(
        f"unknown workload spec {type(spec).__name__!r}; expected one of "
        "SoloSpec / BatchSpec / PathSpec / CVSpec")
