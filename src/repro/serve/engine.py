"""Solve requests and responses of the serving engines.

:class:`SolveRequest` is one composite-minimization request from any
registered problem family (lasso, group lasso, sparse logistic
regression, ℓ1-ℓ2 SVM — see ``repro.problems.families``);
:class:`SolveResponse` is its verdict; :func:`validate_request` holds
the shape/family checks every engine runs before it accepts one.  The
engines themselves are ``repro.serve.continuous`` and
``repro.serve.mesh``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.problems.families import get_family
from repro.problems.sparse import is_sparse
from repro.solvers.batched import BatchedProblemSpec


@dataclass
class SolveRequest:
    """One composite-minimization request:  min F(x) + c·g(x).

    ``family`` picks F (``repro.problems.families``): the quadratic
    families ("lasso"/"group_lasso") read ``A`` as the design matrix and
    need ``b``; "logreg"/"svm" read ``A`` as the label-signed feature
    matrix Z = diag(a)·Y and take no ``b``.  The quadratic families'
    ``A`` may be a :class:`~repro.problems.sparse.CSCDesign` (host
    arrays, as a tenant sends it): such a request is served in a slab of
    sparse designs of its nnz bucket (``BatchedProblemSpec.nnz_cap``).

    ``priority``/``deadline`` are scheduling hints consumed by the
    engine's admission queue (``repro.serve.continuous``).

    Warm starts: ``x0`` is spliced into the slab on admission (zeros if
    unset).  ``warm_from`` is engine-side sugar — "use
    the solution of that finished request as my x0"; admission is
    deferred until the referenced request completes (it must be an
    earlier, same-signature request of the same engine).  ``active_mask``
    is a per-coordinate {0,1} freeze mask (safe-screening support —
    ``repro.path``): zero coordinates are excluded from selection,
    updates and the termination measure.
    """
    A: np.ndarray               # (m, n) design / signed-feature matrix,
    #                             or a CSCDesign (quadratic families)
    b: np.ndarray | None = None  # (m,) observations (quadratic families)
    c: float = 1.0              # regularization weight
    block_size: int = 1         # 1 ⇒ ℓ1; >1 ⇒ group-ℓ2 blocks
    family: str = ""            # "" ⇒ lasso/group_lasso by block_size
    x0: np.ndarray | None = None  # optional warm start
    priority: int = 0           # higher = admitted first ("priority" policy)
    deadline: float | None = None  # absolute time ("deadline" policy)
    warm_from: int | None = None   # req_id whose solution becomes x0
    active_mask: np.ndarray | None = None  # (n,) freeze mask (1 = live)
    #: Per-request stopping tolerance (None ⇒ the engine's
    #: ``SolverConfig.tol``).  The slab's stop check reads a per-slot
    #: tolerance vector, so one engine can mix tenant tolerances (the
    #: multi-tenant serving scenario, and what lets
    #: ``CVSpec(tol_coarse=)`` ride a shared engine).
    tol: float | None = None

    @property
    def spec(self) -> BatchedProblemSpec:
        family = self.family or (
            "lasso" if self.block_size == 1 else "group_lasso")
        return BatchedProblemSpec.for_design(
            self.A, n=int(self.A.shape[1]), block_size=self.block_size,
            g_kind="l1" if self.block_size == 1 else "group_l2",
            family=family)

    def data_arrays(self, spec: BatchedProblemSpec) -> tuple:
        """The family data tuple this request contributes to the stack.

        ``A`` always supplies the leading (m, n) design array whatever the
        family calls it; ``b`` supplies the observation vector.  Families
        with additional per-instance arrays need a richer request type —
        fail loudly rather than guessing.

        The arrays come back as float32 where they already are: host
        arrays stay on the host (no copy when already float32), device
        arrays on their device.  The caller places them, so the
        continuous slab can ship a row straight to the device that owns
        its slot.  A sparse design comes back padded to the slab's nnz
        capacity.
        """
        def f32(a):
            if is_sparse(a):
                return a.padded(spec.nnz_cap)
            if isinstance(a, jax.Array):
                return a.astype(jnp.float32)
            return np.asarray(a, np.float32)

        keys = get_family(spec.family).data_keys
        out = []
        for j, k in enumerate(keys):
            if j == 0:
                out.append(f32(self.A))
            elif k == "b":
                out.append(f32(self.b))
            else:
                raise NotImplementedError(
                    f"SolveRequest has no field for data key {k!r} of "
                    f"family {spec.family!r}")
        return tuple(out)


@dataclass
class SolveResponse:
    """Per-request solver verdict (read back out of its slot)."""
    x: np.ndarray
    iters: int
    converged: bool
    stat: float                 # final ‖x̂(x)−x‖∞
    bucket: int                 # slab capacity served in
    #: Health verdict: "ok" for a normal completion (converged or
    #: max-iters), "diverged"/"stalled" when the numerical-health
    #: watchdog (``ServeConfig.watchdog``) quarantined the solve,
    #: "timeout" when the continuous engine evicted a past-deadline
    #: request (``ContinuousSolverEngine.expire_overdue``).
    status: str = "ok"


def validate_request(r: SolveRequest, spec: BatchedProblemSpec) -> None:
    """Shape/family checks every engine runs on a request — raise
    before any device work so rejection is atomic."""
    if is_sparse(r.A):
        try:
            r.A.check()
        except ValueError as e:
            raise ValueError(f"request: {e}") from None
    needs_b = "b" in get_family(spec.family).data_keys
    if needs_b and np.shape(r.b) != (spec.m,):
        raise ValueError(
            f"request: family {spec.family!r} needs b of shape "
            f"({spec.m},), got {np.shape(r.b)}")
    if not needs_b and r.b is not None:
        raise ValueError(
            f"request: family {spec.family!r} takes no b")
    if r.x0 is not None and np.shape(r.x0) != (spec.n,):
        raise ValueError(
            f"request: x0 must have shape ({spec.n},), got "
            f"{np.shape(r.x0)}")
    if r.active_mask is not None and np.shape(r.active_mask) != (spec.n,):
        raise ValueError(
            f"request: active_mask must have shape ({spec.n},), got "
            f"{np.shape(r.active_mask)}")
    if r.warm_from is not None and r.x0 is not None:
        raise ValueError(
            "request: warm_from and x0 are mutually exclusive")
    if r.tol is not None and not (float(r.tol) >= 0):
        raise ValueError(
            f"request: tol must be a non-negative float, got {r.tol!r}")
