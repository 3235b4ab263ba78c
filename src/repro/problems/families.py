"""Problem-family registry for the batched multi-instance engine.

The paper's framework covers any composite ``F + G`` (Eq. (1)); the batched
engine (``repro.solvers.batched``) vmaps :func:`repro.core.flexa.
flexa_iteration` over a stack of instances, which requires rebuilding each
instance's F closures from *traced* data slices inside the vmap.  A
:class:`ProblemFamily` packages exactly what that takes, per F choice:

* ``data_keys``  — which arrays of ``Problem.data`` vary per instance and
  get stacked along a leading batch dimension (the first one is the (m, n)
  design/feature matrix that fixes the shape signature);
* ``make_fns``   — the traceable ``(*arrays, col_sq=None) ->``
  :class:`~repro.problems.base.SmoothF` closure builder: F as a loss of
  one design product, with ``f`` and ``grad_f`` its compositions.  These
  are the *same* builders the solo constructors install
  (``lasso.quadratic_fns``, ``logreg.logistic_fns``,
  ``svm.squared_hinge_fns``), so batched and solo solves share one
  definition of the math;
* ``curv_scale`` — the constant in ``diag_curv = curv_scale·‖columns‖²``,
  used to derive the paper's §4 default ``τᵢ = tr(diag ∇²F)/ (2·2n)`` from
  the precomputed column norms without calling ``diag_curv`` on the host.

G stays orthogonal: the family fixes F, while ``g_kind``/``block_size``
(part of the shape signature) select the prox — so sparse logistic
regression and *group*-sparse logistic regression are one family.

Adding a family is one :func:`register_family` call; the batched engine,
the serve engine and the compile-cache keys pick it up automatically.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax.numpy as jnp

from repro.problems.base import Problem
from repro.problems.lasso import quadratic_fns
from repro.problems.logreg import logistic_fns
from repro.problems.sparse import design_col_sq
from repro.problems.svm import squared_hinge_fns


@dataclass(frozen=True)
class ProblemFamily:
    name: str
    data_keys: tuple            # Problem.data arrays stacked per instance
    make_fns: Callable          # (*arrays, col_sq=None) -> SmoothF
    curv_scale: float           # diag_curv == curv_scale * col_sq
    # Safe-screening hook (``repro.path.screening``): maps the gradient of
    # F at a reference point to the per-block dual-correlation scores the
    # sequential strong rule thresholds against the regularization weight
    # (KKT: a block may be zero at weight c only if its score ≤ c).  None
    # ⇒ the family opts out of screening (the unit-slope assumption of
    # the strong rule has not been checked for it) and the path engine
    # solves every block at every λ.
    screen_scores: Callable | None = None   # (grad, block_size) -> (n_blocks,)

    @property
    def screenable(self) -> bool:
        return self.screen_scores is not None

    def col_sq(self, *arrays) -> jnp.ndarray:
        """‖column‖² of the (m, n) design matrix (arrays[0]), dense or
        sparse — traceable."""
        return design_col_sq(arrays[0])

    def half_curv(self, col_sq) -> jnp.ndarray:
        """diag_curv/2 — what the §4 default τ rule reduces over (matches
        ``flexa.default_tau0`` exactly, so batched and solo drivers can
        never disagree on the default τ)."""
        return 0.5 * self.curv_scale * col_sq


_FAMILIES: dict[str, ProblemFamily] = {}


def register_family(fam: ProblemFamily) -> ProblemFamily:
    if fam.name in _FAMILIES:
        raise ValueError(f"problem family {fam.name!r} already registered")
    _FAMILIES[fam.name] = fam
    return fam


def get_family(name: str) -> ProblemFamily:
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(f"unknown problem family {name!r}; available: "
                       f"{available_families()}") from None


def available_families() -> tuple[str, ...]:
    return tuple(sorted(_FAMILIES))


def _lasso_screen_scores(grad, block_size: int):
    """ℓ1 correlation bound: |∇ⱼF(x)| = |2 aⱼᵀ(Ax − b)| per coordinate.

    KKT for  min ‖Ax−b‖² + c‖x‖₁:  xⱼ = 0 is optimal only if |∇ⱼF| ≤ c,
    so this is exactly the score the strong rule / KKT recheck threshold
    against c (the repo's unnormalized factor-2 convention is absorbed
    into the gradient itself)."""
    return jnp.abs(grad)


def _group_lasso_screen_scores(grad, block_size: int):
    """Group-norm bound: ‖∇_g F(x)‖₂ per block (block KKT: a zero group is
    optimal only if its gradient group-norm is ≤ c)."""
    return jnp.linalg.norm(grad.reshape(-1, block_size), axis=-1)


def _grad_block_scores(grad, block_size: int):
    """The generic dual-correlation bound for any smooth F: |∇ⱼF| under
    ℓ1 blocks, ‖∇_g F‖₂ under group blocks — the KKT zero-block
    condition is ``score_g ≤ c`` for every convex differentiable F, so
    the same score feeds the strong rule and the recheck.

    Slope-bound verdict (the strong rule additionally assumes the score
    is ≈1-Lipschitz along the λ-path — Tibshirani et al. 2012 argue it
    via ``c_g(λ) = λ·θ_g(λ)`` with θ dual-feasible, a heuristic for any
    convex loss, not just the quadratic): checked empirically for
    *logreg* (logistic loss) and *svm* (squared hinge) on planted
    instances — 5 seeds × 8-point geometric grids to 0.05·λ_max,
    tol ∈ {1e-7, 1e-8} — the rule screened ~40 % of blocks with ZERO
    KKT violations, and the screened path was bit-identical to the
    unscreened warm path.  Both families therefore register this hook;
    the KKT recheck keeps the path exact even where the heuristic would
    someday miss (a miss costs one re-solve round, never a wrong
    answer)."""
    if block_size == 1:
        return jnp.abs(grad)
    return jnp.linalg.norm(grad.reshape(-1, block_size), axis=-1)


register_family(ProblemFamily(
    name="lasso", data_keys=("A", "b"),
    make_fns=quadratic_fns, curv_scale=2.0,
    screen_scores=_lasso_screen_scores))
# Same smooth part as lasso; the group structure lives in the G side of the
# shape signature (block_size > 1, g_kind="group_l2").
register_family(ProblemFamily(
    name="group_lasso", data_keys=("A", "b"),
    make_fns=quadratic_fns, curv_scale=2.0,
    screen_scores=_group_lasso_screen_scores))
# logreg/svm screening: see the slope-bound verdict on
# _grad_block_scores — empirically safe, and the KKT recheck guarantees
# exactness regardless.
register_family(ProblemFamily(
    name="logreg", data_keys=("Z",),
    make_fns=logistic_fns, curv_scale=0.25,
    screen_scores=_grad_block_scores))
register_family(ProblemFamily(
    name="svm", data_keys=("Z",),
    make_fns=squared_hinge_fns, curv_scale=2.0,
    screen_scores=_grad_block_scores))


def infer_family(problem: Problem) -> str:
    """The family of a :class:`Problem` (explicit field, else structural)."""
    if problem.family:
        return problem.family
    if "A" in problem.data:              # quadratic F with data A, b
        return "lasso" if problem.block_size == 1 else "group_lasso"
    raise ValueError(
        "cannot infer a batched problem family for "
        f"{problem.name!r} (set Problem.family to one of "
        f"{available_families()})")


def build_problem(family: str, arrays, c, *, n: int, block_size: int,
                  g_kind: str, col_sq=None) -> Problem:
    """Rebuild a family :class:`Problem` from raw (possibly traced) arrays.

    Unlike the solo constructors this skips every non-traceable step (numpy
    power iteration etc.), so it can run *inside* jit/vmap with the arrays
    being per-instance traced slices and ``c`` a traced scalar.
    """
    fam = get_family(family)
    return Problem(
        name=f"batched_{family}", n=n, block_size=block_size,
        **fam.make_fns(*arrays, col_sq=col_sq)._asdict(),
        g_kind=g_kind, g_weight=c, family=family,
        data=dict(zip(fam.data_keys, arrays)))
