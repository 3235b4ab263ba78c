"""Lasso:  F(x) = ‖Ax − b‖²,  G(x) = c‖x‖₁  (the paper's headline problem).

Includes Nesterov's instance generator [7, §6] — adapted to the paper's
unnormalized ``F = ‖Ax−b‖²`` — which plants a known sparse optimum x* and
therefore yields an *exact* optimal value V*, so benchmark relative errors
are exact rather than estimated.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.problems.base import Problem, SmoothF, mv, smooth_f
from repro.problems.sparse import (CSCDesign, design_col_sq,
                                   design_matvec, design_rmatvec, is_sparse)


def quadratic_fns(A, b, col_sq=None) -> SmoothF:
    """F = ‖Ax−b‖² as a loss of the residual u = A·x − b
    (:class:`~repro.problems.base.SmoothF`): F = u·u, ∇F = 2Aᵀu.

    The single definition of the factor-2 convention used everywhere:
    ∇F = 2Aᵀ(Ax−b) and ∂²F/∂xᵢ² = 2‖aᵢ‖² (exact for quadratics —
    surrogate choice (6)).  Traceable, so the batched engine can call it
    with per-instance traced slices of (A, b); ``col_sq`` may be
    precomputed to avoid re-reducing ‖aᵢ‖² inside a solve loop.  ``A``
    is a dense (m, n) array or a sparse design (a :class:`~repro.
    problems.sparse.BlockedDesign`; a ``CSCDesign`` is laid out on the
    device first): only the products come from its layout.
    """
    if isinstance(A, CSCDesign):
        A = A.blocked()
    if col_sq is None:
        col_sq = design_col_sq(A)            # ‖aᵢ‖² per column

    def product(x):
        return design_matvec(A, x) - b

    def loss(r):
        return mv(r, r)

    def loss_grad(r):
        return 2.0 * design_rmatvec(A, r)

    def diag_curv(_):
        return 2.0 * col_sq

    return smooth_f(product, loss, loss_grad, diag_curv)


def make_lasso(A, b, c: float, block_size: int = 1,
               v_star=None, x_star=None, name: str = "lasso") -> Problem:
    """``A`` dense, or a :class:`~repro.problems.sparse.CSCDesign` (kept
    as given: host arrays stay on the host)."""
    if not is_sparse(A):
        A = jnp.asarray(A)
    b = jnp.asarray(b)
    # a sparse design's closures read it in the stored layout, laid out
    # on the device once here
    stored = A.blocked() if is_sparse(A) else A
    fns = quadratic_fns(stored, b)

    # L_F = 2·λmax(AᵀA): cheap power-iteration estimate.
    L = float(2.0 * (_power_iter_sq_sparse(stored) if is_sparse(A)
                     else _power_iter_sq(np.asarray(A))))
    return Problem(
        name=name, n=A.shape[1], block_size=block_size,
        **fns._asdict(),
        g_kind="l1" if block_size == 1 else "group_l2", g_weight=float(c),
        family="lasso" if block_size == 1 else "group_lasso",
        v_star=v_star, x_star=x_star, lipschitz=L,
        data={"A": A, "b": b},
    )


def _power_iter_sq(A: np.ndarray, iters: int = 50, seed: int = 0) -> float:
    """λmax(AᵀA) via power iteration on the thin side."""
    rng = np.random.default_rng(seed)
    m, n = A.shape
    if m <= n:
        M = A @ A.T
    else:
        M = A.T @ A
    v = rng.standard_normal(M.shape[0])
    v /= np.linalg.norm(v)
    lam = 0.0
    for _ in range(iters):
        w = M @ v
        lam = float(np.linalg.norm(w))
        v = w / max(lam, 1e-30)
    return lam


def _power_iter_sq_sparse(A, iters: int = 50, seed: int = 0) -> float:
    """λmax(AᵀA) of a sparse design (stored layout), by its own
    products."""
    v = np.random.default_rng(seed).standard_normal(A.n).astype(np.float32)
    v = jnp.asarray(v / np.linalg.norm(v))
    lam = 0.0
    for _ in range(iters):
        w = A.rmatvec(A.matvec(v))
        lam = float(jnp.linalg.norm(w))
        v = w / max(lam, 1e-30)
    return lam


def nesterov_instance(m: int, n: int, nnz_frac: float, c: float = 1.0,
                      seed: int = 0, block_size: int = 1) -> Problem:
    """Plant a known optimum for  min ‖Ax−b‖² + c‖x‖₁  (Nesterov [7]).

    Construction (adapted to the factor-2 gradient of the unnormalized F):
      1. random B ~ N(0,1), random residual y* ~ N(0,1) (normalized),
      2. u = Bᵀ y*;  on a support of size s rescale columns so ⟨aᵢ,y*⟩ = ±c/2,
         off support shrink columns whenever |⟨aᵢ,y*⟩| > (c/2)θᵢ, θᵢ~U(0,1),
      3. x*ᵢ = ξᵢ·sign(uᵢ) on the support (ξᵢ~U(0,1)), 0 elsewhere,
      4. b = A x* + y*  ⇒  ∇F(x*) = −2Aᵀy*, and by step 2 the optimality
         condition 0 ∈ ∇F(x*) + c∂‖x*‖₁ holds exactly.
    Then V* = ‖y*‖² + c‖x*‖₁ in closed form.
    """
    rng = np.random.default_rng(seed)
    s = max(1, int(round(nnz_frac * n)))
    B = rng.standard_normal((m, n))
    y = rng.standard_normal(m)
    y /= np.linalg.norm(y)

    u = B.T @ y
    half_c = 0.5 * c
    scale = np.ones(n)
    # Support: the s *largest* |uᵢ| (Nesterov's choice) — keeps the support
    # column rescaling c/(2|uᵢ|) bounded, i.e. a well-conditioned instance.
    order = np.argsort(-np.abs(u))
    sup, off = order[:s], order[s:]
    scale[sup] = half_c / np.abs(u[sup])
    theta = rng.uniform(0.0, 1.0, size=off.shape[0])
    too_big = np.abs(u[off]) > half_c * theta
    shrink = np.where(too_big, half_c * theta / np.abs(u[off]), 1.0)
    scale[off] = shrink
    A = B * scale[None, :]

    x_star = np.zeros(n)
    x_star[sup] = rng.uniform(0.0, 1.0, size=s) * np.sign(u[sup])
    b = A @ x_star + y

    v_star = float(y @ y + c * np.abs(x_star).sum())
    return make_lasso(
        A, b, c, block_size=block_size, v_star=v_star,
        x_star=jnp.asarray(x_star),
        name=f"nesterov_lasso(m={m},n={n},nnz={nnz_frac:.0%})",
    )
