"""ℓ1-regularized ℓ2-loss SVM (paper §2, [18]):

  F(x) = Σⱼ max{0, 1 − aⱼ yⱼᵀx}²,   G(x) = c‖x‖₁.

The squared hinge is C¹ with Lipschitz-continuous gradient (A2–A3 hold);
``∇F(x) = −2 Zᵀ max(0, 1−Zx)`` with Z = diag(a)Y, and ``2Σⱼ zⱼᵢ²`` is a
diagonal curvature majorizer.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.problems.base import Problem, SmoothF, mv, smooth_f
from repro.problems.lasso import _power_iter_sq


def squared_hinge_fns(Z, col_sq=None) -> SmoothF:
    """F = ‖max(0, 1−Zx)‖² as a loss of the margins t = Z·x
    (:class:`~repro.problems.base.SmoothF`): with h = max(0, 1 − t),
    F = h·h and ∇F = −2Zᵀh.

    ``Z = diag(a)·Y``.  Traceable (batched-engine compatible); ``col_sq``
    may be precomputed to avoid re-reducing ‖zᵢ‖² inside a solve loop.
    """
    if col_sq is None:
        col_sq = jnp.sum(Z * Z, axis=0)

    def product(x):
        return mv(Z, x)

    def loss(t):
        h = jnp.maximum(0.0, 1.0 - t)
        return mv(h, h)

    def loss_grad(t):
        h = jnp.maximum(0.0, 1.0 - t)
        return -2.0 * mv(Z.T, h)

    def diag_curv(x):
        return 2.0 * col_sq

    return smooth_f(product, loss, loss_grad, diag_curv)


def make_svm(Y, a, c: float, block_size: int = 1) -> Problem:
    Y = jnp.asarray(Y)
    a = jnp.asarray(a)
    Z = Y * a[:, None]
    fns = squared_hinge_fns(Z)

    L = float(2.0 * _power_iter_sq(np.asarray(Z)))
    return Problem(
        name="l1_l2_svm", n=Y.shape[1], block_size=block_size,
        **fns._asdict(),
        g_kind="l1", g_weight=float(c), family="svm",
        lipschitz=L, data={"Z": Z},
    )


def random_svm_instance(m: int, n: int, nnz_frac: float, c: float = 0.5,
                        seed: int = 0) -> Problem:
    rng = np.random.default_rng(seed)
    Y = rng.standard_normal((m, n))
    w = np.zeros(n)
    s = max(1, int(round(nnz_frac * n)))
    w[rng.permutation(n)[:s]] = rng.standard_normal(s)
    a = np.where(Y @ w > 0, 1.0, -1.0)
    return make_svm(Y, a, c)
