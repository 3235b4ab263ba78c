"""Sparse logistic regression (paper §2, [24, 25]):

  F(x) = Σⱼ log(1 + exp(−aⱼ yⱼᵀ x)),   G(x) = c‖x‖₁  (or group ℓ2).

F is convex with Lipschitz gradient; the diagonal curvature majorizer is
``0.25·Σⱼ yⱼᵢ²`` (since σ'(t) ≤ 1/4), which drives the Newton-type surrogate
(choice (7) with a diagonal Hessian bound).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.problems.base import Problem, SmoothF, mv, smooth_f
from repro.problems.lasso import _power_iter_sq


def logistic_fns(Z, col_sq=None) -> SmoothF:
    """F = Σⱼ log(1+exp(−zⱼᵀx)) as a loss of the margins t = Z·x
    (:class:`~repro.problems.base.SmoothF`): ∇F = −Zᵀσ(−t).

    ``Z = diag(a)·Y`` is the label-signed feature matrix.  Traceable, so
    the batched engine can call it with per-instance traced slices of Z;
    ``col_sq`` may be precomputed to avoid re-reducing ‖zᵢ‖² in a loop.
    """
    if col_sq is None:
        col_sq = jnp.sum(Z * Z, axis=0)

    def product(x):
        return mv(Z, x)

    def loss(t):
        # log(1+e^{−t}) computed stably
        return jnp.sum(jnp.logaddexp(0.0, -t))

    def loss_grad(t):
        sig = jax.nn.sigmoid(-t)       # = e^{−t}/(1+e^{−t})
        return -mv(Z.T, sig)

    def diag_curv(x):
        # Global bound: σ(t)σ(−t) ≤ 1/4  ⇒  diag(∇²F) ≤ 0.25·Σ zⱼᵢ².
        return 0.25 * col_sq

    return smooth_f(product, loss, loss_grad, diag_curv)


def make_logreg(Y, a, c: float, block_size: int = 1) -> Problem:
    """Y: (m, n) feature rows yⱼ; a: (m,) labels in {−1, +1}."""
    Y = jnp.asarray(Y)
    a = jnp.asarray(a)
    Z = Y * a[:, None]                 # margins are z = Zx
    fns = logistic_fns(Z)

    L = float(0.25 * _power_iter_sq(np.asarray(Z)))
    return Problem(
        name="sparse_logreg", n=Y.shape[1], block_size=block_size,
        **fns._asdict(),
        g_kind="l1" if block_size == 1 else "group_l2", g_weight=float(c),
        family="logreg", lipschitz=L, data={"Z": Z},
    )


def random_logreg_instance(m: int, n: int, nnz_frac: float, c: float = 0.5,
                           seed: int = 0, block_size: int = 1) -> Problem:
    """Separable-ish synthetic instance with a sparse ground-truth direction."""
    rng = np.random.default_rng(seed)
    Y = rng.standard_normal((m, n))
    w = np.zeros(n)
    s = max(1, int(round(nnz_frac * n)))
    idx = rng.permutation(n)[:s]
    w[idx] = rng.standard_normal(s)
    logits = Y @ w + 0.3 * rng.standard_normal(m)
    a = np.where(logits > 0, 1.0, -1.0)
    return make_logreg(Y, a, c, block_size=block_size)
