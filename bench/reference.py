"""Plain Algorithm 1 (FLEXA, arXiv:1311.2444) for the Lasso, in float32.

Written from the paper and imports nothing of the program under test.
F(x) = ‖Ax − b‖², G(x) = c‖x‖₁, scalar blocks, the paper's §4 settings:

* best response with the exact-block surrogate (6):
  dᵢ = τᵢ + 2‖aᵢ‖²,  ẑᵢ = soft(xᵢ − ∇ᵢF(x)/dᵢ, c/dᵢ);
* greedy selection Sᵏ = {i : Eᵢ ≥ ρ maxⱼ Eⱼ}, Eᵢ = |ẑᵢ − xᵢ|, ρ = 0.5;
* xᵏ⁺¹ = xᵏ + γᵏ 1_S (ẑ − xᵏ), γᵏ⁺¹ = γᵏ (1 − θ γᵏ), γ⁰ = 0.9, θ = 1e-5;
* τ⁰ = tr(AᵀA) / 2n; τ doubles when V rises, halves after 10
  consecutive decreases, at most 60 changes;
* stop when ‖ẑ − xᵏ‖∞ ≤ tol or after ``max_iters`` iterations.

``precision`` selects how products with A are computed: ``"highest"``
(float32 accuracy, the configuration's precision), ``"high"`` (three
bfloat16 passes) or ``"bf16"`` (A and x rounded to bfloat16, float32
accumulation).  ``"bf16"`` is the control that the correctness check
has to reject; ``"high"`` computes these products exactly as
``"highest"`` does on a TPU v5e (identical iterates, measured), so it is
no lower precision there.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

MAX_TAU_CHANGES = 60


def _products(A, precision: str):
    """``(A x, Aᵀ r)`` at the requested precision."""
    if precision == "bf16":
        Ab = A.astype(jnp.bfloat16)

        def ax(x):
            return jnp.matmul(Ab, x.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)

        def atr(r):
            return jnp.matmul(r.astype(jnp.bfloat16), Ab,
                              preferred_element_type=jnp.float32)
        return ax, atr
    p = {"highest": jax.lax.Precision.HIGHEST,
         "high": jax.lax.Precision.HIGH}[precision]
    return (lambda x: jnp.matmul(A, x, precision=p),
            lambda r: jnp.matmul(r, A, precision=p))


@partial(jax.jit, static_argnames=("precision",))
def objective(A, b, c, x, precision: str = "highest"):
    """V(x) = ‖Ax − b‖² + c‖x‖₁."""
    ax, _ = _products(A, precision)
    r = ax(x) - b
    return jnp.sum(r * r) + c * jnp.sum(jnp.abs(x))


@partial(jax.jit, static_argnames=("precision", "rho", "gamma0", "theta",
                                   "tau_patience"))
def solve(A, b, c, tol, max_iters, *, precision: str = "highest",
          rho: float = 0.5, gamma0: float = 0.9, theta: float = 1e-5,
          tau_patience: int = 10):
    """Run Algorithm 1 from x = 0 until ``stat ≤ tol`` or ``max_iters``
    iterations (both traced: one program serves every budget).  Returns
    ``(x, iters, stat)``."""
    m, n = A.shape
    ax, atr = _products(A, precision)
    Ar = A.astype(jnp.bfloat16).astype(jnp.float32) \
        if precision == "bf16" else A
    col_sq = jnp.sum(Ar * Ar, axis=0)
    tau0 = jnp.sum(col_sq) / (2.0 * n)

    def value(x):
        r = ax(x) - b
        return jnp.sum(r * r) + c * jnp.sum(jnp.abs(x))

    def cond(s):
        x, gamma, tau_scale, v_prev, consec, changes, k, stat = s
        return (k < max_iters) & (stat > tol)

    def body(s):
        x, gamma, tau_scale, v_prev, consec, changes, k, stat = s
        grad = 2.0 * atr(ax(x) - b)
        d = tau0 * tau_scale + 2.0 * col_sq
        w = x - grad / d
        z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - c / d, 0.0)
        E = jnp.abs(z - x)
        M = jnp.max(E)
        sel = (E >= rho * M).astype(jnp.float32)
        x_new = x + gamma * sel * (z - x)
        v_new = value(x_new)
        can = changes < MAX_TAU_CHANGES
        up = (v_new > v_prev) & can
        consec = jnp.where(v_new > v_prev, 0, consec + 1)
        down = (consec >= tau_patience) & can
        tau_scale = jnp.where(up, 2.0 * tau_scale, tau_scale)
        tau_scale = jnp.where(down, 0.5 * tau_scale, tau_scale)
        consec = jnp.where(down, 0, consec)
        changes = changes + up.astype(jnp.int32) + down.astype(jnp.int32)
        return (x_new, gamma * (1.0 - theta * gamma), tau_scale, v_new,
                consec, changes, k + 1, M)

    x0 = jnp.zeros((n,), jnp.float32)
    s0 = (x0, jnp.float32(gamma0), jnp.float32(1.0), value(x0),
          jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.float32(jnp.inf))
    x, _, _, _, _, _, k, stat = jax.lax.while_loop(cond, body, s0)
    return x, k, stat
