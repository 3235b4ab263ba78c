"""Plain Algorithm 1 (FLEXA, arXiv:1311.2444) for sparse logistic
regression, in float32.

Written from the paper and imports nothing of the program under test.
The problem is arXiv:1402.5521's: F(x) = Σⱼ log(1 + exp(−zⱼᵀx)),
G(x) = c‖x‖₁, with Z = diag(a)·Y the label-signed feature rows, scalar
blocks, and §4's settings of arXiv:1311.2444 otherwise:

* the loss from the margins t = Z x, stably, as logaddexp(0, −t);
  ∇F = −Zᵀσ(−t);
* best response with the exact-block surrogate, whose curvature is the
  majorizer dᵢ = τᵢ + ¼‖zᵢ‖² (σ(t)σ(−t) ≤ ¼ bounds the diagonal of
  ∇²F), ẑᵢ = soft(xᵢ − ∇ᵢF(x)/dᵢ, c/dᵢ);
* greedy selection Sᵏ = {i : Eᵢ ≥ ρ maxⱼ Eⱼ}, Eᵢ = |ẑᵢ − xᵢ|, ρ = 0.5;
* xᵏ⁺¹ = xᵏ + γᵏ 1_S (ẑ − xᵏ), γᵏ⁺¹ = γᵏ (1 − θ γᵏ), γ⁰ = 0.9, θ = 1e-5;
* τ⁰ = Σᵢ ⅛‖zᵢ‖² / 2n: §4's tr(AᵀA)/2n is half the trace of the
  quadratic's curvature diagonal 2‖aᵢ‖² over 2n, and the same rule on
  the logistic majorizer ¼‖zᵢ‖² gives this.  It departs from the
  paper, which states the rule for the Lasso only; it is the program's
  default for this family too.  τ doubles when V rises, halves after 10
  consecutive decreases, at most 60 changes;
* stop when ‖ẑ − xᵏ‖∞ ≤ tol or after ``max_iters`` iterations.

``precision`` selects how products with Z are computed, by
``bench/reference.py``'s own products: ``"highest"`` (float32
accuracy, the configuration's precision), ``"high"`` (three bfloat16
passes) or ``"bf16"`` (Z and the vectors it multiplies rounded to
bfloat16, float32 accumulation), the control that the correctness
check has to reject.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import MAX_TAU_CHANGES, _products


def _value(t, c, x):
    return jnp.sum(jnp.logaddexp(0.0, -t)) + c * jnp.sum(jnp.abs(x))


@partial(jax.jit, static_argnames=("precision",))
def objective(Z, c, x, precision: str = "highest"):
    """V(x) = Σⱼ log(1 + exp(−zⱼᵀx)) + c‖x‖₁."""
    zx, _ = _products(Z, precision)
    return _value(zx(x), c, x)


@partial(jax.jit, static_argnames=("precision", "rho", "gamma0", "theta",
                                   "tau_patience"))
def solve(Z, c, tol, max_iters, *, precision: str = "highest",
          rho: float = 0.5, gamma0: float = 0.9, theta: float = 1e-5,
          tau_patience: int = 10):
    """Run Algorithm 1 from x = 0 until ``stat ≤ tol`` or ``max_iters``
    iterations (both traced: one program serves every budget).  Returns
    ``(x, iters, stat)``."""
    m, n = Z.shape
    zx, ztr = _products(Z, precision)     # (Z x, Zᵀ r)
    Zr = Z.astype(jnp.bfloat16).astype(jnp.float32) \
        if precision == "bf16" else Z
    curv = 0.25 * jnp.sum(Zr * Zr, axis=0)
    tau0 = jnp.sum(0.5 * curv) / (2.0 * n)

    def cond(s):
        x, t, gamma, tau_scale, v_prev, consec, changes, k, stat = s
        return (k < max_iters) & (stat > tol)

    def body(s):
        x, t, gamma, tau_scale, v_prev, consec, changes, k, stat = s
        grad = -ztr(jax.nn.sigmoid(-t))
        d = tau0 * tau_scale + curv
        w = x - grad / d
        z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - c / d, 0.0)
        E = jnp.abs(z - x)
        M = jnp.max(E)
        sel = (E >= rho * M).astype(jnp.float32)
        x_new = x + gamma * sel * (z - x)
        t_new = zx(x_new)
        v_new = _value(t_new, c, x_new)
        can = changes < MAX_TAU_CHANGES
        up = (v_new > v_prev) & can
        consec = jnp.where(v_new > v_prev, 0, consec + 1)
        down = (consec >= tau_patience) & can
        tau_scale = jnp.where(up, 2.0 * tau_scale, tau_scale)
        tau_scale = jnp.where(down, 0.5 * tau_scale, tau_scale)
        consec = jnp.where(down, 0, consec)
        changes = changes + up.astype(jnp.int32) + down.astype(jnp.int32)
        return (x_new, t_new, gamma * (1.0 - theta * gamma), tau_scale,
                v_new, consec, changes, k + 1, M)

    x0 = jnp.zeros((n,), jnp.float32)
    t0 = zx(x0)
    s0 = (x0, t0, jnp.float32(gamma0), jnp.float32(1.0), _value(t0, c, x0),
          jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.float32(jnp.inf))
    x, _, _, _, _, _, _, k, stat = jax.lax.while_loop(cond, body, s0)
    return x, k, stat
