"""Nesterov's planted-optimum Lasso instances, made on the device.

The construction (Nesterov, "Gradient methods for minimizing composite
objective function", 2007, §6), written for F = ‖Ax − b‖² and
G = c‖x‖₁ as in arXiv:1311.2444 §4:

1. B ~ N(0, 1) of shape (m, n); y ~ N(0, 1) of length m, scaled to
   ‖y‖ = 1.
2. u = Bᵀy.  On the support (the s largest |uᵢ|, s = round(nnz·n))
   column i is scaled by c / (2|uᵢ|), so ⟨aᵢ, y⟩ = ±c/2.  Off the
   support, column i is scaled by c θᵢ / (2|uᵢ|), θᵢ ~ U(0, 1), where
   |uᵢ| > c θᵢ / 2, so |⟨aᵢ, y⟩| ≤ c/2 everywhere.
3. x*ᵢ = ξᵢ sign(uᵢ), ξᵢ ~ U(0, 1), on the support, 0 off it.
4. b = A x* + y.  Then ∇F(x*) = −2Aᵀy lies in −c ∂‖x*‖₁, so x* is
   optimal and V* = ‖y‖² + c‖x*‖₁ in closed form.

An instance is a pure function of two keys.  ``base_key`` draws the
construction; ``sign_key`` then flips the signs of rows and columns
(A ← diag(r) A diag(s), b ← r·b, x* ← s·x*).  A flip changes every
array the solver reads but maps each iterate of Algorithm 1 exactly
onto the flipped iterate (products, soft-threshold and maxima are
sign-symmetric and exact), so every seed costs the solver the same
iterations on the same base instance.

Products run at ``Precision.HIGHEST`` (float32 accuracy on a TPU).
Everything is one jitted call; nothing is made on the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _one(base_key, sign_key, nnz, *, m: int, n: int, c: float):
    k_b, k_y, k_theta, k_xi = jax.random.split(base_key, 4)
    B = jax.random.normal(k_b, (m, n), jnp.float32)
    y = jax.random.normal(k_y, (m,), jnp.float32)
    y = y / jnp.sqrt(jnp.sum(y * y))
    u = jnp.matmul(y, B, precision=HIGHEST)
    au = jnp.abs(u)
    s = jnp.maximum(1, jnp.round(nnz * n)).astype(jnp.int32)
    rank = jnp.argsort(jnp.argsort(-au))
    support = rank < s
    half_c = 0.5 * c
    theta = jax.random.uniform(k_theta, (n,), jnp.float32)
    shrink = jnp.where(au > half_c * theta, half_c * theta / au, 1.0)
    scale = jnp.where(support, half_c / au, shrink)
    A = B * scale[None, :]
    xi = jax.random.uniform(k_xi, (n,), jnp.float32)
    x_star = jnp.where(support, xi * jnp.sign(u), 0.0)
    b = jnp.matmul(A, x_star, precision=HIGHEST) + y
    v_star = jnp.sum(y * y) + c * jnp.sum(jnp.abs(x_star))

    k_r, k_s = jax.random.split(sign_key)
    r = jax.random.rademacher(k_r, (m,), jnp.float32)
    sg = jax.random.rademacher(k_s, (n,), jnp.float32)
    return A * r[:, None] * sg[None, :], b * r, x_star * sg, v_star


@partial(jax.jit, static_argnames=("m", "n", "c"))
def make_instances(base_keys, sign_keys, nnz, *, m: int, n: int,
                   c: float = 1.0):
    """``len(nnz)`` instances, one per (base key, sign key, nnz
    fraction): lists ``A``, ``b``, ``x_star`` of separate arrays, and
    ``v_star`` stacked."""
    A, b, x_star, v_star = jax.vmap(partial(_one, m=m, n=n, c=c))(
        base_keys, sign_keys, nnz)
    count = len(nnz)
    return ([A[i] for i in range(count)], [b[i] for i in range(count)],
            [x_star[i] for i in range(count)], v_star)


def pool_keys(pool_key: int, seed: int, count: int):
    """Base keys from the cell's fixed ``pool_key``; sign keys from the
    run's ``seed`` (any whole number, wider than 32 bits included)."""
    base = jax.random.split(jax.random.PRNGKey(pool_key), count)
    seed %= 2 ** 64
    root = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, seed >> 32):
        root = jax.random.fold_in(root, np.uint32(word))
    return base, jax.random.split(root, count)
