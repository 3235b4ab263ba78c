"""Problem interface for composite minimization  min F(x) + G(x)  (Eq. (1)).

A :class:`Problem` bundles the smooth part ``F`` (value + gradient + a
per-coordinate curvature majorizer used by exact-block/Newton surrogates) and
the block-separable nonsmooth part ``G`` (kind + weight).  All callables are
pure jnp functions of the flat variable vector, so they can be jitted,
differentiated, and sharded.

F is also held as a loss of one product of x (:class:`SmoothF`): every
registered family reads x through one product with its design, u = A·x − b
or u = Z·x, and the iteration carries u from one step to the next, so that
it reads the design twice a step.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.prox import group_soft_threshold, soft_threshold


def mv(a, b):
    """``a @ b`` at float32 accuracy on every backend.

    XLA on TPU runs a float32 product as one bfloat16 pass unless told
    otherwise (about three significant digits), which the solvers'
    float32 equivalence contracts cannot absorb.  Every product of a
    design matrix goes through here.
    """
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class SmoothF(NamedTuple):
    """A smooth F as a loss of one product of x: u = ``product(x)``,
    F = ``loss(u)``, ∇F = ``loss_grad(u)`` (which holds the one
    transpose product).  ``f`` and ``grad_f`` are the compositions, so
    F has one definition; ``Problem(**fns._asdict())`` installs all
    six."""
    f: Callable                 # x -> F(x)
    grad_f: Callable            # x -> ∇F(x)
    diag_curv: Callable         # x -> per-coordinate curvature majorizer
    product: Callable           # x -> u
    loss: Callable              # u -> F
    loss_grad: Callable         # u -> ∇F


def smooth_f(product, loss, loss_grad, diag_curv) -> SmoothF:
    """The :class:`SmoothF` of ``F(x) = loss(product(x))``."""
    return SmoothF(f=lambda x: loss(product(x)),
                   grad_f=lambda x: loss_grad(product(x)),
                   diag_curv=diag_curv, product=product, loss=loss,
                   loss_grad=loss_grad)


def _identity(x):
    return x


@dataclass
class Problem:
    name: str
    n: int                      # total number of scalar variables
    block_size: int             # nᵢ (1 ⇒ scalar blocks, as in the paper's Lasso)
    f: Callable                 # x -> F(x)
    grad_f: Callable            # x -> ∇F(x)
    diag_curv: Callable         # x -> per-coordinate curvature majorizer of F
    g_kind: str = "l1"          # "l1" | "group_l2" | "zero"
    g_weight: float = 0.0       # c
    # Which F-family the problem belongs to ("lasso" | "group_lasso" |
    # "logreg" | "svm" | "" for ad-hoc F).  The batched engine uses this to
    # rebuild the F closures from stacked data inside vmap
    # (repro.problems.families).
    family: str = ""
    # Optional certificates (Nesterov instances have closed-form optima):
    v_star: Optional[float] = None
    x_star: Optional[jnp.ndarray] = None
    lipschitz: Optional[float] = None   # L_F estimate (FISTA etc.)
    data: dict = field(default_factory=dict)
    # F as a loss of one product u = product(x) (:class:`SmoothF`); the
    # iteration carries u.  A problem built from its own ``f`` and
    # ``grad_f`` alone reads x itself: u = x, loss = f, loss_grad = grad_f.
    product: Optional[Callable] = None  # x -> u
    loss: Optional[Callable] = None     # u -> F
    loss_grad: Optional[Callable] = None  # u -> ∇F

    def __post_init__(self):
        if self.product is None:
            self.product, self.loss, self.loss_grad = (
                _identity, self.f, self.grad_f)

    # ------------------------------------------------------------------ #
    @property
    def n_blocks(self) -> int:
        return self.n // self.block_size

    def blockify(self, x: jnp.ndarray) -> jnp.ndarray:
        return x.reshape(self.n_blocks, self.block_size)

    def _g_off(self) -> bool:
        """G ≡ 0 shortcut.  ``g_weight`` may be a traced scalar (the batched
        engine vmaps over per-instance weights), so only test equality when
        it is a concrete Python number."""
        return self.g_kind == "zero" or (
            isinstance(self.g_weight, (int, float)) and self.g_weight == 0.0)

    def g(self, x: jnp.ndarray):
        if self._g_off():
            return jnp.asarray(0.0, x.dtype)
        if self.g_kind == "l1":
            return self.g_weight * jnp.sum(jnp.abs(x))
        if self.g_kind == "group_l2":
            xb = self.blockify(x)
            return self.g_weight * jnp.sum(jnp.linalg.norm(xb, axis=-1))
        raise ValueError(self.g_kind)

    def v(self, x: jnp.ndarray):
        """Full objective V = F + G."""
        return self.v_at(self.product(x), x)

    def v_at(self, u: jnp.ndarray, x: jnp.ndarray):
        """V(x) from the product ``u = product(x)`` already in hand."""
        return self.loss(u) + self.g(x)

    def prox(self, w: jnp.ndarray, t) -> jnp.ndarray:
        """Blockwise prox of ``t·g`` at ``w`` (t broadcastable over coords)."""
        if self._g_off():
            return w
        if self.g_kind == "l1":
            return soft_threshold(w, t * self.g_weight)
        if self.g_kind == "group_l2":
            wb = self.blockify(w)
            tb = jnp.broadcast_to(jnp.asarray(t), w.shape)
            tb = self.blockify(tb)[:, :1]  # per-block scalar
            return group_soft_threshold(wb, tb * self.g_weight).reshape(w.shape)
        raise ValueError(self.g_kind)

    def block_norms(self, x: jnp.ndarray) -> jnp.ndarray:
        """Per-block ℓ2 norms of a flat vector."""
        if self.block_size == 1:
            return jnp.abs(x)
        return jnp.linalg.norm(self.blockify(x), axis=-1)

    def stationarity(self, x: jnp.ndarray, tau: float = 1.0):
        """‖x − prox_g(x − ∇F(x)/τ)/‖∞ — a stationarity residual.

        Zero exactly at the stationary points of (1) (fixed points of the
        best-response map, Prop. 3(b)).
        """
        w = x - self.grad_f(x) / tau
        return jnp.max(jnp.abs(self.prox(w, 1.0 / tau) - x))
