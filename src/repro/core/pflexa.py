"""Distributed FLEXA for Lasso-type quadratics (shard_map SPMD).

This mirrors the paper's MPI implementation (§4: 16/32 processes, column
partition of A) on a JAX device mesh:

* the variable vector ``x`` and the *columns* of ``A`` are sharded over a
  mesh axis (the per-process blocks of the paper);
* the only dense collective is the ``psum`` building the shared residual
  ``r = Ax − b``  (the paper's all-reduce over Infiniband → here ICI);
* the greedy selection rule needs one scalar ``pmax`` of the local error
  bounds — the "no centralized coordination" property of §4;
* best responses (soft-threshold per block), the τ-controller and the γ
  schedule run shard-locally and identically on every device.

Beyond the naive translation, the residual is *carried* between iterations
(``r ← r + A·Δx``), so each iteration costs exactly one matvec + one
transposed matvec — matching what a tuned implementation (and certainly the
paper's C++/GSL one) does, instead of recomputing ``F`` from scratch.

The same code runs on a single device (mesh of size 1): benchmarks and tests
use it unmodified.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import SolverConfig
from repro.core.flexa import MAX_TAU_CHANGES
from repro.core.prox import soft_threshold
from repro.core import selection, stepsize
from repro.core.result import SolverResult
from repro.launch.mesh import make_mesh
from repro.problems.base import mv


class PFlexaState(NamedTuple):
    x: jnp.ndarray          # local shard of the variable (n_local,)
    r: jnp.ndarray          # replicated residual Ax − b (m,)
    gamma: jnp.ndarray
    tau_scale: jnp.ndarray
    v_prev: jnp.ndarray
    consec_dec: jnp.ndarray
    n_tau_changes: jnp.ndarray
    k: jnp.ndarray
    stat: jnp.ndarray
    key: jnp.ndarray        # replicated PRNG key (randomized selection)


#: Selection rules the sharded step supports.  Every shard evaluates its
#: local blocks; random draws use per-shard keys (``fold_in(axis_index)``)
#: split from one replicated stream, and the only collectives the rules add
#: are scalar pmax/psum reductions.
SHARDED_SELECTION_RULES = ("greedy", "full", "jacobi", "random", "hybrid",
                           "cyclic")


# Unified result contract (repro.solvers.result); old name kept as alias.
PFlexaResult = SolverResult


def _pad_cols(A: np.ndarray, p: int) -> tuple[np.ndarray, int]:
    m, n = A.shape
    pad = (-n) % p
    if pad:
        A = np.concatenate([A, np.zeros((m, pad), A.dtype)], axis=1)
    return A, pad


def make_sharded_step(mesh: Mesh, axis: str, c: float, cfg: SolverConfig,
                      tau0: float):
    """Build the shard_map'ed Algorithm-1 iteration for Lasso."""
    rule = "full" if cfg.jacobi else cfg.selection
    if rule not in SHARDED_SELECTION_RULES:
        raise ValueError(
            f"pflexa supports selection rules {SHARDED_SELECTION_RULES}; "
            f"got {rule!r}")

    def local_mask(E_loc, M, state: PFlexaState):
        """Step S.3 on the local blocks (masks keep it SPMD — only scalar
        collectives).  Returns (mask, next replicated key)."""
        if rule in ("full", "jacobi"):
            return jnp.ones_like(E_loc), state.key
        if rule == "greedy":
            # greedy_mask takes the externally-pmax'ed M so the shard-local
            # rule is literally the solo one.
            return selection.greedy_mask(E_loc, cfg.rho, M), state.key
        if rule == "cyclic":
            # Fixed per-shard shuffle (keyed on seed + shard index), chunk
            # k mod n_chunks — every block updated once per cycle.
            perm_key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed), jax.lax.axis_index(axis))
            return selection.cyclic_shuffle_mask(
                E_loc.shape[0], state.k, cfg.sel_chunks, perm_key), state.key
        # random / hybrid: split the replicated stream (same on all shards)
        # then fold in the shard index so draws are independent per shard.
        new_key, sub = jax.random.split(state.key)
        shard_key = jax.random.fold_in(sub, jax.lax.axis_index(axis))
        sketch = jax.random.bernoulli(
            shard_key, cfg.sel_p, E_loc.shape).astype(E_loc.dtype)
        total = jax.lax.psum(jnp.sum(sketch), axis)
        # Globally empty draw → fall back to the argmax set (never stall).
        sketch = jnp.where(total > 0, sketch,
                           (E_loc >= M).astype(E_loc.dtype))
        if rule == "random":
            return sketch, new_key
        Ms = jax.lax.pmax(jnp.max(E_loc * sketch), axis)
        return sketch * (E_loc >= cfg.rho * Ms).astype(E_loc.dtype), new_key

    def local_step(A_loc, colsq_loc, b, state: PFlexaState):
        x, r = state.x, state.r
        tau = tau0 * state.tau_scale
        g_loc = 2.0 * mv(A_loc.T, r)                     # ∇ᵢF, local columns
        d_loc = tau + 2.0 * colsq_loc                    # surrogate (6)
        z_loc = soft_threshold(x - g_loc / d_loc, c / d_loc)

        E_loc = jnp.abs(z_loc - x)                       # Eᵢ = |x̂ᵢ − xᵢ|
        M = jax.lax.pmax(jnp.max(E_loc), axis)           # one scalar collective
        mask, new_key = local_mask(E_loc, M, state)

        dx_loc = state.gamma * mask * (z_loc - x)
        x_new = x + dx_loc
        # Residual carry: r ← r + A·Δx (one matvec + one psum).
        r_new = r + jax.lax.psum(mv(A_loc, dx_loc), axis)

        # Objective at the new point (no extra matvec thanks to the carry).
        g_abs = jax.lax.psum(jnp.sum(jnp.abs(x_new)), axis)
        v_new = mv(r_new, r_new) + c * g_abs

        can_change = state.n_tau_changes < MAX_TAU_CHANGES
        adapt = bool(cfg.tau_adapt)
        increased = (v_new > state.v_prev) & can_change & adapt
        consec = jnp.where(v_new > state.v_prev, 0, state.consec_dec + 1)
        halve = (consec >= cfg.tau_patience) & can_change & adapt
        tau_scale = jnp.where(increased, state.tau_scale * cfg.tau_grow,
                              state.tau_scale)
        tau_scale = jnp.where(halve, tau_scale * cfg.tau_shrink, tau_scale)
        consec = jnp.where(halve, 0, consec)
        n_changes = state.n_tau_changes + increased.astype(jnp.int32) \
            + halve.astype(jnp.int32)

        stat = jax.lax.pmax(jnp.max(jnp.abs(z_loc - x)), axis)
        new_state = PFlexaState(
            x=x_new, r=r_new,
            gamma=stepsize.gamma_next(state.gamma, cfg.theta),
            tau_scale=tau_scale, v_prev=v_new, consec_dec=consec,
            n_tau_changes=n_changes, k=state.k + 1, stat=stat,
            key=new_key)
        sel = jax.lax.pmean(jnp.mean(mask), axis)
        info = {"V": v_new, "stat": stat, "E_max": M, "sel_frac": sel,
                "gamma": state.gamma, "tau_scale": tau_scale}
        return new_state, info

    state_specs = PFlexaState(
        x=P(axis), r=P(), gamma=P(), tau_scale=P(), v_prev=P(),
        consec_dec=P(), n_tau_changes=P(), k=P(), stat=P(), key=P())
    info_specs = {k: P() for k in
                  ("V", "stat", "E_max", "sel_frac", "gamma", "tau_scale")}

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(), state_specs),
        out_specs=(state_specs, info_specs),
        check_vma=False,
    )
    return jax.jit(sharded)


def solve(A, b, c: float, cfg: SolverConfig | None = None,
          mesh: Mesh | None = None, axis: str = "model",
          x0=None) -> PFlexaResult:
    """Distributed FLEXA solve of  min ‖Ax−b‖² + c‖x‖₁.

    ``mesh`` defaults to a 1-D mesh over all visible devices; on a single
    CPU device this degrades gracefully to the serial algorithm (identical
    iterates — tested).
    """
    cfg = cfg or SolverConfig()
    if mesh is None:
        mesh = make_mesh((len(jax.devices()),), (axis,))
    p = int(np.prod(mesh.devices.shape))

    A_np = np.asarray(A, np.float32)
    A_np, pad = _pad_cols(A_np, p)
    m, n_pad = A_np.shape
    n = n_pad - pad

    col_sharding = NamedSharding(mesh, P(axis))
    mat_sharding = NamedSharding(mesh, P(None, axis))
    rep = NamedSharding(mesh, P())

    A_dev = jax.device_put(jnp.asarray(A_np), mat_sharding)
    b_dev = jax.device_put(jnp.asarray(b, jnp.float32), rep)
    colsq = jnp.sum(A_dev * A_dev, axis=0)

    if cfg.tau0 > 0:
        tau0 = cfg.tau0
    else:
        tau0 = float(jnp.sum(colsq) / (2.0 * n))          # tr(AᵀA)/2n (§4)

    if x0 is None:
        x0 = jnp.zeros((n_pad,), jnp.float32)
    else:
        x0 = jnp.concatenate([jnp.asarray(x0, jnp.float32),
                              jnp.zeros((pad,), jnp.float32)])
    x0 = jax.device_put(x0, col_sharding)
    r0 = mv(A_dev, x0) - b_dev
    v0 = mv(r0, r0) + c * jnp.sum(jnp.abs(x0))

    state = PFlexaState(
        x=x0, r=r0,
        gamma=jnp.asarray(cfg.gamma0, jnp.float32),
        tau_scale=jnp.asarray(1.0, jnp.float32),
        v_prev=jnp.asarray(v0, jnp.float32),
        consec_dec=jnp.asarray(0, jnp.int32),
        n_tau_changes=jnp.asarray(0, jnp.int32),
        k=jnp.asarray(0, jnp.int32),
        stat=jnp.asarray(jnp.inf, jnp.float32),
        key=jax.random.PRNGKey(cfg.seed),
    )
    step = make_sharded_step(mesh, axis, float(c), cfg, tau0)

    hist: dict[str, list] = {k: [] for k in
                             ("V", "stat", "sel_frac", "gamma", "time")}
    t0 = time.perf_counter()
    converged = False
    for _ in range(cfg.max_iters):
        state, info = step(A_dev, colsq, b_dev, state)
        stat = float(info["stat"])
        hist["V"].append(float(info["V"]))
        hist["stat"].append(stat)
        hist["sel_frac"].append(float(info["sel_frac"]))
        hist["gamma"].append(float(info["gamma"]))
        hist["time"].append(time.perf_counter() - t0)
        if stat <= cfg.tol:
            converged = True
            break
    x_full = np.asarray(state.x)[:n]
    return SolverResult(x=jnp.asarray(x_full), iters=int(state.k),
                        converged=converged, history=hist, method="pflexa",
                        state=state, meta={"pad": pad, "n_shards": p})
