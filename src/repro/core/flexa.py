"""Algorithm 1 — the Flexible Parallel Algorithm (FLEXA) driver.

This is the paper's primary contribution, implemented as a pure-JAX solver:

  (S.1) termination: ‖x̂(xᵏ) − xᵏ‖∞ ≤ tol
  (S.2) best response zᵏ (exact or inexact, per surrogate choice)
  (S.3) greedy ρ-selection mask from the error bound Eᵢ = ‖x̂ᵢ − xᵢᵏ‖
  (S.4) xᵏ⁺¹ = xᵏ + γᵏ (ẑᵏ − xᵏ), γᵏ from Eq. (4)
  plus the §4 practical τ-controller (double on objective increase, halve
  after ``tau_patience`` consecutive decreases, finitely many changes).

Two drivers are provided:

* :func:`solve` — Python loop around a jitted step; records a per-iteration
  history (objective, stationarity, |Sᵏ|, wall time) for the benchmarks.
* :func:`solve_compiled` — a single ``lax.while_loop`` program (production
  path; no host round trips, usable under pjit on device).

The distributed (shard_map) version lives in ``repro.core.pflexa``.
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config.base import SolverConfig
from repro.core import selection, stepsize
from repro.core.surrogate import best_response, curvature
from repro.obs import trace as obs
from repro.problems.base import Problem
from repro.problems.sparse import capacity_bucket, is_sparse, stack_designs
from repro.core.result import SolverResult


class FlexaState(NamedTuple):
    x: jnp.ndarray
    u: jnp.ndarray              # the design product at x (Problem.product)
    gamma: jnp.ndarray          # scalar γᵏ
    tau_scale: jnp.ndarray      # scalar multiplier on the base τ vector
    v_prev: jnp.ndarray         # V(xᵏ)
    consec_dec: jnp.ndarray     # consecutive-decrease counter (τ rule)
    n_tau_changes: jnp.ndarray  # finite-change budget accounting
    k: jnp.ndarray              # iteration counter
    stat: jnp.ndarray           # ‖x̂(xᵏ)−xᵏ‖∞ of the *last* step
    key: jnp.ndarray            # PRNG key (randomized selection rules)


# All solvers in the repo share one result contract (repro.solvers.result);
# the old per-module name is kept as an alias for existing call sites.
FlexaResult = SolverResult

MAX_TAU_CHANGES = 60  # "finite number of changes" cap (Theorem 1 compliance)


def tau0_from_colsq(col_sq, n: int):
    """Paper §4 default  τᵢ = tr(AᵀA)/2n  from the column norms ‖aᵢ‖².

    Traceable — shared by :func:`default_tau0` (host path) and the batched
    engine (``repro.solvers.batched._tau_base``), so the two drivers can
    never disagree on the default.
    """
    return jnp.sum(col_sq) / (2.0 * n)


def default_tau0(problem: Problem) -> float:
    """Paper §4: τᵢ = tr(AᵀA)/2n for Lasso-type quadratics.

    tr(AᵀA) = Σᵢ‖aᵢ‖² = Σᵢ diag_curv/2 for F = ‖Ax−b‖².
    """
    col_sq = problem.diag_curv(None) / 2.0
    return float(tau0_from_colsq(col_sq, problem.n))


def _base_tau(problem: Problem, cfg: SolverConfig) -> jnp.ndarray:
    t0 = cfg.tau0 if cfg.tau0 > 0 else default_tau0(problem)
    return jnp.full((problem.n,), t0, dtype=jnp.float32)


def init_state(problem: Problem, x0, cfg: SolverConfig,
               key=None) -> FlexaState:
    """``key`` seeds the randomized selection rules; it defaults to
    ``PRNGKey(cfg.seed)`` (the batched engine folds in the instance index
    so every instance follows its own stream)."""
    x0 = jnp.asarray(x0, dtype=jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    u0 = problem.product(x0)
    return FlexaState(
        x=x0,
        u=u0,
        gamma=jnp.asarray(cfg.gamma0, jnp.float32),
        tau_scale=jnp.asarray(1.0, jnp.float32),
        v_prev=jnp.asarray(problem.v_at(u0, x0), jnp.float32),
        consec_dec=jnp.asarray(0, jnp.int32),
        n_tau_changes=jnp.asarray(0, jnp.int32),
        k=jnp.asarray(0, jnp.int32),
        stat=jnp.asarray(jnp.inf, jnp.float32),
        key=key,
    )


def flexa_iteration(problem: Problem, cfg: SolverConfig,
                    tau_base: jnp.ndarray, state: FlexaState,
                    active: jnp.ndarray | None = None):
    """One Algorithm-1 iteration ``state -> (state, info)`` — S.2–S.4 plus
    the §4 τ-controller.

    Pure and traceable: the same function backs the jitted per-step driver
    (:func:`make_step`), the single-program ``lax.while_loop`` driver
    (:func:`solve_compiled`), and the batched multi-instance engine
    (``repro.solvers.batched`` vmaps it over a stack of problems, with the
    problem closures rebuilt from per-instance data inside the vmap).

    ``active`` is an optional per-coordinate {0,1} *freeze mask* (the
    regularization-path engine's safe-screening hook, ``repro.path``):
    coordinates with ``active == 0`` are excluded from the selection set
    Sᵏ, never updated, and excluded from the ‖x̂−x‖∞ termination measure —
    the solver runs on the induced subproblem while the compiled program
    keeps its full fixed shape.  ``None`` (the default) is bit-identical
    to the unmasked iteration; a mask of all-ones multiplies by exact
    fp32 1.0s, so it is bit-identical too.

    The state carries ``u``, the problem's design product at ``x``
    (``Problem.product``: A·x − b, or Z·x).  The gradient reads it, and
    the objective computes it at the new iterate, which the next
    iteration's gradient reads: an iteration makes one transpose product
    (under ``grad``) and one forward product (under ``objective``).

    Each step runs under a ``jax.named_scope`` (``grad``,
    ``best_response``, ``select``, ``update``, ``objective``, ``tau``):
    the scope names the step in the compiled operations' metadata, which
    a profiler trace carries, and adds no operation.
    """
    x = state.x
    tau = tau_base * state.tau_scale
    with jax.named_scope("grad"):
        grad = problem.loss_grad(state.u)
    with jax.named_scope("best_response"):
        d = curvature(problem, tau, cfg.surrogate)
    if active is not None:
        active = jnp.asarray(active, jnp.float32)
        active_b = active if problem.block_size == 1 \
            else problem.blockify(active)[:, 0]

    # (S.2) best response; optionally inexact with the Thm-1(v) schedule.
    with jax.named_scope("best_response"):
        if cfg.inexact_alpha1 > 0 and problem.block_size > 1:
            inner = 5  # few inner prox-grad steps; cert recorded in info
            zhat, cert = best_response(problem, x, grad, d,
                                       inner_iters=inner, eps=0.0)
        else:
            zhat = best_response(problem, x, grad, d)
            cert = jnp.asarray(0.0)

    # (S.3) error bound + selection rule (greedy by default; random/hybrid/
    # cyclic per cfg.selection — see repro.core.selection.make_mask).
    # Screened-out blocks contribute E = 0, so the greedy threshold ρ·M is
    # measured over the surviving subproblem, and the final mask multiply
    # keeps them out of Sᵏ whatever the rule picked.
    with jax.named_scope("select"):
        E = problem.block_norms(zhat - x)
        if active is not None:
            E = E * active_b
        M = jnp.max(E)
        if selection.needs_key(cfg.selection) and not cfg.jacobi:
            key, sub = jax.random.split(state.key)
        else:
            key, sub = state.key, state.key
        mask_b = selection.make_mask(E, cfg, sub, state.k, M=M)
        if active is not None:
            mask_b = mask_b * active_b
        mask = mask_b if problem.block_size == 1 \
            else jnp.repeat(mask_b, problem.block_size)

    # (S.4) damped, masked update.
    with jax.named_scope("update"):
        xnew = x + state.gamma * mask * (zhat - x)
    with jax.named_scope("objective"):
        u_new = problem.product(xnew)
        v_new = problem.v_at(u_new, xnew)

    # §4 τ-controller (finitely many changes).
    with jax.named_scope("tau"):
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

    # ‖x̂−x‖∞ termination measure (over surviving coordinates only when a
    # freeze mask is injected — frozen coordinates are certified by the
    # screening KKT recheck, not by the solver).
    with jax.named_scope("select"):
        step_err = jnp.abs(zhat - x)
        if active is not None:
            step_err = step_err * active
        stat = jnp.max(step_err)
    new_state = FlexaState(
        x=xnew,
        u=u_new,
        gamma=stepsize.gamma_next(state.gamma, cfg.theta),
        tau_scale=tau_scale,
        v_prev=v_new,
        consec_dec=consec,
        n_tau_changes=n_changes,
        k=state.k + 1,
        stat=stat,
        key=key,
    )
    info = {
        "V": v_new,
        "stat": stat,
        "E_max": M,
        "sel_frac": jnp.mean(mask_b),
        "gamma": state.gamma,
        "tau_scale": tau_scale,
        "inexact_cert": cert,
    }
    return new_state, info


def make_step(problem: Problem, cfg: SolverConfig, active=None):
    """Build the jitted Algorithm-1 iteration ``state -> (state, info)``.

    ``active`` optionally restricts the step to a per-coordinate freeze
    mask (see :func:`flexa_iteration`).

    A registered family's data arrays enter the compiled step as
    arguments, its F closures rebuilt inside from them: arrays a jitted
    function closes over are compiled into the program as constants, so
    every design matrix would be copied into its executable (hundreds of
    MB at fig1b size, past the 2 GB program limit at fig1d).  Ad-hoc
    problems keep their closures."""
    from repro.problems.families import (available_families, build_problem,
                                         get_family)

    tau_base = _base_tau(problem, cfg)
    if active is not None:
        active = jnp.asarray(active, jnp.float32)
    fam = (get_family(problem.family)
           if problem.family in available_families() else None)
    if fam is None or any(k not in problem.data for k in fam.data_keys):
        @jax.jit
        def step(state: FlexaState):
            return flexa_iteration(problem, cfg, tau_base, state,
                                   active=active)

        return step

    # A batch of one, vmapped like the batched engine's iteration: the
    # products then reduce in the order they do there, which keeps solo
    # and batched trajectories together.
    arrays = tuple(_batch_of_one(problem.data[k]) for k in fam.data_keys)
    col_sq = jax.vmap(fam.col_sq)(*arrays)

    def instance_step(arrays, col_sq, state, tau_base, active):
        p = build_problem(problem.family, arrays, problem.g_weight,
                          n=problem.n, block_size=problem.block_size,
                          g_kind=problem.g_kind, col_sq=col_sq)
        return flexa_iteration(p, cfg, tau_base, state, active=active)

    @jax.jit
    def family_step(arrays, col_sq, tau_base, active, state: FlexaState):
        one = jax.tree_util.tree_map(lambda a: a[None], state)
        new, info = jax.vmap(instance_step, in_axes=(0, 0, 0, None, None))(
            arrays, col_sq, one, tau_base, active)
        return jax.tree_util.tree_map(lambda a: a[0], (new, info))

    return lambda state: family_step(arrays, col_sq, tau_base, active,
                                     state)


def _batch_of_one(a):
    """One instance's data array (or sparse design, in the stored
    layout) with a leading batch axis of one."""
    if is_sparse(a):
        return stack_designs([a], capacity_bucket(a.capacity, a.m, a.n))
    return jnp.asarray(a)[None]


def solve(problem: Problem, x0=None, cfg: SolverConfig | None = None,
          callback=None, active=None) -> FlexaResult:
    """Python-loop driver with history recording (benchmark path).

    ``active`` restricts the solve to a fixed per-coordinate active set
    (screening support for ``repro.path``); frozen coordinates keep their
    ``x0`` value untouched.

    Traced (``repro.obs``): one ``solo.solve`` span (args ``iters``,
    ``converged``) holding ``solo.prepare`` (the step's build and the
    initial state) and, per iteration ``it``, ``solo.dispatch`` (the
    step call; the first one traces the fresh step), ``solo.sync`` (the
    first readback, where the host waits for the device) and
    ``solo.readback`` (the other scalar reads and the history)."""
    cfg = cfg or SolverConfig()
    with obs.span("solo.solve", cat="solo") as whole:
        if x0 is None:
            x0 = jnp.zeros((problem.n,), jnp.float32)
        with obs.span("solo.prepare", cat="solo"):
            step = make_step(problem, cfg, active=active)
            state = init_state(problem, x0, cfg)

        hist: dict[str, list] = {k: [] for k in
                                 ("V", "stat", "E_max", "sel_frac", "gamma",
                                  "time", "tau_scale")}
        t0 = time.perf_counter()
        converged = False
        for it in range(cfg.max_iters):
            with obs.span("solo.dispatch", cat="solo", it=it):
                state, info = step(state)
            with obs.span("solo.sync", cat="solo", it=it):
                stat = float(info["stat"])
            with obs.span("solo.readback", cat="solo", it=it):
                for key in ("V", "stat", "E_max", "sel_frac", "gamma",
                            "tau_scale"):
                    hist[key].append(float(info[key]))
                hist["time"].append(time.perf_counter() - t0)
            if callback is not None:
                callback(it, state, info)
            if stat <= cfg.tol:
                converged = True
                break
        iters = int(state.k)
        if whole is not None:
            whole.args.update(iters=iters, converged=converged)
    return SolverResult(x=state.x, iters=iters, converged=converged,
                        state=state, history=hist, method="flexa")


def solve_compiled(problem: Problem, x0=None,
                   cfg: SolverConfig | None = None) -> FlexaResult:
    """Single-program ``lax.while_loop`` driver (no host sync per step)."""
    cfg = cfg or SolverConfig()
    if x0 is None:
        x0 = jnp.zeros((problem.n,), jnp.float32)
    step = make_step(problem, cfg)

    def cond(state: FlexaState):
        return (state.k < cfg.max_iters) & (state.stat > cfg.tol)

    def body(state: FlexaState):
        new_state, _ = step(state)
        return new_state

    final = jax.lax.while_loop(cond, body, init_state(problem, x0, cfg))
    return SolverResult(x=final.x, iters=int(final.k),
                        converged=bool(final.stat <= cfg.tol), state=final,
                        method="flexa_compiled")
