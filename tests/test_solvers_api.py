"""Unified solver API: one facade, one result contract, and the batched
multi-instance engine matching independent solves (the PR's acceptance
criteria live here)."""
import numpy as np
import pytest

from repro.config.base import SolverConfig
from repro.problems.lasso import nesterov_instance
from repro.problems.logreg import random_logreg_instance
from repro.problems.svm import random_svm_instance
from repro.solvers import available_methods, SolverResult
from repro.solvers.api import _solve as solve
from repro.solvers.batched import _solve_batched as solve_batched

FIVE_METHODS = ("flexa", "fista", "admm", "grock", "gauss_seidel")


@pytest.fixture(scope="module")
def mini_lasso():
    return nesterov_instance(m=30, n=100, nnz_frac=0.1, c=1.0, seed=0)


@pytest.fixture(scope="module")
def mini_batch():
    return [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
            for s in range(4)]


def test_registry_exposes_the_whole_family():
    methods = available_methods()
    for m in FIVE_METHODS + ("jacobi", "flexa_compiled", "pflexa"):
        assert m in methods


@pytest.mark.parametrize("method", FIVE_METHODS)
def test_facade_runs_all_five_methods(mini_lasso, method):
    """`from repro.solvers import solve` drives every algorithm on the same
    miniature Lasso through one call signature and one result contract."""
    # GRock runs serial here: its P>1 variant legitimately diverges on
    # correlated columns (the paper's point; tested in test_baselines).
    options = {"P": 1} if method == "grock" else {}
    r = solve(mini_lasso, method=method,
              cfg=SolverConfig(max_iters=400, tol=1e-7), **options)
    assert isinstance(r, SolverResult)
    assert r.method == method
    assert np.asarray(r.x).shape == (mini_lasso.n,)
    assert r.iters >= 1
    # shared history contract
    for key in ("V", "stat", "time"):
        assert len(r.history[key]) == r.iters
    # all five reach the planted optimum neighbourhood on this instance
    rel = (r.history["V"][-1] - mini_lasso.v_star) / mini_lasso.v_star
    assert rel < 1e-2, (method, rel)


def test_facade_rejects_unknown_method(mini_lasso):
    with pytest.raises(KeyError, match="unknown solver"):
        solve(mini_lasso, method="newton_raphson")


def test_facade_rejects_unknown_option(mini_lasso):
    with pytest.raises(TypeError, match="unknown solver options"):
        solve(mini_lasso, method="fista", momentum=0.9)


def test_method_specific_options_reach_the_algorithm(mini_lasso):
    r1 = solve(mini_lasso, method="grock", P=1,
               cfg=SolverConfig(max_iters=50, tol=0))
    rN = solve(mini_lasso, method="grock", P=16,
               cfg=SolverConfig(max_iters=50, tol=0))
    # more parallel coordinates per iteration ⇒ different trajectory
    assert r1.history["V"][-1] != rN.history["V"][-1]


# ------------------------------------------------------------------ #
# Batched multi-instance engine                                      #
# ------------------------------------------------------------------ #
def test_solve_batched_matches_independent_solves(mini_batch):
    """Acceptance: per-instance batched solutions == B independent solve()
    calls (atol 1e-5).

    Compared over a fixed iteration budget with tau_adapt=False so both
    drivers take the exact same number of identical smooth steps: the
    τ-controller and tol-based stopping both branch on last-bit fp32
    comparisons, which makes *stopping times* (not solutions) sensitive to
    matvec reduction order — see repro/solvers/batched.py docstring.
    tol=-1 disables even the exact-fixed-point (stat == 0.0) early exit."""
    cfg = SolverConfig(max_iters=300, tol=-1.0, tau_adapt=False)
    rb = solve_batched(mini_batch, cfg=cfg)
    assert np.asarray(rb.x).shape == (len(mini_batch), mini_batch[0].n)
    assert (np.asarray(rb.iters) == 300).all()
    for i, p in enumerate(mini_batch):
        ri = solve(p, method="flexa", cfg=cfg)
        assert ri.iters == 300
        np.testing.assert_allclose(np.asarray(rb.x[i]), np.asarray(ri.x),
                                   atol=1e-5)


def test_solve_batched_default_cfg_reaches_each_optimum(mini_batch):
    """With the full adaptive-τ configuration every instance still lands on
    its own planted optimum (trajectories need not be bit-identical)."""
    rb = solve_batched(mini_batch, cfg=SolverConfig(max_iters=1500,
                                                    tol=1e-7))
    assert np.asarray(rb.converged).all()
    for i, p in enumerate(mini_batch):
        v = float(p.v(rb.x[i]))
        assert (v - p.v_star) / p.v_star < 1e-5


def test_solve_batched_history_driver(mini_batch):
    B = len(mini_batch)
    rb = solve_batched(mini_batch, cfg=SolverConfig(max_iters=40, tol=0),
                       record_history=True)
    assert len(rb.history["V"]) == 40
    assert rb.history["V"][0].shape == (B,)
    assert (np.asarray(rb.iters) == 40).all()
    # trajectories descend
    assert (rb.history["V"][-1] <= rb.history["V"][0]).all()


def test_solve_batched_rejects_mixed_shapes(mini_batch):
    odd = nesterov_instance(m=24, n=64, nnz_frac=0.15, c=1.0, seed=9)
    with pytest.raises(ValueError, match="shape signature"):
        solve_batched(mini_batch + [odd])


def test_solve_batched_heterogeneous_regularization():
    """Per-instance c is part of the batched contract (serving requests
    carry their own regularization weight)."""
    base = nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=0)
    import dataclasses
    weak = dataclasses.replace(base, g_weight=0.1)
    cfg = SolverConfig(max_iters=300, tol=-1.0, tau_adapt=False)
    rb = solve_batched([base, weak], cfg=cfg)
    nnz = (np.abs(np.asarray(rb.x)) > 1e-6).sum(axis=1)
    assert nnz[1] > nnz[0]          # weaker ℓ1 ⇒ denser solution
    for i, p in enumerate((base, weak)):
        ri = solve(p, method="flexa", cfg=cfg)
        np.testing.assert_allclose(np.asarray(rb.x[i]), np.asarray(ri.x),
                                   atol=1e-5)


# ------------------------------------------------------------------ #
# Problem families in the batched engine                             #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("make,family", [
    (lambda s: random_logreg_instance(m=30, n=48, nnz_frac=0.2, c=0.5,
                                      seed=s), "logreg"),
    (lambda s: random_svm_instance(m=30, n=40, nnz_frac=0.2, c=0.5,
                                   seed=s), "svm"),
])
def test_solve_batched_families_match_independent_solves(make, family):
    """Acceptance: a logreg batch and an svm batch each match B sequential
    solve() calls to ≤1e-5 (fixed iters, tau_adapt=False — same fp32
    reduction-order caveat as the Lasso equivalence test: even the greedy
    mask branches on exact comparisons, so very long budgets can let a
    last-bit E-threshold flip split trajectories)."""
    probs = [make(s) for s in range(4)]
    cfg = SolverConfig(max_iters=200, tol=-1.0, tau_adapt=False)
    rb = solve_batched(probs, cfg=cfg)
    assert rb.meta["family"] == family
    assert (np.asarray(rb.iters) == 200).all()
    for i, p in enumerate(probs):
        ri = solve(p, method="flexa", cfg=cfg)
        assert ri.iters == 200
        np.testing.assert_allclose(np.asarray(rb.x[i]), np.asarray(ri.x),
                                   atol=1e-5)


def test_solve_batched_rejects_mixed_families():
    lr = random_logreg_instance(m=30, n=48, nnz_frac=0.2, c=0.5, seed=0)
    sv = random_svm_instance(m=30, n=48, nnz_frac=0.2, c=0.5, seed=0)
    with pytest.raises(ValueError, match="shape signature"):
        solve_batched([lr, sv])


def test_batched_hybrid_selection_reaches_each_optimum(mini_batch):
    """Randomized selection inside the compiled batched program: every
    instance still converges (per-instance PRNG streams via fold_in)."""
    cfg = SolverConfig(max_iters=3000, tol=1e-6, selection="hybrid",
                       sel_p=0.5, seed=7)
    rb = solve_batched(mini_batch, cfg=cfg)
    assert np.asarray(rb.converged).all()
    for i, p in enumerate(mini_batch):
        v = float(p.v(rb.x[i]))
        assert (v - p.v_star) / p.v_star < 1e-4


# ------------------------------------------------------------------ #
# Selection rules through the facade                                 #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("rule", ["hybrid", "random", "cyclic", "topk",
                                  "southwell"])
def test_selection_rules_reach_greedy_optimum(mini_lasso, rule):
    """Every S.3 rule drives Algorithm 1 to the same planted optimum the
    greedy rule finds (random rules just take more iterations)."""
    cfg = SolverConfig(max_iters=4000, tol=1e-7, selection=rule,
                       sel_k=16, seed=1)
    r = solve(mini_lasso, method="flexa", cfg=cfg)
    rel = (r.history["V"][-1] - mini_lasso.v_star) / mini_lasso.v_star
    assert rel < 1e-5, (rule, rel)


def test_random_selection_is_seed_deterministic(mini_lasso):
    cfg = SolverConfig(max_iters=50, tol=0, selection="random", seed=3)
    r1 = solve(mini_lasso, method="flexa", cfg=cfg)
    r2 = solve(mini_lasso, method="flexa", cfg=cfg)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    r3 = solve(mini_lasso, method="flexa",
               cfg=SolverConfig(max_iters=50, tol=0, selection="random",
                                seed=4))
    assert not np.array_equal(np.asarray(r1.x), np.asarray(r3.x))


# ------------------------------------------------------------------ #
# Solver serving engine                                              #
# ------------------------------------------------------------------ #
def test_solver_serve_engine_heterogeneous_family_mix(mini_batch):
    """One engine serving Lasso, logreg and svm requests together: each
    family lands in its own slab (one per family signature) and every
    response matches its solo solve."""
    from repro.config.base import ServeConfig
    from repro.serve import ContinuousSolverEngine, SolveRequest

    cfg = SolverConfig(max_iters=2000, tol=1e-6, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=4, chunk_iters=50))
    probs = list(mini_batch[:2]) \
        + [random_logreg_instance(m=30, n=48, nnz_frac=0.2, c=0.5, seed=s)
           for s in range(2)] \
        + [random_svm_instance(m=30, n=40, nnz_frac=0.2, c=0.5, seed=0)]
    reqs = [SolveRequest(A=np.asarray(p.data["A"]),
                         b=np.asarray(p.data["b"]), c=float(p.g_weight))
            for p in probs[:2]]
    reqs += [SolveRequest(A=np.asarray(p.data["Z"]), c=float(p.g_weight),
                          family=p.family) for p in probs[2:]]

    ids = [eng.submit(r) for r in reqs]
    out = eng.drain()
    resps = [out[i] for i in ids]
    assert len(eng._slabs) == 3
    assert all(r.converged for r in resps)
    for i, p in enumerate(probs):
        ri = solve(p, method="flexa", cfg=cfg)
        np.testing.assert_allclose(resps[i].x, np.asarray(ri.x), atol=1e-4)


def test_solver_serve_engine_rejects_malformed_family_requests():
    from repro.serve import ContinuousSolverEngine, SolveRequest

    eng = ContinuousSolverEngine(SolverConfig(max_iters=10))
    Z = np.zeros((5, 4), np.float32)
    with pytest.raises(ValueError, match="takes no b"):
        eng.submit(SolveRequest(A=Z, b=np.zeros(5, np.float32),
                                family="logreg"))
    with pytest.raises(ValueError, match="needs b"):
        eng.submit(SolveRequest(A=Z, c=1.0))
    # atomic rejection: nothing queued, no request recorded
    assert eng.pending == 0 and not eng.telemetry.requests
