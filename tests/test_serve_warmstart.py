"""Warm-start admission, path serving and scheduler fairness
(``repro.serve.continuous`` additions of the path PR).

* ``SolveRequest.x0`` splice: an admission carrying a warm start begins
  iterating from it (an exact-solution x0 converges in a handful of
  iterations);
* ``warm_from`` sugar: deferred admission until the referenced request
  finishes, no head-of-line blocking for independent requests, validated
  against unknown ids / signature mismatches;
* ``PathRequest``: point-by-point path serving matches the
  ``repro.path.solve_path`` driver, with screening counters populated;
* multi-signature fairness: with ``slabs_per_tick = 1`` the tick
  rotation services every (family × shape) slab within n_slabs ticks —
  the starvation test.
"""
import numpy as np
import pytest

from repro.config.base import ServeConfig, SolverConfig
from repro.path.driver import _solve_path as solve_path
from repro.problems.lasso import nesterov_instance
from repro.serve import ContinuousSolverEngine, PathRequest, SolveRequest
from repro.solvers.api import _solve as solve

CFG = SolverConfig(tol=1e-7, max_iters=3000, tau_adapt=False)


def _instance(seed=1, m=30, n=96):
    p = nesterov_instance(m=m, n=n, nnz_frac=0.1, c=1.0, seed=seed)
    return (p, np.asarray(p.data["A"], np.float32),
            np.asarray(p.data["b"], np.float32))


# ------------------------------------------------------------------ #
# x0 splice
# ------------------------------------------------------------------ #
def test_x0_splice_warm_start_admission():
    p, A, b = _instance()
    solo = solve(p, cfg=CFG)
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=16))
    rid = eng.submit(SolveRequest(A=A, b=b, c=1.0,
                                  x0=np.asarray(solo.x, np.float32)))
    out = eng.drain()
    # From the exact solution the very first chunk converges...
    assert out[rid].iters <= 16
    # ...to the same answer.
    np.testing.assert_allclose(out[rid].x, np.asarray(solo.x), atol=1e-6)


def test_active_mask_request_freezes_coordinates():
    p, A, b = _instance()
    n = A.shape[1]
    mask = np.ones(n, np.float32)
    mask[n // 2:] = 0.0          # freeze the upper half
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=1, chunk_iters=16))
    rid = eng.submit(SolveRequest(A=A, b=b, c=1.0, active_mask=mask))
    out = eng.drain()
    assert np.all(out[rid].x[n // 2:] == 0.0)
    ref = solve(p, cfg=CFG, active=mask)
    np.testing.assert_allclose(out[rid].x, np.asarray(ref.x), atol=1e-5)


# ------------------------------------------------------------------ #
# warm_from sugar
# ------------------------------------------------------------------ #
def test_warm_from_defers_until_dependency_finishes():
    _, A, b = _instance()
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=25))
    a = eng.submit(SolveRequest(A=A, b=b, c=1.0))
    w = eng.submit(SolveRequest(A=A, b=b, c=0.9, warm_from=a))
    free = eng.submit(SolveRequest(A=A, b=b, c=0.8))
    out = eng.drain()
    rec = {r["req_id"]: r for r in eng.audit}
    # the dependent request waited for its producer...
    assert rec[w]["admit_tick"] > rec[a]["evict_tick"]
    # ...but did NOT block the independent request behind it
    assert rec[free]["admit_tick"] == 1
    # and solves the same problem as an explicit-x0 submission
    eng2 = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=25))
    x0 = out[a].x
    r2 = eng2.submit(SolveRequest(A=A, b=b, c=0.9,
                                  x0=np.asarray(x0, np.float32)))
    out2 = eng2.drain()
    np.testing.assert_allclose(out[w].x, out2[r2].x, atol=1e-6)


def test_warm_from_validation_errors():
    _, A, b = _instance()
    p2, A2, b2 = _instance(seed=2, m=20, n=64)
    eng = ContinuousSolverEngine(CFG, ServeConfig(slab_capacity=1,
                                                  chunk_iters=16))
    a = eng.submit(SolveRequest(A=A, b=b, c=1.0))
    with pytest.raises(ValueError, match="unknown request id"):
        eng.submit(SolveRequest(A=A, b=b, c=1.0, warm_from=999))
    with pytest.raises(ValueError, match="signature mismatch"):
        eng.submit(SolveRequest(A=A2, b=b2, c=1.0, warm_from=a))
    with pytest.raises(ValueError, match="mutually exclusive"):
        eng.submit(SolveRequest(A=A, b=b, c=1.0, warm_from=a,
                                x0=np.zeros(A.shape[1], np.float32)))
    eng.drain()


# ------------------------------------------------------------------ #
# PathRequest through the engine
# ------------------------------------------------------------------ #
def test_path_request_matches_driver():
    p, A, b = _instance()
    ref = solve_path(p, n_points=8, lam_min_ratio=0.05, cfg=CFG)
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=4, chunk_iters=25))
    pid = eng.submit_path(PathRequest(A=A, b=b, n_points=8,
                                      lam_min_ratio=0.05))
    eng.drain()
    res = eng.path_result(pid)
    assert res["done"]
    np.testing.assert_allclose(res["lambdas"], ref.lambdas, rtol=1e-6)
    np.testing.assert_allclose(res["x"], ref.x, atol=1e-5)
    assert res["screened_out"].sum() > 0
    # between points a path holds no slot: each point is its own request
    assert len(res["req_ids"]) >= 8 - 1   # head point may be trivial


def test_concurrent_paths_share_one_slab():
    """Two CV-fold-style paths interleave through one signature's slab
    and both come out exact."""
    p1, A1, b1 = _instance(seed=3)
    p2, A2, b2 = _instance(seed=4)
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=25))
    pid1 = eng.submit_path(PathRequest(A=A1, b=b1, n_points=6,
                                       lam_min_ratio=0.1))
    pid2 = eng.submit_path(PathRequest(A=A2, b=b2, n_points=6,
                                       lam_min_ratio=0.1))
    eng.drain()
    for pid, p in ((pid1, p1), (pid2, p2)):
        res = eng.path_result(pid)
        assert res["done"]
        ref = solve_path(p, lambdas=res["lambdas"], cfg=CFG)
        np.testing.assert_allclose(res["x"], ref.x, atol=1e-5)


# ------------------------------------------------------------------ #
# Multi-signature fairness
# ------------------------------------------------------------------ #
def test_round_robin_tick_never_starves_a_signature():
    """With slabs_per_tick=1 and a request stream that keeps the first
    signature's queue perpetually full, the second signature still gets
    serviced within 2 ticks of its submission — dict-order servicing
    would let the chatty signature monopolize every tick."""
    _, A, b = _instance()
    _, A2, b2 = _instance(seed=2, m=20, n=64)
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=1, chunk_iters=8,
                         slabs_per_tick=1))
    eng.submit(SolveRequest(A=A, b=b, c=1.0))
    victim = eng.submit(SolveRequest(A=A2, b=b2, c=1.0))
    victim_done_at = None
    for tick in range(1, 400):
        # keep signature A saturated: one fresh request per tick
        eng.submit(SolveRequest(A=A, b=b, c=1.0))
        done = eng.step()
        if victim in done:
            victim_done_at = tick
            break
    assert victim_done_at is not None, "second signature starved"
    rec = {r["req_id"]: r for r in eng.audit}
    assert rec[victim]["admit_tick"] <= 2


def test_slabs_per_tick_rotation_covers_all_signatures():
    sigs = [_instance(seed=s, m=16 + 4 * s, n=48) for s in (1, 2, 3)]
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=1, chunk_iters=8,
                         slabs_per_tick=1))
    ids = [eng.submit(SolveRequest(A=A, b=b, c=1.0))
           for _, A, b in sigs]
    out = eng.drain()
    assert set(ids) <= set(out)
    # every signature admitted within the first rotation sweep
    rec = {r["req_id"]: r for r in eng.audit}
    assert max(rec[i]["admit_tick"] for i in ids) <= 3


def test_default_config_services_all_slabs_each_tick():
    """slabs_per_tick=0 (default) keeps the pre-PR behaviour: every slab
    advances every tick."""
    sigs = [_instance(seed=s, m=16 + 4 * s, n=48) for s in (1, 2)]
    eng = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=1, chunk_iters=8))
    ids = [eng.submit(SolveRequest(A=A, b=b, c=1.0))
           for _, A, b in sigs]
    eng.step()
    rec = {r["req_id"]: r for r in eng.audit}
    assert all(rec[i]["admit_tick"] == 1 for i in ids)
    eng.drain()
