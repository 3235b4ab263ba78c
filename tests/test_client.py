"""``repro.client`` — the one front door.

The acceptance matrix lives here: all four workload kinds (solo, batch,
path, CV) × three backends (inline, continuous, mesh) × ≥2 problem
families, each compared against the legacy entry point it replaces:

* inline results are **bitwise** equal to the legacy path (same code,
  same compiled program — the deterministic-config guarantee);
* serve backends agree within the stack's established 1e-5 tol-stopping
  envelope (fp32 reduction-order noise shifts stopping times, never
  answers — see repro/solvers/batched.py).

Plus the session behaviours (stream/step/pending,
backend capability errors, spec validation, ClientConfig composition)
and the coarse-to-fine CV continuation contract.
"""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.client import (BatchSpec, CVSpec, FlexaClient, PathSpec,
                          SoloSpec, SpecError, UnknownBackendError,
                          UnsupportedWorkloadError, available_backends)
from repro.config.base import ClientConfig, ServeConfig, SolverConfig
from repro.problems.lasso import make_lasso, nesterov_instance
from repro.problems.logreg import random_logreg_instance

BACKENDS = ("inline", "continuous", "mesh")
#: Fixed τ + tol-stopping at 1e-7: the configuration whose cross-driver
#: agreement the serve/path PRs measured at ≤1e-5 (3e-6 typical).
CFG = SolverConfig(tol=1e-7, max_iters=4000, tau_adapt=False)
SERVE = ServeConfig(slab_capacity=4, chunk_iters=50)
SOLO_FAMILIES = ("lasso", "logreg")
PATH_FAMILIES = ("lasso", "group_lasso")
GRID = dict(n_points=5, lam_min_ratio=0.1)

ATOL = {"inline": 0.0, "continuous": 1e-5, "mesh": 1e-5}


def client(backend: str) -> FlexaClient:
    return FlexaClient(backend=backend, solver=CFG, serve=SERVE)


def _instance(family: str, seed: int):
    if family == "lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed)
    if family == "group_lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed, block_size=4)
    return random_logreg_instance(m=24, n=48, nnz_frac=0.15, c=0.5,
                                  seed=seed)


def _assert_close(got, ref, backend: str):
    if ATOL[backend] == 0.0:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=ATOL[backend])


@pytest.fixture(autouse=True)
def _silence_legacy_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        yield


# ------------------------------------------------------------------ #
# The equivalence matrix                                             #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def solo_refs():
    from repro.solvers.api import _solve
    return {f: _solve(_instance(f, 0), cfg=CFG) for f in SOLO_FAMILIES}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", SOLO_FAMILIES)
def test_matrix_solo(backend, family, solo_refs):
    got = client(backend).run(SoloSpec(problem=_instance(family, 0)))
    assert got.backend == backend
    assert got.converged
    _assert_close(got.x, solo_refs[family].x, backend)


@pytest.fixture(scope="module")
def batch_refs():
    from repro.solvers.batched import _solve_batched
    return {f: _solve_batched([_instance(f, s) for s in range(3)],
                              cfg=CFG) for f in SOLO_FAMILIES}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", SOLO_FAMILIES)
def test_matrix_batch(backend, family, batch_refs):
    probs = [_instance(family, s) for s in range(3)]
    got = client(backend).run(BatchSpec(problems=probs))
    assert len(got) == 3 and np.asarray(got.converged).all()
    _assert_close(got.x, batch_refs[family].x, backend)


@pytest.fixture(scope="module")
def path_refs():
    from repro.path.driver import _solve_path
    return {f: _solve_path(_instance(f, 0), cfg=CFG, **GRID)
            for f in PATH_FAMILIES}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", PATH_FAMILIES)
def test_matrix_path(backend, family, path_refs):
    got = client(backend).run(PathSpec(problem=_instance(family, 0),
                                       **GRID))
    ref = path_refs[family]
    np.testing.assert_allclose(got.lambdas, ref.lambdas, rtol=1e-12)
    _assert_close(got.x, ref.x, backend)
    assert list(got.support) == list(ref.support)


def _cv_data(family: str):
    """K=3 folds + validation pairs sharing one shape signature."""
    rng = np.random.default_rng(7)
    n, bs = 48, (4 if family == "group_lasso" else 1)
    x_true = np.zeros(n, np.float32)
    x_true[rng.choice(n, 6, replace=False)] = 1.0
    folds, val = [], []
    for i in range(3):
        A = rng.standard_normal((24, n)).astype(np.float32)
        b = A @ x_true + 0.3 * rng.standard_normal(24).astype(np.float32)
        Av = rng.standard_normal((12, n)).astype(np.float32)
        bv = Av @ x_true + 0.3 * rng.standard_normal(12).astype(
            np.float32)
        folds.append(make_lasso(A, b, c=1.0, name=f"{family}_f{i}",
                                block_size=bs))
        val.append((Av, bv))
    return folds, val


@pytest.fixture(scope="module")
def cv_refs():
    """Legacy CV: lockstep fold sweep + manual mean-MSE selection."""
    from repro.path.driver import _solve_path_batched
    out = {}
    for f in PATH_FAMILIES:
        folds, val = _cv_data(f)
        paths = _solve_path_batched(folds, cfg=CFG, **GRID)
        P = paths[0].lambdas.shape[0]
        mse = np.array([[float(np.sum((Av @ paths[i].x[k] - bv) ** 2))
                         / Av.shape[0]
                         for k in range(P)]
                        for i, (Av, bv) in enumerate(val)])
        best = int(np.argmin(mse.mean(axis=0)))
        out[f] = {"paths": paths, "best": best,
                  "best_lambda": float(paths[0].lambdas[best])}
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", PATH_FAMILIES)
def test_matrix_cv(backend, family, cv_refs):
    folds, val = _cv_data(family)
    got = client(backend).run(CVSpec(problems=folds, validation=val,
                                     **GRID))
    ref = cv_refs[family]
    assert got.best_index == ref["best"]
    assert got.best_lambda == pytest.approx(ref["best_lambda"],
                                            rel=1e-12)
    for i, path in enumerate(ref["paths"]):
        _assert_close(got.folds[i].x, path.x, backend)
    _assert_close(got.x_best,
                  np.stack([p.x[ref["best"]] for p in ref["paths"]]),
                  backend)


@pytest.mark.parametrize("backend", ("continuous", "mesh"))
def test_matrix_path_cold_respects_warm_flag(backend, path_refs):
    """PathSpec.warm/screen reach the serve path protocol too: a cold
    unscreened path through a serve backend matches the inline cold
    reference (it must NOT silently warm-start)."""
    from repro.path.driver import _solve_path

    cold_ref = _solve_path(_instance("lasso", 0), cfg=CFG, warm=False,
                           screen=False, **GRID)
    got = client(backend).run(PathSpec(problem=_instance("lasso", 0),
                                       warm=False, screen=False, **GRID))
    np.testing.assert_allclose(got.x, cold_ref.x, atol=1e-5)
    # ...and per-point iteration counts now follow the cold profile, not
    # the warm one (the warm reference differs from cold on this grid).
    warm_ref = path_refs["lasso"]
    assert list(got.iters) != list(warm_ref.iters) \
        or np.allclose(warm_ref.x, cold_ref.x, atol=1e-7)


# ------------------------------------------------------------------ #
# Determinism (bitwise under fixed seed)                             #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_is_bitwise_deterministic(backend):
    """Two fresh sessions, same spec/config/seed → identical bits (the
    per-request PRNG streams are keyed by request identity, never by
    time or slot)."""
    cfg = dataclasses.replace(CFG, selection="hybrid", sel_p=0.5, seed=3,
                              max_iters=2000)
    spec = BatchSpec(problems=[_instance("lasso", s) for s in range(3)])
    xs = [FlexaClient(backend=backend, solver=cfg, serve=SERVE).run(spec).x
          for _ in range(2)]
    np.testing.assert_array_equal(np.asarray(xs[0]), np.asarray(xs[1]))


# ------------------------------------------------------------------ #
# Coarse-to-fine CV continuation (the tol_coarse contract)           #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend", BACKENDS)
def test_cv_tol_coarse_matches_full_accuracy_sweep(backend, cv_refs):
    """The satellite contract: a loose-tol sweep + full-tol winner
    re-solve selects the same λ and lands on the same winner solutions
    as the all-points-full-accuracy sweep — for strictly less sweep
    work."""
    folds, val = _cv_data("lasso")
    ref = cv_refs["lasso"]
    got = client(backend).run(CVSpec(problems=folds, validation=val,
                                     tol_coarse=1e-3, **GRID))
    assert got.best_index == ref["best"]
    assert got.meta["tol_coarse"] == 1e-3
    np.testing.assert_allclose(
        got.x_best,
        np.stack([p.x[ref["best"]] for p in ref["paths"]]), atol=1e-5)
    coarse_work = sum(int(f.iters.sum()) for f in got.folds)
    full_work = sum(int(p.iters.sum()) for p in ref["paths"])
    assert coarse_work < full_work


# ------------------------------------------------------------------ #
# Session behaviour                                                  #
# ------------------------------------------------------------------ #
def test_stream_yields_in_completion_order():
    c = client("continuous")
    tickets = [c.submit(SoloSpec(problem=_instance("lasso", s)))
               for s in range(3)]
    assert c.pending == 3
    seen = dict(c.stream())
    assert sorted(seen) == sorted(tickets)
    assert c.pending == 0
    for t in tickets:
        assert seen[t].converged

def test_inline_completes_at_submit():
    c = client("inline")
    t = c.submit(SoloSpec(problem=_instance("lasso", 0)))
    assert c.pending == 0
    assert c.result(t, wait=False) is not None


def test_run_result_and_drain_agree():
    c = client("continuous")
    t1 = c.submit(SoloSpec(problem=_instance("lasso", 0)))
    t2 = c.submit(SoloSpec(problem=_instance("lasso", 1)))
    out = c.drain()
    assert set(out) == {t1, t2}
    assert out[t1] is c.result(t1)


def test_solo_history_contract_inline():
    got = client("inline").run(SoloSpec(problem=_instance("lasso", 0),
                                        method="fista"))
    assert got.raw.method == "fista"
    assert len(got.history["V"]) == got.iters


# ------------------------------------------------------------------ #
# Capability + validation errors                                     #
# ------------------------------------------------------------------ #
def test_unknown_backend_rejected():
    with pytest.raises(UnknownBackendError, match="unknown backend"):
        FlexaClient(backend="quantum")
    assert set(available_backends()) >= {"inline", "continuous", "mesh"}


@pytest.mark.parametrize("backend", ("continuous", "mesh"))
def test_non_flexa_methods_are_inline_only(backend):
    with pytest.raises(UnsupportedWorkloadError, match="inline"):
        client(backend).submit(SoloSpec(problem=_instance("lasso", 0),
                                        method="fista"))


@pytest.mark.parametrize("backend", ("continuous", "mesh"))
def test_record_history_is_inline_only(backend):
    with pytest.raises(UnsupportedWorkloadError, match="record_history"):
        client(backend).submit(BatchSpec(
            problems=[_instance("lasso", 0)], record_history=True))


@pytest.mark.parametrize("backend", ("continuous", "mesh"))
def test_nonquadratic_paths_are_inline_only(backend):
    with pytest.raises(UnsupportedWorkloadError, match="inline"):
        client(backend).submit(PathSpec(problem=_instance("logreg", 0),
                                        **GRID))
    # ...while the inline backend runs them (logreg screening landed
    # with this PR).
    r = client("inline").run(PathSpec(problem=_instance("logreg", 0),
                                      n_points=4, lam_min_ratio=0.2))
    assert r.x.shape[0] == 4


def test_spec_validation_errors():
    c = client("inline")
    with pytest.raises(SpecError, match="at least one problem"):
        c.submit(BatchSpec(problems=[]))
    with pytest.raises(SpecError, match="must be a Problem"):
        c.submit(SoloSpec(problem=np.zeros((3, 3))))
    with pytest.raises(SpecError, match="unknown workload spec"):
        c.submit(object())
    folds, val = _cv_data("lasso")
    with pytest.raises(SpecError, match="align"):
        c.submit(CVSpec(problems=folds, validation=val[:1]))
    with pytest.raises(SpecError, match="scoring route"):
        c.submit(CVSpec(problems=folds, tol_coarse=1e-3))
    with pytest.raises(SpecError, match="mutually exclusive"):
        c.submit(CVSpec(problems=folds, validation=val,
                        tol_coarse=1e-3, tol_schedule=[1e-7] * 20))
    with pytest.raises(KeyError, match="unknown ticket"):
        c.result(10_000)


def test_eager_submit_failure_leaks_no_ticket():
    """An inline execution error rejects atomically: no ticket is
    registered, so the session stays clean (KeyError, not a bogus
    'never completed' ClientError)."""
    c = client("inline")
    with pytest.raises(ValueError, match="align"):
        c.submit(PathSpec(problem=_instance("lasso", 0), n_points=5,
                          tol_schedule=[1e-3]))      # wrong length
    assert c.pending == 0
    with pytest.raises(KeyError, match="unknown ticket"):
        c.result(0)


# ------------------------------------------------------------------ #
# Config composition                                                 #
# ------------------------------------------------------------------ #
def test_client_config_composes_solver_and_serve():
    cfg = ClientConfig(solver=CFG,
                       serve=ServeConfig(slab_capacity=2),
                       backend="continuous")
    c = FlexaClient(cfg)
    assert c.config.serve.slab_capacity == 2
    assert c.backend == "continuous"
    # overrides win over the config object's fields
    c2 = FlexaClient(cfg, backend="inline")
    assert c2.backend == "inline" and c2.config.serve.slab_capacity == 2
