"""Public-API surface snapshot + deprecation contracts.

Three things are pinned here:

1. the exact public exports of ``repro.solvers`` / ``repro.serve`` /
   ``repro.path`` / ``repro.client`` / ``repro.obs`` / ``repro.remote``
   (an intentional API change must edit the snapshot — an accidental
   one fails loudly);
2. the legacy entry points (``solve``/``solve_batched``/``solve_path*``)
   are **gone**: their FutureWarning deprecation cycle completed and the
   shims were removed — ``FlexaClient`` is the front door, the
   ``_solve*`` internals stay importable for the engine layer and tests;
3. what remains of the warning contract: raw engine construction still
   warns once per process, and the client's own backends never trigger
   the warnings (they run under ``deprecation.internal_use``).
"""
import os
import warnings

import pytest

import repro.client
import repro.path
import repro.serve
import repro.solvers
from repro import deprecation
from repro.config.base import ServeConfig, SolverConfig
from repro.problems.lasso import nesterov_instance

# ------------------------------------------------------------------ #
# 1. Surface snapshot                                                #
# ------------------------------------------------------------------ #
SURFACE = {
    "repro.solvers": [
        "BatchedProblemSpec", "SlabState", "SolverResult",
        "available_methods", "cache_stats", "get_solver",
        "make_batched_solver", "make_chunk_stepper",
        "register", "slab_alloc",
    ],
    "repro.serve": [
        "AdmissionQueue", "ContinuousSolverEngine",
        "MeshServeEngine", "MeshTelemetry",
        "PathRequest", "PathState", "QueueEntry", "RequestTrace",
        "ServeTelemetry", "SolveRequest", "SolveResponse",
    ],
    "repro.path": [
        "DEFAULT_KKT_SLACK", "MAX_KKT_ROUNDS", "PathResult",
        "ScreenReport", "block_scores", "geometric_grid",
        "kkt_violations", "lambda_max", "strong_rule_active",
        "validate_grid",
    ],
    "repro.client": [
        "Backend", "BatchResult", "BatchSpec", "CVResult", "CVSpec",
        "ClientConfig", "ClientError", "ContinuousBackend",
        "FlexaClient", "InlineBackend", "MeshBackend", "PathResult",
        "PathSpec",
        "SoloResult", "SoloSpec", "SpecError", "TicketDiagnostics",
        "UnknownBackendError",
        "UnsupportedWorkloadError", "WorkItem",
        "available_backends", "make_backend", "normalize",
        "register_backend", "solve_request_of",
    ],
    "repro.obs": [
        "CostLedger", "HealthConfig", "LEDGER_KEYS", "MetricWindows",
        "SlidingWindow", "SolveFailure", "Span", "Tracer",
        "allclose_or_both_nonfinite", "assert_finite_close",
        "bitwise_equal", "get_tracer", "instant", "render_requests",
        "render_snapshot", "set_tracer", "span", "sparkline", "tracing",
    ],
    "repro.remote": [
        "ProtocolError", "QuotaExceeded", "QuotaPolicy", "SCHEMA",
        "SLOClass", "SLO_CLASSES", "TenantQuota", "TokenBucket",
        "decode_array", "decode_result", "decode_spec", "encode_array",
        "encode_item", "encode_result", "resolve_slo",
    ],
}


@pytest.mark.parametrize("module", sorted(SURFACE))
def test_public_surface_snapshot(module):
    import importlib
    mod = importlib.import_module(module)
    assert sorted(mod.__all__) == SURFACE[module], (
        f"{module}.__all__ drifted — if the API change is intentional, "
        "update the snapshot in tests/test_api_surface.py")
    for name in mod.__all__:
        assert hasattr(mod, name), f"{module}.{name} exported but absent"


def _fresh_interpreter(code: str) -> str:
    """Run ``code`` in a new Python process on the CPU; its stdout."""
    import subprocess
    import sys
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        repro.client.__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH")) if p)}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=300, env=env)
    return out.stdout.strip() + out.stderr


@pytest.mark.parametrize("module", ("repro.client", "repro.serve",
                                    "repro.solvers"))
def test_solver_packages_never_import_the_lm_stack(module):
    """The solver service's packages load no LM model code (the LM
    generator lives in ``repro.models.generate``).  Checked in a fresh
    interpreter: this process may hold the models from another test."""
    out = _fresh_interpreter(
        f"import sys, {module}; "
        "print(sorted(m for m in sys.modules "
        "if m.startswith('repro.models')))")
    assert out.splitlines()[0] == "[]", out


def test_remote_package_stays_lazy():
    """``import repro.remote`` exposes only policy + protocol; the
    server and the registered backend are imported on demand (the
    client registry pulls ``repro.remote.backend`` the first time
    ``backend="remote"`` is requested).  Checked in a fresh interpreter:
    this process may already hold both from another test file."""
    out = _fresh_interpreter(
        "import sys, repro.remote; "
        "print(sorted(m for m in ('repro.remote.server', "
        "'repro.remote.backend') if m in sys.modules))")
    assert out.splitlines()[0] == "[]", out


# ------------------------------------------------------------------ #
# 2. The legacy shims completed their deprecation cycle              #
# ------------------------------------------------------------------ #
REMOVED = [
    ("repro.solvers", "solve"),
    ("repro.solvers", "solve_batched"),
    ("repro.path", "solve_path"),
    ("repro.path", "solve_path_batched"),
]


@pytest.mark.parametrize("module,name", REMOVED,
                         ids=[f"{m}.{n}" for m, n in REMOVED])
def test_legacy_entry_points_removed(module, name):
    """PR 5 wrapped these in one-shot FutureWarnings pointing at
    FlexaClient; this PR removes them.  Anything still calling one
    should fail with AttributeError, not silently bypass the client."""
    import importlib
    mod = importlib.import_module(module)
    assert not hasattr(mod, name)
    assert name not in mod.__all__


def test_internal_entry_points_still_importable():
    """The underscore internals the shims delegated to remain — the
    engine layer and the test suite build on them."""
    from repro.path.driver import _solve_path, _solve_path_batched
    from repro.solvers.api import _solve
    from repro.solvers.batched import _solve_batched
    assert all(callable(f) for f in
               (_solve, _solve_batched, _solve_path, _solve_path_batched))


# ------------------------------------------------------------------ #
# 3. The remaining warning contract                                  #
# ------------------------------------------------------------------ #
def _future_warnings(w):
    return [x for x in w if issubclass(x.category, FutureWarning)]


@pytest.fixture
def mini():
    return nesterov_instance(m=16, n=32, nnz_frac=0.2, c=1.0, seed=0)


def test_engine_construction_warns_once(mini):
    deprecation.reset_warnings()
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            repro.serve.MeshServeEngine(SolverConfig(max_iters=5))
            repro.serve.MeshServeEngine(SolverConfig(max_iters=5))
            repro.serve.ContinuousSolverEngine(
                SolverConfig(max_iters=5), ServeConfig(slab_capacity=2))
        fw = _future_warnings(w)
        assert len(fw) == 2                 # one per engine class
    finally:
        deprecation.reset_warnings()


def test_client_backends_never_trigger_legacy_warnings(mini):
    """The front door must not warn about the machinery it fronts."""
    from repro.client import FlexaClient, SoloSpec

    deprecation.reset_warnings()
    try:
        cfg = SolverConfig(tol=1e-6, max_iters=500, tau_adapt=False)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for backend in ("inline", "continuous", "mesh"):
                FlexaClient(backend=backend, solver=cfg).run(
                    SoloSpec(problem=mini))
        assert _future_warnings(w) == []
    finally:
        deprecation.reset_warnings()
