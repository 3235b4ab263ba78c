"""``repro.client`` — one front door to the whole solver stack.

The paper's framework spans "virtually all" update schedules; the repo's
execution engines span in-process and continuous-batched scheduling,
on one device or a device mesh.  This package is the single API over all of it:

    from repro.client import FlexaClient, SoloSpec, PathSpec

    client = FlexaClient(backend="continuous")
    result = client.run(SoloSpec(problem))        # == inline

* :class:`FlexaClient` — the session (``submit`` / ``run`` / ``step`` /
  ``stream`` / ``drain``), configured by one :class:`~repro.config.base.
  ClientConfig` composing :class:`SolverConfig` + :class:`ServeConfig` +
  the backend name;
* typed specs — :class:`SoloSpec`, :class:`BatchSpec`,
  :class:`PathSpec`, :class:`CVSpec` — normalizing onto one internal
  :class:`WorkItem`;
* the :class:`Backend` protocol + registry (``inline`` /
  ``continuous`` / ``mesh`` / ``remote``; :func:`register_backend` to
  extend — ``remote`` runs against a ``repro.remote.server`` process,
  see ``docs/remote.md``);
* result contracts: :class:`SoloResult`, :class:`BatchResult`, the
  shared :class:`~repro.path.driver.PathResult`, :class:`CVResult`;
* the error taxonomy (:mod:`repro.client.errors`).

The legacy entry points (``repro.solvers.solve`` / ``solve_batched``,
``repro.path.solve_path`` / ``solve_path_batched``) completed their
deprecation cycle and are **removed**; direct engine construction still
warns once per process — see ``docs/client.md`` for the migration table.
"""
from repro.client.backends import (Backend, ContinuousBackend,
                                   InlineBackend, MeshBackend,
                                   available_backends, make_backend,
                                   register_backend)
from repro.client.errors import (ClientError, SpecError,
                                 UnknownBackendError,
                                 UnsupportedWorkloadError)
from repro.client.session import FlexaClient
from repro.client.specs import (BatchResult, BatchSpec, CVResult, CVSpec,
                                PathSpec, SoloResult, SoloSpec,
                                TicketDiagnostics, WorkItem, normalize,
                                solve_request_of)
from repro.config.base import ClientConfig
from repro.path.driver import PathResult

__all__ = [
    "FlexaClient", "ClientConfig",
    "SoloSpec", "BatchSpec", "PathSpec", "CVSpec",
    "SoloResult", "BatchResult", "PathResult", "CVResult",
    "TicketDiagnostics", "WorkItem", "normalize", "solve_request_of",
    "Backend", "InlineBackend", "ContinuousBackend",
    "MeshBackend",
    "available_backends", "register_backend", "make_backend",
    "ClientError", "SpecError", "UnknownBackendError",
    "UnsupportedWorkloadError",
]
