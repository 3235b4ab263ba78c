"""Serving layer: the solver's serving engines.

The engines here are the *backends* behind the client front door
(``repro.client.FlexaClient`` with ``backend="continuous"``/``"mesh"``);
constructing them directly still works but emits a one-shot
``FutureWarning`` (see ``docs/client.md``).

* :class:`ContinuousSolverEngine` — continuous batching: slot slabs,
  chunked compiled steps, eviction/backfill from a policy-ordered
  admission queue (``repro.serve.continuous``).
* :class:`MeshServeEngine` — the continuous runtime sharded over a 1-D
  device mesh: one slab shard + admission queue per device, routed from
  the shared queue with work stealing at the drain tail
  (``repro.serve.mesh``); telemetry rolls up per device via
  :class:`MeshTelemetry`.
* :class:`SolveRequest` / :class:`SolveResponse` — one request and its
  verdict (``repro.serve.engine``).
* :class:`PathRequest` / :class:`PathState` — the point-by-point path
  protocol (``repro.serve.pathstate``) the engines drive a
  regularization path through.
* :class:`ServeTelemetry` — shared latency/occupancy/cache telemetry
  (``repro.serve.metrics``).

The LM generator lives in ``repro.models.generate``.
"""
from repro.serve.continuous import (AdmissionQueue, ContinuousSolverEngine,
                                    QueueEntry)
from repro.serve.engine import SolveRequest, SolveResponse
from repro.serve.mesh import MeshServeEngine
from repro.serve.metrics import MeshTelemetry, RequestTrace, ServeTelemetry
from repro.serve.pathstate import PathRequest, PathState

__all__ = [
    "SolveRequest", "SolveResponse",
    "ContinuousSolverEngine", "AdmissionQueue", "QueueEntry",
    "MeshServeEngine", "MeshTelemetry",
    "PathRequest", "PathState",
    "RequestTrace", "ServeTelemetry",
]
