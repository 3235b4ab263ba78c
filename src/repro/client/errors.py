"""Typed error taxonomy of the client front door.

Everything the client raises on purpose derives from :class:`ClientError`,
so callers can catch one base class at the session boundary.  The
distinctions that matter operationally:

* :class:`SpecError` — the workload description itself is malformed
  (wrong shapes, unknown method, empty batch).  Raised at ``submit``
  time, before any device work, so rejection is atomic.
* :class:`UnsupportedWorkloadError` — the spec is well-formed but the
  *selected backend* cannot execute it (e.g. a FISTA solo on a serving
  engine, a logistic-regression path over the continuous scheduler).  The
  message names a backend that can.
* :class:`UnknownBackendError` — ``ClientConfig.backend`` names nothing
  in the registry.
"""
from __future__ import annotations


class ClientError(Exception):
    """Base class of every deliberate ``repro.client`` failure."""


class SpecError(ClientError, ValueError):
    """A workload spec is malformed (caught before any execution)."""


class UnsupportedWorkloadError(ClientError):
    """The chosen backend cannot run this (valid) workload."""


class UnknownBackendError(ClientError, KeyError):
    """``backend=`` names no registered execution backend."""
