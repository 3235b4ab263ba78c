"""Solver substrate for the composite problem  min F(x) + G(x).

The user-facing front door is ``repro.client``
(:class:`~repro.client.FlexaClient` + typed specs — see
``docs/client.md``); this package holds the machinery the client's
backends execute.  The PR 5 legacy shims (``solve`` / ``solve_batched``)
completed their FutureWarning deprecation cycle and are gone — the
registry dispatch lives on as ``repro.solvers.api._solve`` and the
batched driver as ``repro.solvers.batched._solve_batched``, both
internal to the inline backend.

* the batched multi-instance FLEXA engine: B independent instances
  advance in lock-step inside one compiled (vmap + while_loop) program
  (:func:`make_batched_solver`, ``batched.py``).
* the resumable slab core (:func:`slab_alloc` / :func:`make_chunk_stepper`)
  — what the serving engines (``repro.serve.continuous`` and its
  mesh-sharded form) schedule over; slabs carry a per-slot
  stopping-tolerance vector so one engine can mix tenant tolerances.
* :func:`register` / :func:`available_methods` — extend or inspect the
  method registry; :func:`cache_stats` — compile-cache counters.
"""
from repro.solvers.batched import (BatchedProblemSpec, SlabState,
                                   make_batched_solver, make_chunk_stepper,
                                   slab_alloc)
from repro.solvers.cache import cache_stats
from repro.solvers.registry import available_methods, get_solver, register
from repro.solvers.result import SolverResult

__all__ = [
    "make_batched_solver", "BatchedProblemSpec",
    "SlabState", "slab_alloc", "make_chunk_stepper",
    "SolverResult", "register", "get_solver", "available_methods",
    "cache_stats",
]
