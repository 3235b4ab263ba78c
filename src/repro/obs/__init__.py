"""Unified observability layer: span tracing, cost ledger, live ops view,
numerical-health watchdog, and windowed SLOs.

``repro.obs`` spans the whole stack — client submit/run/step, backend
dispatch, continuous/mesh serve engines, path-driver KKT rounds and
compaction repacks, and compile-cache hits/misses — with five pieces:

* :mod:`repro.obs.trace` — deterministic injectable-clock span recorder
  exporting JSONL and Chrome trace-event JSON (Perfetto-loadable).
  Disabled (the default) it is bitwise-invisible: all instrumentation
  sites short-circuit on one global read.
* :mod:`repro.obs.ledger` — the stack-wide :class:`CostLedger`
  (row-iters / live-iters / device FLOPs / padding / freeze / compiles)
  every engine and every client result now reports with identical keys.
* :mod:`repro.obs.dashboard` — ``python -m repro.obs.dashboard``:
  terminal ops view rendering queue depth, slab occupancy, latency
  percentiles, SLO windows, health counters, per-device mesh rollups,
  and per-request convergence sparklines from sampled trajectories.
* :mod:`repro.obs.health` — the numerical-health watchdog contract
  (:class:`HealthConfig`, quarantine status codes, typed
  :class:`SolveFailure`) plus NaN-safe comparison helpers
  (:func:`allclose_or_both_nonfinite`, :func:`assert_finite_close`,
  :func:`bitwise_equal`) for benches/tests that compare outputs which
  may legitimately contain diverged solves.
* :mod:`repro.obs.windows` — ring-buffer sliding windows over the
  injectable clock (:class:`MetricWindows`): per-window p50/p99/rate
  for latency, occupancy, throughput and health events, opt-in via
  ``ServeTelemetry(window_s=...)``.

See ``docs/observability.md`` for the span model, ledger key semantics,
and the determinism contract (gated by ``benchmarks/obs_bench.py``).
"""
from repro.obs.dashboard import render_requests, render_snapshot, sparkline
from repro.obs.health import (HealthConfig, SolveFailure,
                              allclose_or_both_nonfinite,
                              assert_finite_close, bitwise_equal)
from repro.obs.ledger import LEDGER_KEYS, CostLedger
from repro.obs.trace import (Span, Tracer, get_tracer, instant, set_tracer,
                             span, tracing)
from repro.obs.windows import MetricWindows, SlidingWindow

__all__ = [
    "CostLedger",
    "HealthConfig",
    "LEDGER_KEYS",
    "MetricWindows",
    "SlidingWindow",
    "SolveFailure",
    "Span",
    "Tracer",
    "allclose_or_both_nonfinite",
    "assert_finite_close",
    "bitwise_equal",
    "get_tracer",
    "instant",
    "render_requests",
    "render_snapshot",
    "set_tracer",
    "span",
    "sparkline",
    "tracing",
]
