"""Numerical-health watchdog + windowed SLOs.

Pins the PR's contracts:

* **quarantine** — injected-NaN requests evict as ``"diverged"`` on
  their first chunk and injected stalls as ``"stalled"`` within
  ``stall_patience + 1`` chunks, through the exactly-once eviction
  path (typed ``SolveFailure`` outcomes, audit records closed once);
* **determinism** — watchdog off builds the legacy program (bitwise by
  construction); watchdog on leaves a healthy workload bit-identical;
* **windows** — sliding-window SLO aggregation prunes by horizon under
  an injected clock, empty windows report ``None`` percentiles, and
  health-event counters survive drain-tail slab migration.
"""
import json
import warnings

import numpy as np
import pytest

from repro.obs.health import (
    HealthConfig,
    SolveFailure,
    allclose_or_both_nonfinite,
    assert_finite_close,
    bitwise_equal,
)
from repro.obs.windows import MetricWindows, SlidingWindow


class FakeClock:
    """Deterministic injectable clock: 0.0, 0.5, 1.0, ..."""

    def __init__(self, step: float = 0.5):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        t, self.t = self.t, self.t + self.step
        return t


@pytest.fixture(autouse=True)
def _silence_legacy_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        yield


def _lasso(seed: int):
    from repro.problems.lasso import nesterov_instance
    return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0, seed=seed)


def _engine(cfg=None, serve=None, **serve_kw):
    from repro.config.base import ServeConfig, SolverConfig
    from repro.serve.continuous import ContinuousSolverEngine
    cfg = cfg or SolverConfig(max_iters=400, tol=1e-5, tau_adapt=False)
    serve = serve or ServeConfig(slab_capacity=4, chunk_iters=25,
                                 watchdog=True, stall_patience=3,
                                 **serve_kw)
    return ContinuousSolverEngine(cfg, serve)


# ------------------------------------------------------------------ #
# NaN-aware comparison utilities (satellite b)                       #
# ------------------------------------------------------------------ #
def test_bitwise_equal():
    a = np.array([1.0, np.nan, np.inf], np.float32)
    assert bitwise_equal(a, a.copy())
    assert not bitwise_equal(a, a.astype(np.float64))       # dtype
    assert not bitwise_equal(a, a[:2])                      # shape
    b = a.copy()
    b[0] = 2.0
    assert not bitwise_equal(a, b)


def test_allclose_or_both_nonfinite():
    nan, inf = np.nan, np.inf
    f = np.float32
    ok = allclose_or_both_nonfinite
    assert ok(np.array([1.0, nan], f), np.array([1.0, nan], f))
    assert ok(np.array([inf, 2.0], f), np.array([inf, 2.0 + 1e-7], f))
    assert not ok(np.array([1.0, nan], f), np.array([nan, 1.0], f))
    assert not ok(np.array([inf], f), np.array([-inf], f))  # sign
    assert not ok(np.array([inf], f), np.array([nan], f))   # kind
    assert not ok(np.array([1.0], f), np.array([1.1], f))   # value
    assert not ok(np.array([1.0], f), np.array([1.0, 2.0], f))


def test_assert_finite_close_raises_with_context():
    a = np.array([1.0, np.nan], np.float32)
    b = np.array([1.0, 2.0], np.float32)
    assert_finite_close(a, a.copy(), context="self")        # no raise
    with pytest.raises(AssertionError, match="replay"):
        assert_finite_close(a, b, context="replay")


# ------------------------------------------------------------------ #
# HealthConfig wiring                                                #
# ------------------------------------------------------------------ #
def test_health_config_of_serve():
    from repro.config.base import ServeConfig
    assert HealthConfig.of(ServeConfig()) is None           # off default
    hc = HealthConfig.of(ServeConfig(watchdog=True, stall_patience=7))
    assert hc == HealthConfig(stall_window=7)
    assert hash(hc) == hash(HealthConfig(stall_window=7))   # cache key


# ------------------------------------------------------------------ #
# Quarantine: NaN and stall injections (tentpole)                    #
# ------------------------------------------------------------------ #
def test_nan_injection_quarantined_first_chunk():
    from repro.client.specs import solve_request_of
    eng = _engine()
    p = _lasso(0)
    n = p.data["A"].shape[1]
    bad = eng.submit(solve_request_of(
        p, x0=np.full(n, np.nan, np.float32)))
    good = eng.submit(solve_request_of(_lasso(1)))
    resps = eng.drain()

    assert resps[bad].status == "diverged"
    assert not resps[bad].converged
    assert resps[good].status == "ok" and resps[good].converged
    rec = next(r for r in eng.audit if r["req_id"] == bad)
    assert rec["status"] == "diverged"
    assert rec["evict_tick"] - rec["admit_tick"] <= 1
    assert [f.req_id for f in eng.failures] == [bad]
    assert isinstance(eng.failures[0], SolveFailure)
    snap = eng.telemetry.snapshot()
    assert snap["health"] == {"quarantined": 1, "diverged": 1,
                              "stalled": 0, "timeouts": 0}


def test_stall_injection_quarantined_within_patience():
    from repro.client.specs import solve_request_of
    from repro.config.base import SolverConfig
    # gamma0=0 with tau_adapt off freezes the iterate: the ‖x̂−x‖∞
    # stat never decreases, the canonical stall.
    cfg = SolverConfig(max_iters=400, tol=1e-12, gamma0=0.0,
                       tau_adapt=False)
    eng = _engine(cfg=cfg)
    ids = [eng.submit(solve_request_of(_lasso(s))) for s in range(3)]
    resps = eng.drain()
    for i in ids:
        assert resps[i].status == "stalled"
        rec = next(r for r in eng.audit if r["req_id"] == i)
        assert rec["evict_tick"] - rec["admit_tick"] <= 3 + 1
    assert sorted(f.req_id for f in eng.failures) == ids
    assert eng.telemetry.snapshot()["health"]["stalled"] == 3


def test_watchdog_off_never_quarantines():
    from repro.client.specs import solve_request_of
    from repro.config.base import ServeConfig, SolverConfig
    cfg = SolverConfig(max_iters=100, tol=1e-12, gamma0=0.0,
                       tau_adapt=False)
    from repro.serve.continuous import ContinuousSolverEngine
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=4, chunk_iters=25))
    i = eng.submit(solve_request_of(_lasso(0)))
    resps = eng.drain()
    assert resps[i].status == "ok"          # ran to max_iters, no verdict
    assert eng.failures == []
    assert "health" not in eng.telemetry.snapshot()


def test_healthy_workload_bitwise_identical_watchdog_on_off():
    from repro.client.specs import solve_request_of
    from repro.config.base import ServeConfig, SolverConfig
    from repro.serve.continuous import ContinuousSolverEngine
    cfg = SolverConfig(max_iters=400, tol=1e-5, tau_adapt=False)

    def run(**kw):
        eng = ContinuousSolverEngine(
            cfg, ServeConfig(slab_capacity=4, chunk_iters=25, **kw))
        ids = [eng.submit(solve_request_of(_lasso(s)))
               for s in range(6)]
        resps = eng.drain()
        return [resps[i] for i in ids], eng.failures

    off, _ = run()
    on, failures = run(watchdog=True, stall_patience=10)
    assert failures == []
    for a, b in zip(off, on):
        assert bitwise_equal(np.asarray(a.x), np.asarray(b.x))
        assert a.iters == b.iters and a.stat == b.stat
        assert b.status == "ok"


def test_quarantine_statuses_reach_client_and_diagnostics():
    from repro.client import FlexaClient
    from repro.client.specs import BatchSpec, SoloSpec
    from repro.config.base import ClientConfig, ServeConfig, SolverConfig
    cfg = ClientConfig(
        solver=SolverConfig(max_iters=400, tol=1e-5, tau_adapt=False),
        serve=ServeConfig(slab_capacity=4, chunk_iters=25,
                          watchdog=True, stall_patience=3),
        backend="continuous")
    p = _lasso(0)
    n = p.data["A"].shape[1]
    with FlexaClient(cfg) as c:
        t_bad = c.submit(SoloSpec(problem=p,
                                  x0=np.full(n, np.nan, np.float32)))
        t_ok = c.submit(BatchSpec(problems=[_lasso(1), _lasso(2)]))
        r_bad, r_ok = c.result(t_bad), c.result(t_ok)
        assert r_bad.status == "diverged"
        assert r_ok.status == ["ok", "ok"]
        d = c.diagnostics(t_bad)
        assert [r["status"] for r in d.requests] == ["diverged"]
        tele = c.stats()["telemetry"]
        assert tele["health"]["diverged"] == 1


def test_health_carry_survives_drain_tail_migration():
    """compact_drain resizes the slab mid-flight; the device-resident
    stall counters must migrate with their slots — a reset-on-migration
    bug would delay the late request's quarantine past the patience
    bound, and a scrambled gather would misattribute verdicts."""
    from repro.client.specs import solve_request_of
    from repro.config.base import ServeConfig, SolverConfig
    from repro.serve.continuous import ContinuousSolverEngine
    cfg = SolverConfig(max_iters=2000, tol=1e-12, gamma0=0.0,
                       tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=4, chunk_iters=25,
                         compact_drain=True, watchdog=True,
                         stall_patience=3))
    # Four stalls admitted together, one submitted later: the first
    # wave's quarantine drops occupancy to 1, compact_drain migrates to
    # a smaller bucket while the late slot is still counting stalls.
    ids = [eng.submit(solve_request_of(_lasso(s))) for s in range(4)]
    for _ in range(2):
        eng.step()
    late = eng.submit(solve_request_of(_lasso(9)))
    resps = eng.drain()

    assert eng.telemetry.migrations > 0     # the scenario migrated
    for i in ids + [late]:
        assert resps[i].status == "stalled"
    # gamma0=0 stalls evict at exactly admit + patience chunks; the
    # late request's counter crossed the migration — any reset would
    # push its eviction past the bound.
    rec = next(r for r in eng.audit if r["req_id"] == late)
    assert rec["evict_tick"] - rec["admit_tick"] == 3
    assert len(eng.failures) == len(ids) + 1
    snap = eng.telemetry.snapshot()
    assert snap["health"]["stalled"] == len(ids) + 1
    assert snap["health"]["quarantined"] == len(eng.failures)


def test_mesh_engine_routes_quarantines_to_device_children():
    """The mesh engine's quarantine hook credits the owning device's
    child telemetry; the rollup conserves the global counters at any
    device count (runs at whatever mesh is visible, 1 included)."""
    from repro.client.specs import solve_request_of
    from repro.config.base import ServeConfig, SolverConfig
    from repro.serve.mesh import MeshServeEngine
    p = _lasso(0)
    n = p.data["A"].shape[1]
    eng = MeshServeEngine(
        SolverConfig(max_iters=400, tol=1e-5, tau_adapt=False),
        ServeConfig(slab_capacity=2, chunk_iters=25, watchdog=True,
                    stall_patience=3))
    bad = eng.submit(solve_request_of(
        p, x0=np.full(n, np.nan, np.float32)))
    good = eng.submit(solve_request_of(_lasso(1)))
    resps = eng.drain()
    assert resps[bad].status == "diverged"
    assert resps[good].status == "ok"
    snap = eng.telemetry.snapshot()
    assert snap["health"] == {"quarantined": 1, "diverged": 1,
                              "stalled": 0, "timeouts": 0}
    per_dev = sum(t.quarantined_diverged
                  for t in eng.telemetry.per_device)
    assert per_dev == 1                     # credited to a device child


def test_mesh_rollup_sums_quarantines():
    from repro.serve.metrics import MeshTelemetry
    tele = MeshTelemetry(n_devices=2)
    tele.device(0).record_quarantine("diverged")
    tele.device(1).record_quarantine("stalled")
    tele.device(1).record_quarantine("stalled")
    tele.rollup()
    assert tele.quarantined_diverged == 1
    assert tele.quarantined_stalled == 2
    snap = tele.snapshot()
    assert snap["health"] == {"quarantined": 3, "diverged": 1,
                              "stalled": 2, "timeouts": 0}


# ------------------------------------------------------------------ #
# Sliding windows (tentpole piece 2 + satellite c)                   #
# ------------------------------------------------------------------ #
def test_sliding_window_empty_reports_none():
    w = SlidingWindow(horizon=10.0)
    s = w.stats(now=100.0)
    assert s["count"] == 0 and s["rate"] == 0.0
    assert s["mean"] is None and s["p50"] is None
    assert s["p99"] is None and s["max"] is None


def test_sliding_window_rejects_bad_horizon():
    with pytest.raises(ValueError):
        SlidingWindow(horizon=0.0)


def test_sliding_window_rollover_under_fake_clock():
    clock = FakeClock(step=1.0)             # 0, 1, 2, ...
    w = SlidingWindow(horizon=3.0)
    for v in range(6):                      # t=0..5, value == t
        w.add(clock(), float(v))
    now = 5.0
    # horizon 3 at now=5 keeps t in (2, 5]: values 3, 4, 5
    assert w.values(now) == [3.0, 4.0, 5.0]
    s = w.stats(now)
    assert s["count"] == 3 and s["rate"] == pytest.approx(1.0)
    assert s["p50"] == 4.0 and s["max"] == 5.0
    # Advancing far past the horizon empties the window entirely.
    assert w.stats(now=100.0)["count"] == 0


def test_metric_windows_snapshot():
    mw = MetricWindows(horizon=10.0)
    mw.add("latency", 1.0, 0.5)
    mw.add("latency", 2.0, 1.5)
    mw.add("completions", 2.0, 1.0)
    snap = mw.snapshot(now=5.0)
    assert snap["window_s"] == 10.0
    assert snap["latency"]["count"] == 2
    assert snap["latency"]["p50"] == 1.0
    assert snap["completions"]["rate"] == pytest.approx(0.1)


def test_telemetry_windows_opt_in_and_feed():
    from repro.serve.metrics import ServeTelemetry
    tele = ServeTelemetry(clock=FakeClock(step=1.0))
    assert tele.windows() is None           # off by default
    assert "windows" not in tele.snapshot()

    tele = ServeTelemetry(clock=FakeClock(step=1.0), window_s=60.0)
    rid = tele.next_request_id()
    tele.record_arrival(rid, "lasso", "continuous")
    tele.record_admit(rid)
    tele.record_completion(rid, iters=100, converged=True)
    tele.record_quarantine("diverged")
    snap = tele.snapshot()
    win = snap["windows"]
    assert win["window_s"] == 60.0
    assert win["completions"]["count"] == 1
    assert win["latency"]["count"] == 1
    assert win["health_events"]["count"] == 1
    assert snap["health"]["diverged"] == 1


def test_unknown_quarantine_status_rejected():
    from repro.serve.metrics import ServeTelemetry
    with pytest.raises(ValueError):
        ServeTelemetry().record_quarantine("melted")


# ------------------------------------------------------------------ #
# Dashboard panels (satellite c golden render)                       #
# ------------------------------------------------------------------ #
GOLDEN_SNAP = {
    "requests": 4, "completed": 4, "in_flight": 0, "converged": 3,
    "iters_total": 1234,
    "latency_p50": 1.5, "latency_p99": 3.0, "latency_mean": 1.75,
    "queue_wait_p50": 0.0, "queue_wait_p99": 0.5,
    "health": {"quarantined": 1, "diverged": 1, "stalled": 0},
    "windows": {
        "window_s": 60.0,
        "completions": {"count": 4, "rate": 0.0667, "mean": 1.0,
                        "p50": 1.0, "p99": 1.0, "max": 1.0},
        "latency": {"count": 4, "rate": 0.0667, "mean": 1.75,
                    "p50": 1.5, "p99": 2.97, "max": 3.0},
    },
}

GOLDEN_LINES = [
    "health    quarantined 1   diverged 1   stalled 0   timeouts 0",
    "windows   horizon 60s  (rate = events/s over window)",
    "  completions   n     4  rate 0.0667  p50 1  p99 1  max 1",
    "  latency       n     4  rate 0.0667  p50 1.5  p99 2.97  max 3",
]


def test_dashboard_health_and_window_panels_golden():
    from repro.obs.dashboard import render_snapshot
    out = render_snapshot(GOLDEN_SNAP, title="golden")
    for line in GOLDEN_LINES:
        assert line in out.splitlines(), out


def test_dashboard_snapshot_cli_golden(tmp_path, capsys):
    from repro.obs.dashboard import main
    f = tmp_path / "snap.json"
    f.write_text(json.dumps({"telemetry": GOLDEN_SNAP}))
    assert main(["--snapshot", str(f)]) == 0
    out = capsys.readouterr().out
    for line in GOLDEN_LINES:
        assert line in out.splitlines(), out


def test_dashboard_sections_absent_without_sources():
    from repro.obs.dashboard import render_snapshot
    out = render_snapshot({"requests": 1, "completed": 1})
    assert "health" not in out and "windows" not in out
