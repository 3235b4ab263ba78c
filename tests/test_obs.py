"""``repro.obs`` — unified tracing + cost ledger + live ops view.

Pins the PR's three contracts:

* **determinism** — tracing OFF is bitwise-identical to an
  uninstrumented run (host-side spans never touch device programs);
  tracing ON under an injected clock is byte-identical run to run
  (JSONL export compared verbatim);
* **conservation** — every ledger producer satisfies
  ``row_iters == live_iters + padding_iters + freeze_iters`` and prices
  flops in the one shared matvec currency;
* **schema stability** — span/instant records, ledger dicts and the
  telemetry snapshot keep their key sets (dashboards and
  ``BENCH_obs.json`` parse them blind).
"""
import json
import warnings

import numpy as np
import pytest

from repro.obs import CostLedger, LEDGER_KEYS, Tracer, get_tracer, tracing
from repro.obs import trace as obs
from repro.obs.dashboard import render_requests, render_snapshot, sparkline
from repro.obs.trace import INSTANT_KEYS, SPAN_KEYS
from repro.serve.metrics import ServeTelemetry, percentile


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


# ------------------------------------------------------------------ #
# Tracer                                                             #
# ------------------------------------------------------------------ #
def test_tracer_records_nesting_and_ids():
    t = Tracer(clock=FakeClock())
    with t.span("outer", cat="a", k=1):
        t.instant("mark", cat="a", v=2)
        with t.span("inner", cat="b"):
            pass
    ev = t.events()
    assert [e["name"] for e in ev] == ["outer", "mark", "inner"]
    assert [e["id"] for e in ev] == [0, 1, 2]
    outer, mark, inner = ev
    assert outer["parent"] is None
    assert mark["parent"] == 0 and inner["parent"] == 0
    assert outer["ph"] == "X" and mark["ph"] == "i"
    # FakeClock ticks: outer opens at 0.0, mark at 0.5, inner 1.0–1.5,
    # outer closes at 2.0
    assert (outer["t0"], inner["t0"], inner["t1"], outer["t1"]) == \
        (0.0, 1.0, 1.5, 2.0)
    assert outer["args"] == {"k": 1} and mark["args"] == {"v": 2}


def test_trace_schema_stability():
    t = Tracer(clock=FakeClock())
    with t.span("s"):
        t.instant("i")
    span_rec, inst_rec = t.events()
    assert tuple(span_rec) == SPAN_KEYS
    assert tuple(inst_rec) == INSTANT_KEYS


def test_tracer_exports_round_trip(tmp_path):
    t = Tracer(clock=FakeClock())
    with t.span("work", cat="x", n=3):
        t.instant("tick", cat="x")
    jsonl = t.to_jsonl(tmp_path / "trace.jsonl")
    assert (tmp_path / "trace.jsonl").read_text() == jsonl
    parsed = [json.loads(line) for line in jsonl.splitlines()]
    assert parsed == t.events()

    doc = t.to_chrome(tmp_path / "trace.json")
    loaded = json.loads((tmp_path / "trace.json").read_text())
    assert loaded["traceEvents"] == json.loads(json.dumps(
        doc["traceEvents"]))
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases == {"X", "i"}
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    # µs timestamps, complete-event duration, pid/tid present: the
    # fields Perfetto's trace-event importer requires
    assert x["ts"] == 0.0 and x["dur"] == pytest.approx(1.0e6)
    assert {"pid", "tid", "name", "cat"} <= set(x)


def test_tracer_clear_resets_ids():
    t = Tracer(clock=FakeClock())
    with t.span("a"):
        pass
    t.clear()
    with t.span("b"):
        pass
    assert t.events()[0]["id"] == 0


def test_module_helpers_default_to_noop():
    assert get_tracer() is None
    # no tracer: span() hands back a shared null context, instant()
    # returns without recording — the single-global-read fast path
    cm = obs.span("anything", cat="x", k=1)
    assert cm is obs._NULL_CM
    with cm:
        obs.instant("nothing")
    assert get_tracer() is None


def test_tracing_scope_restores_previous():
    t1, t2 = Tracer(clock=FakeClock()), Tracer(clock=FakeClock())
    with tracing(t1):
        assert get_tracer() is t1
        with tracing(t2):
            assert get_tracer() is t2
            obs.instant("inner")
        assert get_tracer() is t1
        obs.instant("outer")
    assert get_tracer() is None
    assert [e["name"] for e in t1.events()] == ["outer"]
    assert [e["name"] for e in t2.events()] == ["inner"]


# ------------------------------------------------------------------ #
# CostLedger                                                         #
# ------------------------------------------------------------------ #
def test_ledger_math_and_conservation():
    led = CostLedger()
    led.add(row_iters=100, live_iters=60, padding_iters=30,
            freeze_iters=10, device_flops=1000, compiles=2)
    assert led.conserved()
    assert led.waste_iters == 40
    assert led.utilization == pytest.approx(0.6)

    other = CostLedger(row_iters=10, live_iters=10)
    total = led + other
    assert total.row_iters == 110 and total.live_iters == 70
    assert led.row_iters == 100                 # __add__ is pure
    led.merge(other)                            # merge is in place
    assert led.row_iters == 110

    cp = led.copy()
    cp.add(row_iters=1)
    assert cp.row_iters == led.row_iters + 1


def test_ledger_rejects_unknown_keys_and_round_trips():
    led = CostLedger()
    with pytest.raises(KeyError, match="unknown ledger key"):
        led.add(flops=3)
    led.add(row_iters=5, live_iters=5)
    d = led.as_dict()
    assert tuple(k for k in d if k != "utilization") == LEDGER_KEYS
    assert CostLedger.from_dict(d).as_dict() == d
    # empty ledger: utilization degenerates to 1.0, still conserved
    assert CostLedger().utilization == 1.0 and CostLedger().conserved()


def test_telemetry_ledger_from_chunks_and_waves():
    tele = ServeTelemetry(clock=FakeClock())
    tele.record_chunk(live=3, capacity=4, chunk_iters=10, wall_s=0.1,
                      flops=10 * 4 * 24 * 64)
    for iters in (10, 10, 4):               # the three slots' requests
        tele.record_advanced(iters)
    tele.record_chunk(live=5, capacity=8, chunk_iters=7, wall_s=0.1,
                      flops=7 * 8 * 24 * 64)
    for iters in (7, 7, 3, 2, 1):           # the second slab's requests
        tele.record_advanced(iters)
    led = tele.ledger()
    assert led.conserved()
    # chunk 1: row 40, occupied 30, advanced 24 → freeze 6, padding 10
    # chunk 2: row 56, occupied 35, advanced 20 → freeze 15, padding 21
    assert led.row_iters == 40 + 56
    assert led.live_iters == 24 + 20
    assert led.padding_iters == 10 + 21
    assert led.freeze_iters == 6 + 15
    assert led.device_flops == (40 + 56) * 24 * 64
    snap = tele.snapshot()
    assert snap["ledger"]["row_iters"] == led.row_iters
    assert snap["continuous"]["device_flops"] == (40 + 56) * 24 * 64


# ------------------------------------------------------------------ #
# ServeTelemetry edge cases (snapshot under partial lifecycles)      #
# ------------------------------------------------------------------ #
def test_percentile_empty_sample_is_none():
    assert percentile([], 50) is None
    assert percentile([], 99) is None
    assert percentile([1.0], 50) == 1.0


def test_snapshot_with_in_flight_requests():
    tele = ServeTelemetry(clock=FakeClock())
    for rid, fam in enumerate(("lasso", "lasso", "logreg")):
        tele.record_arrival(rid, fam, "continuous")
    tele.record_admit(0)
    tele.record_completion(0, iters=12, converged=True)
    tele.record_admit(1)                        # admitted, not completed
    snap = tele.snapshot()
    assert snap["requests"] == 3
    assert snap["completed"] == 1
    assert snap["in_flight"] == 2
    assert snap["iters_total"] == 12            # completed requests only
    # latency percentiles come from the one completed request; the
    # in-flight ones must not poison them with None
    assert snap["latency_p50"] is not None
    assert snap["latency_p99"] == snap["latency_p50"]


def test_snapshot_empty_telemetry_percentiles_are_none():
    snap = ServeTelemetry(clock=FakeClock()).snapshot()
    assert snap["requests"] == 0 and snap["in_flight"] == 0
    for key in ("latency_p50", "latency_p99", "latency_mean",
                "latency_max", "queue_wait_p50", "queue_wait_p99"):
        assert snap[key] is None
    assert "continuous" not in snap


def test_snapshot_schema_stability():
    tele = ServeTelemetry(clock=FakeClock())
    tele.record_arrival(0, "lasso", "continuous")
    tele.record_admit(0)
    tele.record_completion(0, iters=5, converged=True)
    tele.record_chunk(live=1, capacity=2, chunk_iters=5, wall_s=0.1)
    snap = tele.snapshot()
    assert set(snap) == {
        "schema",
        "requests", "completed", "in_flight", "converged", "iters_total",
        "latency_p50", "latency_p99", "latency_mean", "latency_max",
        "queue_wait_p50", "queue_wait_p99", "ledger", "compile_cache",
        "continuous"}
    assert set(snap["ledger"]) == set(LEDGER_KEYS) | {"utilization"}


def test_progress_sampling_is_opt_in():
    tele = ServeTelemetry(clock=FakeClock())
    tele.record_arrival(0, "lasso", "continuous")
    tele.record_progress(0, iters=5, stat=0.5)      # off: dropped
    assert tele.requests[0].samples == []
    tele.sample_progress = True
    tele.record_progress(0, iters=5, stat=0.5)
    tele.record_progress(999, iters=1, stat=0.1)    # unknown id: ignored
    # arrival consumed clock tick 0.0; the sample is stamped at 0.5
    assert tele.requests[0].samples == [(pytest.approx(0.5), 5, 0.5)]
    assert "samples" in tele.requests[0].as_dict()


# ------------------------------------------------------------------ #
# Determinism: tracing off is bitwise-identical, on is reproducible  #
# ------------------------------------------------------------------ #
def _run_continuous_batch(probs):
    from repro.client import BatchSpec, FlexaClient
    from repro.config.base import ServeConfig, SolverConfig
    with FlexaClient(backend="continuous",
                     solver=SolverConfig(tol=1e-7, max_iters=4000,
                                         tau_adapt=False),
                     serve=ServeConfig(slab_capacity=4,
                                       chunk_iters=50)) as c:
        return c.run(BatchSpec(problems=probs))


def test_tracing_off_bitwise_identity():
    """The tentpole determinism gate: an untraced run and a traced run
    execute the same device programs — solutions bitwise equal."""
    probs = [_lasso(s) for s in range(3)]
    base = _run_continuous_batch(probs)
    tr = Tracer(clock=FakeClock())
    with tracing(tr):
        traced = _run_continuous_batch(probs)
    assert get_tracer() is None
    np.testing.assert_array_equal(np.asarray(base.x),
                                  np.asarray(traced.x))
    np.testing.assert_array_equal(np.asarray(base.iters),
                                  np.asarray(traced.iters))
    # and the trace actually saw the run
    counts = tr.counts()
    assert counts.get("serve.chunk", 0) > 0
    assert counts.get("serve.admit", 0) == 3
    assert counts.get("serve.evict", 0) == 3


def test_traced_runs_identical_under_injected_clock():
    """Two traced runs of the same workload under the same injected
    clock export byte-identical JSONL (caches pre-warmed so the
    compile-event stream is steady-state)."""
    probs = [_lasso(s) for s in range(3)]
    _run_continuous_batch(probs)                # warm compile caches
    texts = []
    for _ in range(2):
        tr = Tracer(clock=FakeClock())
        with tracing(tr):
            _run_continuous_batch(probs)
        texts.append(tr.to_jsonl())
    assert texts[0] == texts[1]
    assert texts[0]                             # non-empty


def test_path_driver_accepts_injected_clock():
    from repro.path.driver import _solve_path
    prob = _lasso(0)
    base = _solve_path(prob, n_points=4, lam_min_ratio=0.1)
    clocked = _solve_path(prob, n_points=4, lam_min_ratio=0.1,
                          clock=FakeClock())
    np.testing.assert_array_equal(base.x, clocked.x)
    # 2 ticks of 0.5 exactly: t0 at 0.0, wall stamped at 0.5
    assert clocked.meta["wall_s"] == pytest.approx(0.5)
    assert clocked.ledger is not None and clocked.ledger.conserved()
    assert clocked.ledger.device_flops == clocked.device_flops


def test_path_batched_accepts_injected_clock():
    from repro.path.driver import _solve_path_batched
    probs = [_lasso(s) for s in range(2)]
    base = _solve_path_batched(probs, n_points=3, lam_min_ratio=0.1)
    clocked = _solve_path_batched(probs, n_points=3, lam_min_ratio=0.1,
                                  clock=FakeClock())
    for b, c in zip(base, clocked):
        np.testing.assert_array_equal(b.x, c.x)
        assert c.meta["wall_s"] == pytest.approx(0.5)
        assert c.ledger is not None and c.ledger.conserved()


# ------------------------------------------------------------------ #
# Client integration: ledgers + diagnostics                          #
# ------------------------------------------------------------------ #
def test_client_results_carry_conserved_ledgers():
    from repro.client import BatchSpec, FlexaClient, PathSpec, SoloSpec
    with FlexaClient() as c:
        solo = c.run(SoloSpec(_lasso(0)))
        m, n = 24, 64
        assert solo.ledger.conserved()
        assert solo.ledger.device_flops == solo.iters * m * n
        batch = c.run(BatchSpec(problems=[_lasso(s) for s in range(3)]))
        assert batch.ledger.conserved()
        assert batch.ledger.row_iters == \
            int(np.asarray(batch.iters).max()) * 3
        assert batch.ledger.live_iters == int(np.asarray(batch.iters).sum())
        path = c.run(PathSpec(_lasso(0), n_points=4, lam_min_ratio=0.1))
        assert path.ledger.conserved()
        assert path.ledger.device_flops == path.device_flops


def test_client_cv_ledger_not_overcounted():
    """Inline CV folds share ONE sweep-wide ledger; the CVResult ledger
    must equal it (plus any winner re-solve), not K copies of it."""
    from repro.client import CVSpec, FlexaClient
    with FlexaClient() as c:
        r = c.run(CVSpec(problems=[_lasso(s) for s in range(3)],
                         n_points=4, lam_min_ratio=0.1))
        assert r.ledger is not None
        assert r.ledger.as_dict() == r.folds[0].ledger.as_dict()


def test_client_diagnostics_continuous_with_sampling():
    from repro.client import BatchSpec, FlexaClient, TicketDiagnostics
    with FlexaClient(backend="continuous") as c:
        c.telemetry.sample_progress = True
        probs = [_lasso(s) for s in range(3)]
        ticket = c.submit(BatchSpec(problems=probs))
        d0 = c.diagnostics(ticket)              # in flight, pre-step
        assert isinstance(d0, TicketDiagnostics) and not d0.done
        c.result(ticket)
        d = c.diagnostics(ticket)
        assert d.done and d.kind == "batch" and d.backend == "continuous"
        assert len(d.requests) == 3
        for req in d.requests:
            assert req["completed"] is not None
            assert len(req["samples"]) >= 1     # sampling was on
        assert "queued" in c.stats()
        with pytest.raises(KeyError):
            c.diagnostics(999)


def test_client_diagnostics_inline_reports_requests():
    from repro.client import FlexaClient, SoloSpec
    with FlexaClient() as c:
        t = c.submit(SoloSpec(_lasso(0)))
        d = c.diagnostics(t)
        assert d.done and len(d.requests) == 1
        assert d.requests[0]["family"] == "lasso"
        assert d.requests[0]["completed"] is not None
        assert d.as_dict()["backend"] == "inline"


# ------------------------------------------------------------------ #
# Dashboard rendering (pure)                                         #
# ------------------------------------------------------------------ #
def test_sparkline_edges():
    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0, 1.0]) == "▁▁▁"          # flat → floor
    s = sparkline(list(range(100)), width=16)
    assert len(s) == 16
    assert s[0] == "▁" and s[-1] == "█"                 # ends kept
    assert sparkline([0.0, None, 1.0]) == "▁█"          # Nones dropped


def test_render_snapshot_sections():
    tele = ServeTelemetry(clock=FakeClock())
    tele.record_arrival(0, "lasso", "continuous")
    tele.record_admit(0)
    tele.record_completion(0, iters=7, converged=True)
    tele.record_chunk(live=1, capacity=2, chunk_iters=7, wall_s=0.1,
                      flops=7 * 2 * 24 * 64)
    text = render_snapshot(tele.snapshot(), queue_depth=4, title="t")
    for token in ("requests", "queue     depth 4", "latency", "ledger",
                  "slab", "cache"):
        assert token in text
    # empty snapshot renders without crashing and without sections
    empty = render_snapshot({}, title="empty")
    assert "ledger" not in empty


def test_render_requests_sparklines():
    diag = {"ticket": 7, "requests": [
        {"req_id": 0, "family": "lasso", "iters": 42, "converged": True,
         "completed": 1.0,
         "samples": [(0.0, 10, 1.0), (0.5, 20, 0.1), (1.0, 42, 0.01)]},
        {"req_id": 1, "family": "lasso", "iters": 5, "converged": False,
         "completed": None, "samples": []},
    ]}
    text = render_requests([diag])
    assert "req[0]" in text and "done✓" in text
    assert "req[1]" in text and "running" in text
    assert render_requests([]).startswith("(no sampled requests")
