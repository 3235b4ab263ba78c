"""Checks of the benchmark itself, on the CPU at small sizes.

    python3 -m pytest -q bench/test_bench.py

* the generator plants the optimum it claims;
* the trace reduction gives the numbers worked out by hand, on a
  synthetic trace and on a small trace recorded on a TPU v5e;
* the correctness check passes the program and fails the control (the
  plain reference in bfloat16 put in the program's place);
* with the timed path broken underneath, a whole run reports
  ``correct`` false, once for each fault a cell can have.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import rehearse  # noqa: E402  (sets JAX_PLATFORMS=cpu before jax loads)
import run  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, reference, trace_reduce  # noqa: E402
from bench import pool as pools  # noqa: E402
from bench.gen.nesterov import make_instances, pool_keys  # noqa: E402


# ------------------------------------------------------------------ #
# Generator                                                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("nnz", [0.05, 0.1])
def test_planted_optimum(nnz):
    m, n, c = 60, 300, 1.0
    base, sign = pool_keys(3, 2 ** 40 + 5, 1)
    A, b, xs, vs = make_instances(base, sign, jnp.asarray([nnz]), m=m, n=n,
                                  c=c)
    A, b, xs = (np.asarray(a[0], np.float64) for a in (A, b, xs))
    v_star = float(vs[0])
    grad = 2.0 * A.T @ (A @ xs - b)
    on = xs != 0
    assert on.sum() == round(nnz * n)
    # 0 ∈ ∇F(x*) + c ∂‖x*‖₁, to float32 precision.
    np.testing.assert_allclose(grad[on], -c * np.sign(xs[on]), atol=1e-4)
    assert np.all(np.abs(grad[~on]) <= c * (1 + 1e-4))
    v = float(np.sum((A @ xs - b) ** 2) + c * np.abs(xs).sum())
    assert abs(v - v_star) <= 1e-6 * v_star
    # No point does better than the planted optimum.
    x, _, _ = reference.solve(jnp.asarray(A, jnp.float32),
                              jnp.asarray(b, jnp.float32), c, 1e-5, 3000)
    v_ref = float(reference.objective(jnp.asarray(A, jnp.float32),
                                      jnp.asarray(b, jnp.float32), c, x))
    assert v_ref >= v_star * (1 - 1e-5)
    assert v_ref <= v_star * (1 + 1e-3)


def test_seed_flips_signs_only():
    """Two seeds give different arrays but the same work."""
    cfg = {"m": 40, "n": 200, "c": 1.0}
    p1 = pools.make(cfg, [0.1, 0.2], 0, 1)
    p2 = pools.make(cfg, [0.1, 0.2], 0, 2 ** 33 + 1)
    for i in range(2):
        a1, a2 = np.asarray(p1.A[i]), np.asarray(p2.A[i])
        assert not np.array_equal(a1, a2)
        np.testing.assert_array_equal(np.abs(a1), np.abs(a2))
        assert p1.v_star[i] == p2.v_star[i]
        k1 = reference.solve(*p1.data(i), 1.0, 2e-3, 2000)[1]
        k2 = reference.solve(*p2.data(i), 1.0, 2e-3, 2000)[1]
        assert int(k1) == int(k2)


# ------------------------------------------------------------------ #
# Trace reduction                                                    #
# ------------------------------------------------------------------ #
def test_reduce_synthetic():
    ms = 1_000_000
    trace = {
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [("fusion.1", 0, 2 * ms), ("fusion.2", 1 * ms, 3 * ms),
                    ("copy.3", 6 * ms, 7 * ms), ("fusion.1", 9 * ms,
                                                 12 * ms)],
            "modules": [("jit_step(7)", 0, 3 * ms),
                        ("jit_step(7)", 6 * ms, 7 * ms),
                        ("jit_other(9)", 9 * ms, 12 * ms)]}],
        "host": [("bench.window", 0, 10 * ms), ("bench.solve", 0, 5 * ms)]}
    r = trace_reduce.reduce(trace, 0, 10 * ms,
                            extra_host=[("serve.tick", 5 * ms, 10 * ms)])
    assert r["window_s"] == pytest.approx(0.010)
    # Busy: [0, 3] ∪ [6, 7] ∪ [9, 10] (the last op clipped) = 5 ms.
    assert r["busy_s"] == [pytest.approx(0.005)]
    # A module that runs past the window is left out of the counts.
    assert r["modules"] == {"jit_step": {"count": 2,
                                         "device_s": pytest.approx(0.004)}}
    assert r["top_ops"][0] == ["jit_step/fusion.1", pytest.approx(0.002)]
    assert r["idle_gaps"] == [["bench.solve", pytest.approx(0.003)],
                              ["serve.tick", pytest.approx(0.002)]]


def test_reduce_recorded_tpu_trace():
    """A 20-iteration solo solve at fig1b, recorded on a TPU v5e and cut
    to its first events: busy time and module counts, recomputed here
    with a plain per-nanosecond count."""
    with open(HERE / "testdata" / "tpu_solo20_trace.json") as f:
        trace = json.load(f)
    (lo, hi), = [(s, e) for n, s, e in trace["host"] if n == "bench.window"]
    r = trace_reduce.reduce(trace, lo, hi)
    ops = trace["devices"][0]["ops"]
    marks = []
    for _, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    marks.sort()
    depth, busy, last = 0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert r["busy_s"][0] == pytest.approx(busy / 1e9)
    steps = [m for m in trace["devices"][0]["modules"]
             if trace_reduce.program_name(m[0]) == "jit_family_step"
             and m[1] >= lo and m[2] <= hi]
    assert r["modules"]["jit_family_step"]["count"] == len(steps) > 0
    assert 0 < r["busy_s"][0] < r["window_s"]


# ------------------------------------------------------------------ #
# Correctness: program vs control                                     #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("cell,m,n", [("m2k.serve.poisson", 800, 4000),
                                      ("m2k.solo", 800, 4000),
                                      ("m5k.solo", 200, 4000)])
def test_control_fails(cell, m, n):
    """At a size a test can hold, with the cell's own ratio of rows to
    columns, the program's answers pass the cell's limits, and the
    bfloat16 control fails them, reading at least three times the program
    on one of the cell's numbers (the limits themselves are set between
    the two readings at the cell's own size on the chip, with
    ``bench/control.py``)."""
    _, _, config, traffic = run.load_cell(cell)
    config = {**config, "m": m, "n": n}
    tol, max_iters = traffic["solver"]["tol"], traffic["solver"]["max_iters"]
    nnz = list(traffic.get("nnz_mix", [traffic.get("nnz")]))
    pool = pools.make(config, nnz * 2, int(traffic["pool_key"]), 11)
    from repro.client import FlexaClient, SoloSpec
    from repro.config.base import SolverConfig
    client = FlexaClient(solver=SolverConfig(**traffic["solver"]))
    answers = []
    for i in range(len(pool)):
        r = client.run(SoloSpec(pool.problem(i)))
        answers.append((i, np.asarray(r.x), r.iters, r.status,
                        r.converged))
    limits = traffic["check"]
    sound = check.compare(pool, answers, tol, max_iters)
    assert check.verdict(sound, limits, 0, 0)[0] is True
    ctl = []
    for i in range(len(pool)):
        x, k, _ = check.reference(pool.data(i), pool.c, tol, max_iters,
                                  precision="bf16")
        ctl.append((i, x, k, "ok", k < max_iters))
    control = check.compare(pool, ctl, tol, max_iters)
    assert check.verdict(control, limits, 0, 0)[0] is False
    assert any(control[k] >= 3 * max(sound[k], 1e-12) for k in limits)


# ------------------------------------------------------------------ #
# Faults planted under a whole run                                    #
# ------------------------------------------------------------------ #
def _rehearse(cell, **traffic):
    from repro.solvers import cache
    cache.clear_all()
    return run.execute(
        ["--workload", cell, "--seed", "5", "--seconds", "2", "--trace",
         "0"],
        rehearsal={"config": rehearse.TINY,
                   "traffic": {**rehearse.TINY_TRAFFIC, **traffic},
                   "peak": rehearse.FAKE_PEAK})


def _state_unchanged(monkeypatch):
    import repro.core.flexa as flexa
    import repro.solvers.batched as batched
    real = flexa.flexa_iteration

    def frozen(problem, cfg, tau_base, state, active=None):
        _, info = real(problem, cfg, tau_base, state, active=active)
        return state, info
    monkeypatch.setattr(flexa, "flexa_iteration", frozen)
    monkeypatch.setattr(batched, "flexa_iteration", frozen)


def _half_slab_left_out(monkeypatch):
    import repro.solvers.batched as batched
    real = batched._freeze_done

    def half(done, new_state, old_state):
        odd = jnp.arange(done.shape[0]) % 2 == 1
        return real(done | odd, new_state, old_state)
    monkeypatch.setattr(batched, "_freeze_done", half)


def _answer_altered(monkeypatch):
    import repro.client.backends as backends
    real = backends.SoloResult

    def altered(*args, **kw):
        r = real(*args, **kw)
        r.x = np.asarray(r.x) * 0.9
        return r
    monkeypatch.setattr(backends, "SoloResult", altered)


def _stops_early(monkeypatch):
    """The program tests its stationarity against ten times the request's
    tolerance and still answers converged."""
    import dataclasses

    import repro.config.base as base
    real = base.SolverConfig

    def loose(**kw):
        cfg = real(**kw)
        return dataclasses.replace(cfg, tol=10 * cfg.tol) if cfg.tol > 0 \
            else cfg
    monkeypatch.setattr(base, "SolverConfig", loose)


#: (cell, fault, traffic overrides).  The half-slab fault needs the
#: slab full: at the rehearsal's own rate every request finds slot 0 free.
FAULTS = [
    ("m2k.solo", _state_unchanged, {}),
    ("m5k.solo", _state_unchanged, {}),
    ("m2k.serve.poisson", _state_unchanged, {}),
    ("m2k.serve.poisson", _half_slab_left_out, {"rate_per_s": 40.0}),
    ("m2k.solo", _answer_altered, {}),
    ("m5k.solo", _answer_altered, {}),
    ("m2k.serve.poisson", _answer_altered, {}),
    ("m2k.solo", _stops_early, {}),
    ("m2k.serve.poisson", _stops_early, {}),
]


@pytest.mark.parametrize("cell,fault,traffic", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f, _ in FAULTS])
def test_fault_makes_run_incorrect(cell, fault, traffic, monkeypatch):
    fault(monkeypatch)
    res = _rehearse(cell, drain_limit_s=3, **traffic)
    assert res["correct"] is False


def test_sound_run_is_correct():
    res = _rehearse("m2k.serve.poisson")
    assert res["correct"] is True and res["failed"] == 0
