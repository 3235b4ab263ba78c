"""The program's spans on the profiler's clock, and what they measure.

* **solo driver** — ``flexa.solve`` emits one ``solo.prepare`` per solve
  and one ``solo.dispatch`` / ``solo.sync`` / ``solo.readback`` per
  iteration, and its results are bitwise the same traced or not;
* **admission** — the continuous engine emits one ``serve.stage`` per
  admission and one ``serve.upload`` per admitting tick (none on a
  plain tick) that ships the tick's admitted rows alone, and
  ``serve.chunk`` no longer holds the upload;
* **ledger** — the continuous and mesh engines split their row
  iterations exactly: live = Σ iterations of the answers, freeze =
  occupied rows − live, padding = empty rows;
* **profiler** — spans reach a CPU profiler capture as host events, and
  the iteration's named scopes reach the compiled program's metadata;
* **reduction** — ``bench/trace_program.py``'s ``idle_in`` and
  ``scopes``, on synthetic traces, a CPU capture and a recorded v5e
  trace, and the per-layer readers that read the new spans.
"""
import glob
import importlib.util
import json
import re
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.config.base import ServeConfig, SolverConfig
from repro.core import flexa
from repro.obs import Tracer, tracing
from repro.problems.lasso import nesterov_instance
from repro.serve import (ContinuousSolverEngine, MeshServeEngine,
                         SolveRequest)

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_program, trace_reduce  # noqa: E402

MS = 1_000_000


@pytest.fixture(autouse=True)
def _silence_legacy_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", FutureWarning)
        yield


def _lasso(seed: int, m: int = 20, n: int = 64):
    return nesterov_instance(m=m, n=n, nnz_frac=0.15, c=1.0, seed=seed)


def _request(p):
    return SolveRequest(A=np.asarray(p.data["A"]),
                        b=np.asarray(p.data["b"]), c=float(p.g_weight))


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}",
        ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _named(tracer, name):
    return [s for s in tracer.spans if s.name == name]


# ------------------------------------------------------------------ #
# Solo host loop                                                     #
# ------------------------------------------------------------------ #
def test_solve_emits_one_span_set_per_iteration():
    p = _lasso(0)
    tr = Tracer()
    with tracing(tr):
        r = flexa.solve(p, cfg=SolverConfig(max_iters=40, tol=1e-4))
    counts = tr.counts()
    assert counts["solo.solve"] == counts["solo.prepare"] == 1
    for name in ("solo.dispatch", "solo.sync", "solo.readback"):
        spans = _named(tr, name)
        assert len(spans) == r.iters
        assert [s.args["it"] for s in spans] == list(range(r.iters))
    (whole,) = _named(tr, "solo.solve")
    assert whole.args == {"iters": r.iters, "converged": r.converged}
    # every per-iteration span sits directly under the solve span, in
    # dispatch → sync → readback order
    kids = [s for s in tr.spans if s.parent_id == whole.span_id]
    assert [s.name for s in kids[1:4]] == ["solo.dispatch", "solo.sync",
                                          "solo.readback"]
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))


def test_solve_is_bitwise_identical_traced_or_not():
    p = _lasso(1)
    cfg = SolverConfig(max_iters=60, tol=1e-5)
    base = flexa.solve(p, cfg=cfg)
    with tracing(Tracer()):
        traced = flexa.solve(p, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(base.x), np.asarray(traced.x))
    assert base.iters == traced.iters
    assert base.history["V"] == traced.history["V"]


def test_trace_module_imports_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)   # import jax fails
    spec = importlib.util.spec_from_file_location(
        "trace_without_jax", ROOT / "src" / "repro" / "obs" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "trace_without_jax", mod)
    spec.loader.exec_module(mod)
    assert mod.span("x") is mod._NULL_CM


# ------------------------------------------------------------------ #
# Admission spans                                                    #
# ------------------------------------------------------------------ #
def _traced_engine_run(n_req=3, capacity=2, K=8):
    eng = ContinuousSolverEngine(SolverConfig(max_iters=400, tol=1e-5),
                                 ServeConfig(slab_capacity=capacity,
                                             chunk_iters=K))
    ids = [eng.submit(_request(_lasso(s))) for s in range(n_req)]
    tr = Tracer()
    with tracing(tr):
        out = eng.drain()
    return eng, tr, ids, out


def test_continuous_stage_and_upload_spans():
    eng, tr, ids, out = _traced_engine_run()
    stages = _named(tr, "serve.stage")
    admits = [i for i in tr.instants if i.name == "serve.admit"]
    assert len(stages) == len(admits) == len(ids)
    assert sorted(s.args["req_id"] for s in stages) == sorted(ids)
    slab, = eng._slabs.values()
    assert all(s.args["bytes"] == slab._row_bytes > 0 for s in stages)
    ticks = _named(tr, "serve.tick")
    uploads = _named(tr, "serve.upload")
    by_tick = {}
    for s in stages + uploads:
        by_tick.setdefault(s.parent_id, []).append(s.name)
    admitting = [t for t in ticks if "serve.stage" in by_tick.get(
        t.span_id, [])]
    # one upload per admitting tick, none on a plain tick
    for t in ticks:
        names = by_tick.get(t.span_id, [])
        assert names.count("serve.upload") == (t in admitting)
    assert len(uploads) == len(admitting) >= 2
    # each upload ships the rows its tick staged, and the vectors
    for u in uploads:
        k = by_tick[u.parent_id].count("serve.stage")
        assert u.args["rows"] == k >= 1
        assert u.args["bytes"] == k * slab._row_bytes + slab._vector_bytes
    # serve.chunk starts after the upload it follows has ended
    for u in uploads:
        chunk = next(s for s in tr.spans if s.name == "serve.chunk"
                     and s.span_id > u.span_id)
        assert chunk.t0 >= u.t1


@pytest.mark.parametrize("k", [1, 2, 4])
def test_upload_ships_only_the_admitted_rows(k):
    """A tick that admits k of S = 4 requests ships k data rows: the
    upload's ``rows`` is k and its ``bytes`` k rows (A and b) plus the
    per-slot vectors, not the whole (S, m, n) slab."""
    S, m, n = 4, 20, 64
    eng = ContinuousSolverEngine(SolverConfig(max_iters=400, tol=1e-5),
                                 ServeConfig(slab_capacity=S,
                                             chunk_iters=8))
    for s in range(k):
        eng.submit(_request(_lasso(s, m=m, n=n)))
    tr = Tracer()
    with tracing(tr):
        eng.step()
    (up,) = _named(tr, "serve.upload")
    row = 4 * (m * n + m)
    vectors = 4 * (S + S * n + S + S * n + S) + S   # c x0 ids active tol admit
    assert up.args["rows"] == k
    assert up.args["bytes"] == k * row + vectors


def test_continuous_collect_span_per_evicting_tick():
    _, tr, ids, _ = _traced_engine_run()
    collects = _named(tr, "serve.collect")
    evicts = [i for i in tr.instants if i.name == "serve.evict"]
    assert sum(c.args["evicted"] for c in collects) == len(evicts) \
        == len(ids)
    assert len({e.parent_id for e in evicts}) == len(collects)


# ------------------------------------------------------------------ #
# Exact continuous ledger                                            #
# ------------------------------------------------------------------ #
def _ledger_case(engine_cls, serve):
    eng = engine_cls(SolverConfig(max_iters=500, tol=1e-5), serve)
    probs = [_lasso(s) for s in range(5)]
    ids = [eng.submit(_request(p)) for p in probs]
    out = eng.drain()
    return eng, [out[i] for i in ids]


@pytest.mark.parametrize("engine_cls,serve", [
    (ContinuousSolverEngine, ServeConfig(slab_capacity=2, chunk_iters=16)),
    (ContinuousSolverEngine, ServeConfig(slab_capacity=4, chunk_iters=7)),
    (MeshServeEngine, ServeConfig(slab_capacity=2, chunk_iters=16,
                                  mesh_devices=1)),
])
def test_continuous_ledger_is_exact(engine_cls, serve):
    eng, answers = _ledger_case(engine_cls, serve)
    tele = eng.telemetry
    led = tele.ledger()
    assert led.conserved()
    assert led.live_iters == sum(a.iters for a in answers)
    occupied = tele.chunk_live_iters
    assert led.freeze_iters == occupied - led.live_iters
    assert led.padding_iters == tele.chunk_row_iters - occupied
    # requests converge inside chunks, so some held rows are freeze
    assert led.freeze_iters > 0
    # occupied rows: K per tick a request spent in its slot
    K = serve.chunk_iters
    assert occupied == sum(K * (r["evict_tick"] - r["admit_tick"] + 1)
                           for r in eng.audit)
    snap = tele.snapshot()["continuous"]
    assert snap["advanced_iters"] == led.live_iters
    assert snap["freeze_waste"] == pytest.approx(
        led.freeze_iters / led.row_iters)


# ------------------------------------------------------------------ #
# Profiler capture and named scopes                                  #
# ------------------------------------------------------------------ #
def _capture(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]


def test_spans_reach_a_cpu_profiler_capture(tmp_path):
    p = _lasso(2)
    cfg = SolverConfig(max_iters=5, tol=-1.0)
    flexa.solve(p, cfg=cfg)                    # compile outside the capture
    tr = Tracer()

    def work():
        with tracing(tr):
            flexa.solve(p, cfg=cfg)
    path = _capture(tmp_path, work)
    data = jax.profiler.ProfileData.from_file(path)
    host = [e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    for name in ("solo.solve", "solo.prepare", "solo.dispatch",
                 "solo.sync", "solo.readback"):
        assert host.count(name) == tr.counts()[name]


def _scopes_in(compiled_text: str) -> set:
    return {trace_program.scope_of(op) for op in
            re.findall(r'op_name="([^"]+)"', compiled_text)}


def test_iteration_scopes_name_the_compiled_operations():
    """The scopes reach the solo step's optimized HLO as op metadata,
    and the chunk program's splice carries its own scope."""
    from repro.solvers.batched import (BatchedProblemSpec, make_chunk_stepper,
                                       slab_alloc)
    p = _lasso(3)
    cfg = SolverConfig()
    step = flexa.make_step(p, cfg)
    state = flexa.init_state(p, jnp.zeros(p.n), cfg)
    hlo = jax.jit(step).lower(state).compile().as_text()
    assert {"grad", "best_response", "select", "update", "objective",
            "tau"} <= _scopes_in(hlo)
    spec = BatchedProblemSpec.of(p)
    S = 2
    args = (slab_alloc(spec, cfg, S), np.ones(S, bool), np.zeros(S, bool),
            np.zeros(S, np.float32), np.zeros((S, p.n), np.float32),
            np.zeros(S, np.int32), np.ones((S, p.n), np.float32),
            np.full(S, cfg.tol, np.float32))
    hlo = make_chunk_stepper(spec, cfg, 4).lower(*args).compile().as_text()
    assert {"splice", "grad", "objective"} <= _scopes_in(hlo)


def test_program_op_names_from_a_cpu_capture(tmp_path):
    def f(a, x):
        with jax.named_scope("grad"):
            g = a.T @ (a @ x)
        with jax.named_scope("objective"):
            v = jnp.sum((a @ (x - g)) ** 2)
        return g, v
    jf = jax.jit(f)
    a, x = jnp.ones((16, 32)), jnp.ones(32)
    jf(a, x)[1].block_until_ready()
    path = _capture(tmp_path, lambda: jf(a, x)[1].block_until_ready())
    names = trace_program.program_op_names(path)
    (prog,) = [k for k in names if k.startswith("jit_f(")]
    found = {trace_program.scope_of(op) for op in names[prog].values()}
    assert {"grad", "objective"} <= found


@pytest.mark.parametrize("op_name,scope", [
    ("jit(family_step)/vmap(objective)/dot_general", "objective"),
    ("jit(family_step)/vmap(tau)/jit(_where)/select_n", "tau"),
    ("jit(chunk)/while/body/vmap(grad)/dot_general", "grad"),
    ("jit(chunk)/cond/branch_1_fun/splice/vmap()/dot_general", "splice"),
    ("jit(chunk)/while/body/closed_call/jit(_where)/select_n", ""),
    ("jit(family_step)/vmap()/mul", ""),
    ("reduce_sum", ""),
    ("jit(f)/transpose(jvp(update))/add", "update"),
])
def test_scope_of(op_name, scope):
    assert trace_program.scope_of(op_name) == scope


# ------------------------------------------------------------------ #
# Reduction: idle_in and scopes                                      #
# ------------------------------------------------------------------ #
def _synthetic():
    return {
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [("%fusion.1 = f32[] fusion()", 0, 2 * MS),
                    ("%fusion.2 = f32[] fusion()", 2 * MS, 3 * MS),
                    ("%while.1 = () while()", 6 * MS, 9 * MS),
                    ("%fusion.3 = f32[] fusion()", 6 * MS, 7 * MS),
                    ("%fusion.4 = f32[] fusion()", 7 * MS, 9 * MS)],
            "modules": [("jit_step(7)", 0, 3 * MS),
                        ("jit_chunk(9)", 6 * MS, 9 * MS)],
            "scopes": ["grad", "objective", "", "grad", "splice"]}],
        "host": [("bench.window", 0, 10 * MS),
                 ("serve.tick", 2 * MS, 8 * MS),
                 ("serve.stage", 3 * MS, 4 * MS),
                 ("serve.upload", 4 * MS, 6 * MS),
                 ("serve.tick", 9 * MS, 10 * MS)]}


def test_idle_in_synthetic():
    trace = _synthetic()
    idle = trace_program.idle_in(trace, 0, 10 * MS, trace["host"])
    # device idle: [3, 6] and [9, 10]
    assert idle["bench.window"] == pytest.approx(0.004)
    assert idle["serve.tick"] == pytest.approx(0.004)     # [3,6] + [9,10]
    assert idle["serve.stage"] == pytest.approx(0.001)
    assert idle["serve.upload"] == pytest.approx(0.002)
    # clipped to the window
    part = trace_program.idle_in(trace, 0, 5 * MS, trace["host"])
    assert part["serve.tick"] == pytest.approx(0.002)
    assert part["serve.upload"] == pytest.approx(0.001)


def test_scopes_and_reduce_synthetic():
    trace = _synthetic()
    sc = trace_program.scopes(trace, 0, 10 * MS)
    # the while that holds fusion.3/4 is left out
    assert sc == {"jit_step": {"grad": pytest.approx(0.002),
                               "objective": pytest.approx(0.001)},
                  "jit_chunk": {"grad": pytest.approx(0.001),
                                "splice": pytest.approx(0.002)}}
    r = trace_program.reduce(trace, 0, 10 * MS)
    assert r["scopes"] == sc
    assert r["idle_in"]["serve.upload"] == pytest.approx(0.002)
    # idle gaps are named by the innermost program span at their middle
    assert r["idle_gaps"] == [["serve.upload", pytest.approx(0.003)],
                              ["serve.tick", pytest.approx(0.001)]]
    # what trace_reduce.reduce returned is all still there
    base = trace_reduce.reduce(trace, 0, 10 * MS)
    assert {k: r[k] for k in base} == base


def dev_ops(trace):
    """Device 0's ops as ``(program, start, end)``."""
    dev = trace["devices"][0]
    mods = sorted(dev["modules"], key=lambda m: m[1])
    return [(trace_reduce._owner(mods, s), s, e) for _, s, e in dev["ops"]]


def test_reduce_recorded_v5e_trace():
    """A few solo iterations at fig1b and a few served ticks, recorded on
    a TPU v5e with the program's spans and the ops' scopes."""
    with open(ROOT / "bench" / "testdata" / "tpu_scopes_trace.json") as f:
        trace = json.load(f)
    (lo, hi), = [(s, e) for n, s, e in trace["host"]
                 if n == "bench.window"]
    r = trace_program.reduce(trace, lo, hi)
    # The chunk program reads its 640 MB slab three times an iteration,
    # at one speed: the gradient's two passes and the objective's one.
    chunk = r["scopes"]["jit_chunk"]
    passes = chunk["grad"] + chunk["objective"]
    assert chunk["objective"] / passes == pytest.approx(1 / 3, abs=0.02)
    assert 0.0 < chunk["splice"] < chunk["objective"]
    # Every leaf op of the solo step is in some scope's or in "".
    step = r["scopes"]["jit_family_step"]
    assert {"grad", "objective", "select"} <= set(step)
    leaf = sum(e - s for _, s, e in trace_reduce._leaf_ops(
        [op for op in dev_ops(trace) if op[0] == "jit_family_step"]))
    assert sum(step.values()) == pytest.approx(leaf / 1e9)
    # idle_in recomputed by brute force on a 10 µs grid
    dev = trace["devices"][0]
    busy = trace_reduce.union([(s, e) for _, s, e in dev["ops"]], lo, hi)
    grid = np.arange(lo, hi, 10_000.0) + 5_000.0
    idle = np.ones(grid.size, bool)
    for s, e in busy:
        idle[(grid >= s) & (grid < e)] = False
    for name in ("solo.sync", "serve.upload", "serve.tick"):
        inside = np.zeros(grid.size, bool)
        for n, s, e in trace["host"]:
            if n == name:
                inside |= (grid >= s) & (grid < e)
        assert r["idle_in"][name] == pytest.approx(
            (idle & inside).sum() * 1e-5, abs=2e-4)
    assert r["idle_in"]["serve.tick"] <= r["idle_in"]["bench.window"]


# ------------------------------------------------------------------ #
# Per-layer readers                                                  #
# ------------------------------------------------------------------ #
def test_admission_readers_on_a_handmade_record():
    rec = {"host_spans": [
        ("serve.tick", 0.0, 1.0), ("serve.stage", 0.1, 0.2),
        ("serve.stage", 0.2, 0.4), ("serve.upload", 0.4, 0.7),
        ("serve.chunk", 0.7, 0.8),
        ("serve.tick", 1.0, 1.1), ("serve.chunk", 1.0, 1.1),
        ("serve.tick", 2.0, 2.6), ("serve.stage", 2.1, 2.2),
        ("serve.upload", 2.2, 2.3), ("serve.chunk", 2.3, 2.5)]}
    assert _reader("admit_stage_ms")(rec) == pytest.approx(200.0)
    assert _reader("admit_upload_ms")(rec) == pytest.approx(200.0)
    # a program without the spans reads nothing
    bare = {"host_spans": [("serve.tick", 0.0, 1.0),
                           ("serve.chunk", 0.2, 0.4)]}
    assert _reader("admit_stage_ms")(bare) is None
    assert _reader("admit_upload_ms")(bare) is None
    assert _reader("admit_upload_ms")({}) is None


def test_slot_iter_waste_reader_matches_the_ledger():
    eng, tr, ids, out = _traced_engine_run(n_req=5, capacity=4, K=8)
    rec = {"host_spans": [(s.name, s.t0, s.t1) for s in tr.spans],
           "iters": [out[i].iters for i in ids],
           "traffic": {"serve": {"slab_capacity": 4, "chunk_iters": 8}}}
    led = eng.telemetry.ledger()
    want = 100.0 * (led.padding_iters + led.freeze_iters) / led.row_iters
    assert _reader("slot_iter_waste.serve")(rec) == pytest.approx(want)
    assert 0.0 < want < 100.0
    assert _reader("slot_iter_waste.serve")(
        {"traffic": rec["traffic"]}) is None
