"""Continuous-batching runtime: equivalence, scheduling properties,
telemetry, and the bounded compile caches (the PR's acceptance criteria
live here)."""
from collections import Counter

import numpy as np
import pytest

from repro.config.base import ServeConfig, SolverConfig
from repro.problems.group_lasso import nesterov_group_instance
from repro.problems.lasso import nesterov_instance
from repro.problems.logreg import random_logreg_instance
from repro.problems.svm import random_svm_instance
from repro.serve import (AdmissionQueue, ContinuousSolverEngine,
                         MeshServeEngine, MeshTelemetry, QueueEntry,
                         ServeTelemetry, SolveRequest)
from repro.solvers.api import _solve as solve
from repro.solvers.cache import cache_stats
import repro.solvers.batched as B


def to_request(p, **kw):
    """Problem -> SolveRequest (design matrix key varies per family)."""
    fam = p.family
    if fam in ("lasso", "group_lasso"):
        return SolveRequest(A=np.asarray(p.data["A"]),
                            b=np.asarray(p.data["b"]),
                            c=float(p.g_weight),
                            block_size=p.block_size, **kw)
    return SolveRequest(A=np.asarray(p.data["Z"]), c=float(p.g_weight),
                        family=fam, **kw)


FAMILY_BATCHES = {
    "lasso": lambda: [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0,
                                        seed=s) for s in range(5)],
    "group_lasso": lambda: [nesterov_group_instance(
        m=24, n_blocks=16, block_size=4, nnz_frac=0.25, c=1.0, seed=s)
        for s in range(5)],
    "logreg": lambda: [random_logreg_instance(m=30, n=48, nnz_frac=0.2,
                                              c=0.5, seed=s)
                       for s in range(5)],
    "svm": lambda: [random_svm_instance(m=30, n=40, nnz_frac=0.2, c=0.5,
                                        seed=s) for s in range(5)],
}


# ------------------------------------------------------------------ #
# Acceptance: slab-served == solo solve, all four families           #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("family", sorted(FAMILY_BATCHES))
def test_continuous_matches_solo_all_families(family):
    """Every request served through the slab matches its solo solve()
    within 1e-5 — fixed iteration budget, tau_adapt off (the usual fp32
    reduction-order caveat for cross-driver comparisons), capacity 2 for
    five requests so eviction/backfill genuinely runs."""
    probs = FAMILY_BATCHES[family]()
    cfg = SolverConfig(max_iters=150, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=16))
    ids = [eng.submit(to_request(p)) for p in probs]
    resps = eng.drain()
    assert len(resps) == len(probs)
    for i, p in zip(ids, probs):
        assert resps[i].iters == 150
        solo = solve(p, method="flexa", cfg=cfg)
        np.testing.assert_allclose(np.asarray(resps[i].x),
                                   np.asarray(solo.x), atol=1e-5,
                                   err_msg=f"{family} request {i}")


def test_continuous_convergence_eviction_matches_solo():
    """Tol-based stopping: converged slots are evicted mid-stream and
    still match their solo solves (tight tol keeps the fp32 stopping-time
    noise inside 1e-5); iteration counts vary per request."""
    probs = FAMILY_BATCHES["lasso"]()
    cfg = SolverConfig(max_iters=1500, tol=1e-7, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=32))
    ids = [eng.submit(to_request(p)) for p in probs]
    resps = eng.drain()
    iters = [resps[i].iters for i in ids]
    assert all(resps[i].converged for i in ids)
    assert len(set(iters)) > 1          # not wave lock-step
    for i, p in zip(ids, probs):
        solo = solve(p, method="flexa", cfg=cfg)
        np.testing.assert_allclose(np.asarray(resps[i].x),
                                   np.asarray(solo.x), atol=1e-5)


def test_chunk_stepper_matches_wave_program():
    """A full slab chunk-stepped to completion reproduces the batched
    run-to-convergence while_loop program exactly (same freeze merge ⇒
    same stopping iteration, chunk size K irrelevant)."""
    import jax.numpy as jnp

    probs = FAMILY_BATCHES["lasso"]()[:4]
    cfg = SolverConfig(max_iters=1000, tol=1e-6, tau_adapt=False)
    spec = B.BatchedProblemSpec.of(probs[0])
    data = tuple(jnp.stack([jnp.asarray(p.data[k], jnp.float32)
                            for p in probs]) for k in ("A", "b"))
    c = jnp.asarray([float(p.g_weight) for p in probs], jnp.float32)
    x0 = jnp.zeros((4, spec.n), jnp.float32)

    run = B.make_batched_solver(spec, cfg)
    wave_final, wave_conv = run(data, c, x0)

    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=4, chunk_iters=17))
    ids = [eng.submit(to_request(p)) for p in probs]
    resps = eng.drain()
    for j, i in enumerate(ids):
        # NB the batched program seeds per-instance keys by *slot*, the
        # continuous runtime by *request id* — identical here because
        # submission order fills slots 0..3 with ids 0..3.
        assert resps[i].iters == int(np.asarray(wave_final.k)[j])
        np.testing.assert_allclose(np.asarray(resps[i].x),
                                   np.asarray(wave_final.x)[j],
                                   atol=1e-6)


# ------------------------------------------------------------------ #
# Scheduler properties                                               #
# ------------------------------------------------------------------ #
def test_no_slot_double_booking_and_exactly_one_service():
    probs = FAMILY_BATCHES["lasso"]()
    cfg = SolverConfig(max_iters=400, tol=1e-6, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=16))
    ids = [eng.submit(to_request(p)) for p in probs]
    eng.drain()

    served = [rec["req_id"] for rec in eng.audit]
    assert sorted(served) == sorted(ids)          # exactly once each
    by_slot: dict = {}
    for rec in eng.audit:
        assert rec["evict_tick"] is not None
        assert rec["admit_tick"] <= rec["evict_tick"]
        by_slot.setdefault((rec["signature"], rec["slot"]),
                           []).append((rec["admit_tick"],
                                       rec["evict_tick"]))
    for intervals in by_slot.values():
        intervals.sort()
        for (_, e1), (a2, _) in zip(intervals, intervals[1:]):
            assert a2 > e1            # next tenancy starts after eviction


def test_deterministic_under_fixed_seed_and_trace():
    probs = FAMILY_BATCHES["lasso"]()

    def run():
        cfg = SolverConfig(max_iters=2000, tol=1e-6, selection="hybrid",
                           sel_p=0.5, seed=3)
        eng = ContinuousSolverEngine(
            cfg, ServeConfig(slab_capacity=2, chunk_iters=16))
        ids = [eng.submit(to_request(p)) for p in probs]
        resps = eng.drain()
        return ids, resps, eng.audit

    ids1, r1, audit1 = run()
    ids2, r2, audit2 = run()
    assert ids1 == ids2
    assert audit1 == audit2
    for i in ids1:
        assert r1[i].iters == r2[i].iters
        np.testing.assert_array_equal(np.asarray(r1[i].x),
                                      np.asarray(r2[i].x))


def test_randomized_selection_stream_is_request_keyed():
    """A request's randomized-selection trajectory must not depend on
    what shares the slab: solo occupancy vs riding along with another
    request gives bitwise-identical iterates (stream keyed by req_id)."""
    p = nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=0)
    q = nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=9)
    cfg = SolverConfig(max_iters=120, tol=-1.0, tau_adapt=False,
                       selection="random", sel_p=0.5, seed=5)
    serve = ServeConfig(slab_capacity=2, chunk_iters=16)

    eng1 = ContinuousSolverEngine(cfg, serve)
    i1 = eng1.submit(to_request(p))
    r1 = eng1.drain()[i1]

    eng2 = ContinuousSolverEngine(cfg, serve)
    i2 = eng2.submit(to_request(p))      # same req_id 0 ⇒ same stream
    eng2.submit(to_request(q))           # neighbour must not perturb it
    r2 = eng2.drain()[i2]
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))


# ------------------------------------------------------------------ #
# Admission queue policies                                           #
# ------------------------------------------------------------------ #
def _entries():
    r = SolveRequest(A=np.zeros((2, 2), np.float32),
                     b=np.zeros(2, np.float32))
    return [
        QueueEntry(req_id=0, request=r, arrival=0.0, priority=0,
                   deadline=9.0),
        QueueEntry(req_id=1, request=r, arrival=1.0, priority=5,
                   deadline=None),
        QueueEntry(req_id=2, request=r, arrival=2.0, priority=5,
                   deadline=1.0),
        QueueEntry(req_id=3, request=r, arrival=3.0, priority=1,
                   deadline=2.0),
    ]


def test_admission_queue_policies_order():
    for policy, want in [("fifo", [0, 1, 2, 3]),
                         ("priority", [1, 2, 3, 0]),
                         ("deadline", [2, 3, 0, 1])]:
        q = AdmissionQueue(policy)
        for e in _entries():
            q.push(e)
        got = [q.pop().req_id for _ in range(len(_entries()))]
        assert got == want, (policy, got)
    with pytest.raises(ValueError, match="unknown admission policy"):
        AdmissionQueue("lifo")


def test_priority_policy_reorders_admissions_end_to_end():
    probs = FAMILY_BATCHES["lasso"]()[:3]
    cfg = SolverConfig(max_iters=60, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=1, chunk_iters=16,
                         policy="priority"))
    ids = [eng.submit(to_request(p, priority=pr))
           for p, pr in zip(probs, (0, 1, 7))]
    eng.drain()
    admit_order = [rec["req_id"] for rec in eng.audit]
    assert admit_order == [ids[2], ids[1], ids[0]]


def test_deadline_policy_serves_earliest_deadline_first():
    probs = FAMILY_BATCHES["lasso"]()[:3]
    cfg = SolverConfig(max_iters=60, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=1, chunk_iters=16,
                         policy="deadline"))
    ids = [eng.submit(to_request(p, deadline=d))
           for p, d in zip(probs, (5.0, None, 1.0))]
    eng.drain()
    admit_order = [rec["req_id"] for rec in eng.audit]
    assert admit_order == [ids[2], ids[0], ids[1]]   # dated first, EDF


def test_continuous_engine_rejects_malformed_requests():
    eng = ContinuousSolverEngine(SolverConfig(max_iters=10))
    Z = np.zeros((5, 4), np.float32)
    with pytest.raises(ValueError, match="takes no b"):
        eng.submit(SolveRequest(A=Z, b=np.zeros(5, np.float32),
                                family="logreg"))
    with pytest.raises(ValueError, match="needs b"):
        eng.submit(SolveRequest(A=Z, c=1.0))
    assert eng.pending == 0


# ------------------------------------------------------------------ #
# Slab pack/unpack API                                               #
# ------------------------------------------------------------------ #
def _one_tick(layout: str, watchdog: bool):
    """One tick of the one chunk builder at ``n_devices=1`` on a slab of
    four slots, three of them admitted this tick (the fourth empty)."""
    import jax.numpy as jnp
    from repro.obs.health import HealthConfig
    from test_sparse_designs import csc_from_dense

    probs = FAMILY_BATCHES["lasso"]()[:3]
    reqs = [SolveRequest(A=(np.asarray(p.data["A"]) if layout == "dense"
                            else csc_from_dense(p.data["A"])),
                         b=np.asarray(p.data["b"]), c=float(p.g_weight))
            for p in probs]
    spec = reqs[0].spec
    assert spec.layout == layout
    cfg = SolverConfig(max_iters=300, tol=1e-6, tau_adapt=False)
    S, n = 4, spec.n
    slab = B.slab_alloc(spec, cfg, S)
    write = B.make_row_writer(spec)
    for slot, r in enumerate(reqs):
        slab = write(slab, np.int32(slot),
                     *(jnp.asarray(a) if not hasattr(a, "padded") else a
                       for a in r.data_arrays(spec)))
    admit = np.array([True, True, True, False])
    payload = (np.array([r.c for r in reqs] + [1.0], np.float32),
               np.zeros((S, n), np.float32),
               np.arange(S, dtype=np.int32),
               np.ones((S, n), np.float32),
               np.full((S,), cfg.tol, np.float32))
    health = HealthConfig(stall_window=10) if watchdog else None
    carry = () if health is None else (np.full((S,), np.inf, np.float32),
                                       np.zeros((S,), np.int32))
    chunk = B.make_chunk_stepper(spec, cfg, 8, 1, health)
    return chunk(slab, jnp.ones((S,), bool), jnp.asarray(admit),
                 *(jnp.asarray(a) for a in payload),
                 *(jnp.asarray(a) for a in carry))


@pytest.mark.parametrize("layout", ("dense", "csc"))
@pytest.mark.parametrize("watchdog", (False, True), ids=("off", "on"))
def test_one_chunk_builder_at_one_device(watchdog, layout):
    """The one chunk builder at one device: a tick returns ``(slab,
    stop)`` with the watchdog off and ``(slab, status, prev_stat,
    stall)`` with it on, and on healthy slots its slab equals the other
    variant's bit for bit (the health pass only reads the iterates)."""
    import jax
    from repro.obs.health import STATUS_RUNNING, STATUS_STOPPED

    out = _one_tick(layout, watchdog)
    other = _one_tick(layout, not watchdog)
    if watchdog:
        assert len(out) == 4
        slab, status, prev_stat, stall = out
        assert np.asarray(status).dtype == np.int32
        assert set(np.asarray(status).tolist()) <= {STATUS_RUNNING,
                                                    STATUS_STOPPED}
        np.testing.assert_array_equal(np.asarray(prev_stat)[:3],
                                      np.asarray(slab.state.stat)[:3])
        assert np.asarray(stall).dtype == np.int32
        stop = np.asarray(status) != STATUS_RUNNING
        other_slab, other_stop = other
    else:
        assert len(out) == 2
        slab, stop = out
        assert np.asarray(stop).dtype == bool
        other_slab, status = other[:2]
        np.testing.assert_array_equal(
            np.asarray(status) != STATUS_RUNNING, np.asarray(stop))
        other_stop = np.asarray(status) != STATUS_RUNNING
    np.testing.assert_array_equal(np.asarray(stop), np.asarray(other_stop))
    assert not np.asarray(stop)[:3].all()       # admitted slots ran
    assert np.asarray(slab.state.k)[:3].min() > 0
    for a, b in zip(jax.tree_util.tree_leaves(slab),
                    jax.tree_util.tree_leaves(other_slab)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_row_writer_leaves_other_slots_bitwise_unchanged():
    """Admission's row write touches the admitted slot's data rows and
    nothing else: every other slot's data and every non-data buffer
    (state included, mid-solve) come back bitwise as they were."""
    import jax

    probs = FAMILY_BATCHES["lasso"]()
    cfg = SolverConfig(max_iters=400, tol=1e-7, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=4, chunk_iters=8))
    for p in probs[:4]:
        eng.submit(to_request(p))
    eng.step()                                  # four slots mid-solve
    slab, = eng._slabs.values()
    before = jax.tree_util.tree_map(np.array, slab.slab)
    new = probs[4]
    rows = (np.asarray(new.data["A"]), np.asarray(new.data["b"]))
    after = slab._row_writer(slab.slab, np.int32(2), *rows)
    for j, (d0, d1) in enumerate(zip(before.data, after.data)):
        d1 = np.asarray(d1)
        np.testing.assert_array_equal(d1[2], rows[j])
        np.testing.assert_array_equal(np.delete(d1, 2, axis=0),
                                      np.delete(d0, 2, axis=0))
    rest = lambda t: jax.tree_util.tree_leaves(t._replace(data=()))
    for a, b in zip(rest(before), rest(after)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_row_writer_compiles_once_across_admission_counts():
    """One row-writer program per signature, however many requests a
    tick admits (1..S): the slot is a traced scalar, so admission counts
    never reach the compile cache or jit's trace cache."""
    S = 4
    spec_probs = [nesterov_instance(m=12, n=40, nnz_frac=0.2, c=1.0,
                                    seed=s) for s in range(S)]
    cfg = SolverConfig(max_iters=50, tol=1e-6)
    misses0 = B.make_row_writer.stats()["misses"]
    writers = set()
    for k in range(1, S + 1):
        eng = ContinuousSolverEngine(
            cfg, ServeConfig(slab_capacity=S, chunk_iters=8))
        for p in spec_probs[:k]:
            eng.submit(to_request(p))
        eng.step()
        slab, = eng._slabs.values()
        assert int(slab.active.sum()) == k
        writers.add(slab._row_writer)
    assert B.make_row_writer.stats()["misses"] == misses0 + 1
    (write,) = writers
    assert write._cache_size() == 1


@pytest.mark.parametrize("watchdog", [False, True])
def test_row_admission_matches_solo(watchdog):
    """Requests admitted through the row writer match their solo
    solves, with the health watchdog's chunk program and without."""
    probs = FAMILY_BATCHES["lasso"]()
    cfg = SolverConfig(max_iters=150, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=16,
                         watchdog=watchdog))
    ids = [eng.submit(to_request(p)) for p in probs]
    resps = eng.drain()
    for i, p in zip(ids, probs):
        assert resps[i].iters == 150 and resps[i].status == "ok"
        solo = solve(p, method="flexa", cfg=cfg)
        np.testing.assert_allclose(np.asarray(resps[i].x),
                                   np.asarray(solo.x), atol=1e-5)


# ------------------------------------------------------------------ #
# Compile caches: bounded + instrumented                             #
# ------------------------------------------------------------------ #
def test_compile_cache_bounded_by_env(monkeypatch):
    cache = B.make_chunk_stepper
    cfg = SolverConfig(max_iters=7)
    specs = [B.BatchedProblemSpec(m=4, n=8 + 2 * i) for i in range(3)]

    monkeypatch.setenv("REPRO_COMPILE_CACHE_SIZE", "2")
    for s in specs:
        cache(s, cfg, 5)
    assert len(cache) <= 2
    stats = cache.stats()
    assert stats["maxsize"] == 2
    assert stats["evictions"] >= 1

    # LRU behaviour: re-requesting the newest entry is a hit...
    hits0 = cache.stats()["hits"]
    cache(specs[-1], cfg, 5)
    assert cache.stats()["hits"] == hits0 + 1
    # ...the evicted oldest is a miss (rebuilt).
    misses0 = cache.stats()["misses"]
    cache(specs[0], cfg, 5)
    assert cache.stats()["misses"] == misses0 + 1

    monkeypatch.setenv("REPRO_COMPILE_CACHE_SIZE", "not-a-number")
    assert cache.maxsize() == cache.default_maxsize

    snap = cache_stats()
    for name in ("batched_solver", "chunk_stepper", "row_writer"):
        assert {"hits", "misses", "evictions", "size",
                "maxsize"} <= set(snap[name])


def test_cache_counters_flow_through_serve_telemetry():
    tele = ServeTelemetry()
    snap = tele.snapshot()
    assert "chunk_stepper" in snap["compile_cache"]


# ------------------------------------------------------------------ #
# Telemetry                                                          #
# ------------------------------------------------------------------ #
def test_shared_telemetry_never_collides_request_ids():
    """One telemetry shared by two engines (the apples-to-apples mode)
    must keep every request distinct — ids are allocated by the
    telemetry, not per-engine counters."""
    probs = FAMILY_BATCHES["lasso"]()[:2]
    cfg = SolverConfig(max_iters=50, tol=-1.0, tau_adapt=False)
    serve = ServeConfig(slab_capacity=2, chunk_iters=16, mesh_devices=1)
    tele = MeshTelemetry()
    mesh = MeshServeEngine(cfg, serve, telemetry=tele)
    cont = ContinuousSolverEngine(cfg, serve, telemetry=tele)
    ids = [eng.submit(to_request(p)) for eng in (mesh, cont)
           for p in probs]
    mesh.drain()
    cont.drain()
    assert sorted(ids) == [0, 1, 2, 3]
    assert sorted(tele.requests) == [0, 1, 2, 3]
    assert all(r.completed is not None for r in tele.requests.values())


def test_telemetry_latency_percentiles_explicit_clock():
    tele = ServeTelemetry()
    for i, (arr, adm, done) in enumerate([(0.0, 1.0, 2.0),
                                          (0.0, 1.0, 3.0),
                                          (1.0, 1.5, 11.0)]):
        tele.record_arrival(i, "lasso", "continuous", t=arr)
        tele.record_admit(i, t=adm)
        tele.record_completion(i, iters=10, converged=True, t=done)
    snap = tele.snapshot()
    assert snap["latency_p50"] == pytest.approx(3.0)
    assert snap["latency_max"] == pytest.approx(10.0)
    assert snap["queue_wait_p50"] == pytest.approx(1.0)
    assert snap["iters_total"] == 30


# ------------------------------------------------------------------ #
# Load generator                                                     #
# ------------------------------------------------------------------ #
def test_trace_generators_are_seeded_and_shaped():
    import benchmarks.serve_load as SL

    t1 = SL.TRACES["poisson"](16, 3)
    t2 = SL.TRACES["poisson"](16, 3)
    assert t1 == t2
    assert all(a.arrival <= b.arrival for a, b in zip(t1, t1[1:]))
    assert all(0.0 <= t.difficulty <= 1.0 for t in t1)

    burst = SL.TRACES["bursty"](24, 0)
    assert len({t.arrival for t in burst}) == 2    # 12-request bursts

    rng_uniform = [t.difficulty for t in SL.TRACES["poisson"](400, 1)]
    rng_pareto = [t.difficulty for t in SL.TRACES["heavy_tail"](400, 1)]
    assert np.median(rng_pareto) < np.median(rng_uniform)   # mostly easy
    assert np.max(rng_pareto) > 0.9                         # with a tail


@pytest.mark.slow
def test_serve_load_full_sweep(tmp_path, monkeypatch):
    """The full trace sweep: every trace replays through the continuous
    engine, and its heavy-tail responses are solo-equivalent — the
    BENCH_serve.json acceptance block."""
    import benchmarks.serve_load as SL

    monkeypatch.setattr(SL, "RESULTS", tmp_path)
    art = SL.main()
    assert all(art["acceptance"].values()), art["acceptance"]
    assert (tmp_path / "BENCH_serve.json").exists()


# ------------------------------------------------------------------ #
# Drain-tail slab compaction (ServeConfig.compact_drain)             #
# ------------------------------------------------------------------ #
def _straggler_trace():
    """Six same-signature requests whose iteration counts spread ~100 to
    ~180 (measured at tol 1e-7): once the fast ones evict, the slowest
    request holds the slab alone for chunks on end — the drain tail the
    shape migration exists for."""
    return [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
            for s in range(6)]


def _run_trace(probs, cfg, serve):
    eng = ContinuousSolverEngine(cfg, serve)
    ids = [eng.submit(to_request(p)) for p in probs]
    return eng, ids, eng.drain()


DRAIN_CFG = SolverConfig(max_iters=6000, tol=1e-7, seed=0)


def test_drain_tail_migration_forced_straggler():
    """With compact_drain on, the forced straggler is migrated into
    narrower slabs as the tail drains: telemetry counts migrations, the
    audit carries the per-request migration trail, every request is
    served exactly once, and the straggler finishes in a bucket smaller
    than the base capacity."""
    probs = _straggler_trace()
    eng, ids, resp = _run_trace(probs, DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=8, compact_drain=True))
    assert eng.telemetry.migrations >= 1
    assert eng.telemetry.snapshot()["continuous"]["migrations"] \
        == eng.telemetry.migrations
    # the straggler (slowest request) was still live through the
    # shrink: its final bucket is narrower than the base slab
    slowest = max(ids, key=lambda i: resp[i].iters)
    assert resp[slowest].bucket < 8
    trail = [rec for rec in eng.audit if rec.get("migrations")]
    assert trail, "no audit record carries a migration trail"
    from repro.solvers.compaction import bucket_capacity
    for rec in trail:
        for mv in rec["migrations"]:
            # capacities are buckets: powers of two capped at base
            assert mv["to_capacity"] == bucket_capacity(
                mv["to_capacity"], 8)
            assert mv["from_capacity"] != mv["to_capacity"]
    # exactly-once service across all capacities
    counts = Counter(rec["req_id"] for rec in eng.audit)
    assert sorted(counts) == sorted(ids)
    assert all(v == 1 for v in counts.values())


def test_drain_tail_migration_off_by_default():
    probs = _straggler_trace()
    eng, ids, resp = _run_trace(probs, DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=8))
    assert eng.telemetry.migrations == 0
    assert all(resp[i].bucket == 8 for i in ids)
    assert not any(rec.get("migrations") for rec in eng.audit)


def test_drain_tail_responses_match_fixed_capacity():
    """Migration is a bitwise row move but the chunk program retraces at
    each capacity, so the contract is solver-tolerance agreement (≤1e-5)
    with the never-migrated run — convergence flags and near-identical
    iteration counts included."""
    probs = _straggler_trace()
    _, ids0, r0 = _run_trace(probs, DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=8))
    eng, ids1, r1 = _run_trace(probs, DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=8, compact_drain=True))
    assert eng.telemetry.migrations >= 1
    for i0, i1 in zip(ids0, ids1):
        np.testing.assert_allclose(np.asarray(r1[i1].x),
                                   np.asarray(r0[i0].x), atol=1e-5)
        assert r1[i1].converged == r0[i0].converged


def test_drain_tail_live_iters_conserved_through_migration():
    """Telemetry conservation: with one slab serviced every tick,
    chunk_live_iters == K · Σ_req (evict_tick − admit_tick + 1) —
    migrations move rows but never duplicate or drop a live-slot
    iteration."""
    probs = _straggler_trace()
    K = 8
    eng, ids, _ = _run_trace(probs, DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=K, compact_drain=True))
    assert eng.telemetry.migrations >= 1
    expect = sum(K * (rec["evict_tick"] - rec["admit_tick"] + 1)
                 for rec in eng.audit)
    assert eng.telemetry.chunk_live_iters == expect


def test_drain_tail_grows_back_on_new_arrivals():
    """A shrunk slab grows back toward its base capacity when arrivals
    outnumber the free slots — nobody queues forever behind a narrow
    slab, and service stays exactly-once across both directions."""
    probs = [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
             for s in range(10)]
    eng = ContinuousSolverEngine(DRAIN_CFG, ServeConfig(
        slab_capacity=8, chunk_iters=8, compact_drain=True))
    ids = [eng.submit(to_request(p)) for p in probs[:6]]
    slab = None
    for _ in range(200):                     # tick until the tail shrank
        eng.step()
        slab = next(iter(eng._slabs.values()))
        if slab.capacity < 8 or not slab.pending:
            break
    assert slab.capacity < 8 and slab.live > 0
    shrunk = slab.capacity
    ids += [eng.submit(to_request(p)) for p in probs[6:]]
    eng.step()
    assert slab.capacity > shrunk            # grew back for the flood
    resp = eng.drain()
    assert sorted(resp) == sorted(ids)
    counts = Counter(rec["req_id"] for rec in eng.audit)
    assert sorted(counts) == sorted(ids)
    assert all(v == 1 for v in counts.values())


# ------------------------------------------------------------------ #
# Per-request tolerance (one slab, mixed tolerances)                 #
# ------------------------------------------------------------------ #
def test_per_request_tol_mixes_on_one_slab():
    """Two copies of the same problem, one at a loose per-request tol,
    share a slab: the loose one is evicted earlier (fewer iterations),
    both stop under their own threshold — the slab-resident tol vector
    the ROADMAP said was missing."""
    p = nesterov_instance(m=30, n=64, nnz_frac=0.15, c=1.0, seed=0)
    cfg = SolverConfig(max_iters=2000, tol=1e-7, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=5))
    loose = eng.submit(to_request(p, tol=1e-2))
    tight = eng.submit(to_request(p))            # engine default 1e-7
    resp = eng.drain()
    assert resp[loose].converged and resp[tight].converged
    assert resp[loose].iters < resp[tight].iters
    assert resp[loose].stat <= 1e-2
    assert resp[tight].stat <= 1e-7
    # Same fixed point, up to the loose stopping accuracy.
    np.testing.assert_allclose(np.asarray(resp[loose].x),
                               np.asarray(resp[tight].x), atol=2e-1)


def test_per_request_tol_default_matches_engine_tol():
    """``tol=None`` requests behave exactly as before the refactor —
    the per-request column defaults to the engine config's tol."""
    p = nesterov_instance(m=24, n=64, nnz_frac=0.15, c=1.0, seed=1)
    cfg = SolverConfig(max_iters=2000, tol=1e-6, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=16))
    rid_default = eng.submit(to_request(p))
    rid_explicit = eng.submit(to_request(p, tol=1e-6))
    resp = eng.drain()
    assert resp[rid_default].iters == resp[rid_explicit].iters
    np.testing.assert_array_equal(np.asarray(resp[rid_default].x),
                                  np.asarray(resp[rid_explicit].x))


# ------------------------------------------------------------------ #
# Deadline expiry (the timeout path of the service policy)           #
# ------------------------------------------------------------------ #
def test_expire_overdue_queued_and_live():
    """The deadline sweep evicts overdue work through the normal
    eviction path: a queued victim never costs a chunk (iters=0, no
    audit row — it was never admitted), a live victim's audit record is
    closed with status="timeout", and the freed slot is reused."""
    probs = [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
             for s in range(3)]
    cfg = SolverConfig(max_iters=10_000, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=1, chunk_iters=4))

    live = eng.submit(to_request(probs[0], deadline=1e5))
    eng.step()                                   # admit into the slot
    queued = eng.submit(to_request(probs[1], deadline=-1.0))

    # Sweep at now=0: only the queued entry is overdue.
    assert eng.expire_overdue(now=0.0) == [queued]
    rq = eng.responses[queued]
    assert rq.status == "timeout" and rq.iters == 0
    assert not rq.converged and not np.isfinite(rq.stat)
    assert queued not in {rec["req_id"] for rec in eng.audit}

    # Sweep past the live request's deadline: evicted mid-flight.
    assert eng.expire_overdue(now=2e5) == [live]
    rl = eng.responses[live]
    assert rl.status == "timeout" and not rl.converged
    assert rl.iters > 0                          # it did run chunks
    (rec,) = [r for r in eng.audit if r["req_id"] == live]
    assert rec["status"] == "timeout"

    assert [f.req_id for f in eng.failures
            if f.status == "timeout"] == [queued, live]

    # The freed slot serves new work; exactly-once audit holds.
    ok = eng.submit(to_request(probs[2]))
    resp = eng.drain()
    assert resp[ok].iters == 10_000
    counts = Counter(rec["req_id"] for rec in eng.audit)
    assert all(v == 1 for v in counts.values())

    snap = eng.telemetry.snapshot()
    assert snap["schema"] == 1
    assert snap["health"]["timeouts"] == 2


def test_expire_overdue_without_deadlines_is_a_no_op():
    probs = FAMILY_BATCHES["lasso"]()[:2]
    cfg = SolverConfig(max_iters=50, tol=-1.0, tau_adapt=False)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=16))
    ids = [eng.submit(to_request(p)) for p in probs]
    assert eng.expire_overdue(now=1e18) == []
    resp = eng.drain()
    assert sorted(resp) == sorted(ids)
