"""Mesh-sharded serve runtime: device-count invariance, routing/stealing
properties, telemetry rollup conservation (the PR's acceptance criteria
live here).

Three tiers, so the suite is meaningful at any device count:

* **pure** — routing, stealing and telemetry rollup are host-side pure
  functions, property-tested with no engine and no devices (hypothesis
  when the optional test extra is installed, a seeded grid otherwise —
  the ``test_selection_rules`` pattern);
* **any-device** — engine contracts that hold at ``mesh_devices=1``
  (bitwise equality with the continuous engine, config validation, the
  staging-buffer aliasing regression) — these run in plain tier-1 CI;
* **multi-device** — the device-count-invariance contract proper,
  skipped unless ≥ 4 devices are visible (the CI ``mesh`` job forces
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``); one slow
  subprocess test forces 4 host devices itself so a 1-device tier-1 run
  still covers the sharded path end to end.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.config.base import ServeConfig, SolverConfig
from repro.problems.lasso import nesterov_instance
from repro.serve import (ContinuousSolverEngine, MeshServeEngine,
                         MeshTelemetry, ServeTelemetry)
from repro.serve.mesh import ROUTING_POLICIES, route_device, steal_victim

from test_serve_continuous import FAMILY_BATCHES, to_request

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False

NDEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    NDEV < 4,
    reason="needs >= 4 devices; set XLA_FLAGS="
           "--xla_force_host_platform_device_count=4 before jax imports "
           "(the CI mesh job does)")


# ------------------------------------------------------------------ #
# Pure routing properties                                            #
# ------------------------------------------------------------------ #
LOAD_CASES = [[0], [0, 0, 0], [3, 1, 2], [5, 5, 5, 5], [2, 0, 0, 7],
              [1, 2, 3, 4, 5, 6, 7, 0], [9, 9, 0, 9]]


def _loads():
    if HAVE_HYPOTHESIS:
        strategies = given(
            st.lists(st.integers(0, 20), min_size=1, max_size=8),
            st.integers(0, 100))
        return lambda f: settings(max_examples=60, deadline=None)(
            strategies(f))
    return pytest.mark.parametrize(
        "loads,cursor", [(l, c) for l in LOAD_CASES for c in (0, 3, 17)])


@_loads()
def test_route_least_loaded_is_argmin_lowest_index(loads, cursor):
    d, cur2 = route_device("least_loaded", loads, cursor)
    assert loads[d] == min(loads)
    assert d == loads.index(min(loads))      # lowest index on ties
    assert cur2 == cursor                    # cursor untouched


@_loads()
def test_route_round_robin_cycles_every_device(loads, cursor):
    d, cur2 = route_device("round_robin", loads, cursor)
    assert d == cursor % len(loads) and cur2 == cursor + 1
    seen, c = [], cursor
    for _ in range(len(loads)):
        d, c = route_device("round_robin", loads, c)
        seen.append(d)
    assert sorted(seen) == list(range(len(loads)))   # fair window


def test_route_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown mesh routing"):
        route_device("lifo", [0, 0], 0)
    assert "least_loaded" in ROUTING_POLICIES
    assert "round_robin" in ROUTING_POLICIES


QLEN_CASES = [([0, 0, 0], 0, 1), ([4, 0, 2], 1, 1), ([4, 0, 2], 0, 1),
              ([2, 2, 2], 1, 3), ([5, 5, 1], 2, 2), ([0, 7], 0, 1),
              ([3], 0, 1), ([1, 1, 1, 1], 2, 1), ([2, 3, 3], 0, 2)]


def _qlens():
    if HAVE_HYPOTHESIS:
        strategies = given(
            st.lists(st.integers(0, 9), min_size=1, max_size=8),
            st.integers(0, 7), st.integers(1, 4))
        return lambda f: settings(max_examples=60, deadline=None)(
            strategies(f))
    return pytest.mark.parametrize("qlens,thief,threshold", QLEN_CASES)


@_qlens()
def test_steal_victim_contract(qlens, thief, threshold):
    thief = thief % len(qlens)
    v = steal_victim(qlens, thief, threshold)
    eligible = [q for d, q in enumerate(qlens)
                if d != thief and q >= threshold]
    if v is None:
        assert not eligible                  # nothing worth stealing
    else:
        assert v != thief                    # never steals from itself
        assert qlens[v] >= threshold
        assert qlens[v] == max(eligible)     # longest queue wins
        assert all(qlens[d] < qlens[v]       # lowest index on ties
                   for d in range(v) if d != thief)


# ------------------------------------------------------------------ #
# Telemetry rollup conservation (pure)                               #
# ------------------------------------------------------------------ #
ADDITIVE_KEYS = ("chunks", "chunk_iters", "row_iters", "live_iters",
                 "advanced_iters", "chunk_wall_s", "device_flops")


def _conservation_holds(snap):
    """global chunk counters == Σ per-device, re-derived from the
    snapshot alone (not trusting rollup's own arithmetic)."""
    glob, per = snap["continuous"], snap["mesh"]["per_device"]
    return all(glob[k] == pytest.approx(sum(p[k] for p in per))
               for k in ADDITIVE_KEYS)


@pytest.mark.parametrize("seed", range(6))
def test_mesh_telemetry_rollup_is_sum_of_parts(seed):
    rng = np.random.default_rng(seed)
    n_dev = int(rng.integers(1, 5))
    tele = MeshTelemetry(n_devices=n_dev)
    for _ in range(int(rng.integers(1, 30))):
        d = int(rng.integers(n_dev))
        cap = int(rng.integers(1, 6))
        K = int(rng.integers(1, 64))
        live = int(rng.integers(0, cap + 1))
        tele.device(d).record_chunk(
            live=live, capacity=cap,
            chunk_iters=K,
            wall_s=float(rng.uniform(0.0, 1e-2)),
            flops=K * cap * 24 * 64)
        tele.device(d).record_advanced(int(rng.integers(0, K * live + 1)))
        if rng.uniform() < 0.3:
            tele.record_steal()
        tele.record_route(int(rng.integers(0, 3)))
    snap = tele.snapshot()
    assert snap["mesh"]["devices"] == n_dev
    assert len(snap["mesh"]["per_device"]) == n_dev
    assert _conservation_holds(snap)
    # the unified ledger rolls up conserved (row = live + padding +
    # freeze) and prices exactly the rolled-up flops
    led = tele.ledger()
    assert led.conserved()
    assert led.device_flops == snap["continuous"]["device_flops"]
    # the derived ratios stay ratios
    assert 0.0 <= snap["continuous"]["occupancy_mean"] <= 1.0
    assert 0.0 <= snap["continuous"]["padding_waste"] <= 1.0
    # snapshot is idempotent: rollup overwrites, never accumulates
    assert snap["continuous"]["chunks"] == \
        tele.snapshot()["continuous"]["chunks"]


def test_mesh_telemetry_configure_contract():
    tele = MeshTelemetry()
    tele.configure(3)
    tele.configure(3)                        # idempotent at same size
    assert tele.n_devices == 3
    with pytest.raises(ValueError, match="one MeshTelemetry"):
        tele.configure(4)
    assert all(t.clock is tele.clock for t in tele.per_device)


# ------------------------------------------------------------------ #
# Engine contracts at any device count                               #
# ------------------------------------------------------------------ #
CFG = SolverConfig(max_iters=600, tol=1e-6, tau_adapt=False)


def mesh_serve(**kw):
    base = dict(slab_capacity=2, chunk_iters=16, mesh_devices=1)
    base.update(kw)
    return ServeConfig(**base)


def test_mesh_one_device_matches_continuous_bitwise():
    """At mesh_devices=1 the sharded slab is the continuous slab run
    under a trivial mesh — results, iteration counts and audit schedule
    must agree bitwise."""
    probs = FAMILY_BATCHES["lasso"]()
    em = MeshServeEngine(CFG, mesh_serve())
    ec = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=16))
    im = [em.submit(to_request(p)) for p in probs]
    ic = [ec.submit(to_request(p)) for p in probs]
    rm, rc = em.drain(), ec.drain()
    for a, b in zip(im, ic):
        assert rm[a].iters == rc[b].iters
        assert rm[a].converged and rc[b].converged
        np.testing.assert_array_equal(np.asarray(rm[a].x),
                                      np.asarray(rc[b].x))
    assert [r["admit_tick"] for r in em.audit] == \
        [r["admit_tick"] for r in ec.audit]
    assert all(r["device"] == 0 and r["stolen_from"] is None
               for r in em.audit)
    assert em.steal_log == []                # nowhere to steal from


def test_mesh_one_device_runs_the_plain_chunk_program():
    """At one device the one chunk builder hands the mesh slab the
    continuous slab's own compiled program (no ``shard_map``)."""
    p = FAMILY_BATCHES["lasso"]()[0]
    em = MeshServeEngine(CFG, mesh_serve())
    ec = ContinuousSolverEngine(
        CFG, ServeConfig(slab_capacity=2, chunk_iters=16))
    em.submit(to_request(p))
    ec.submit(to_request(p))
    (sm,), (sc,) = em._slabs.values(), ec._slabs.values()
    assert sm.n_devices == sc.n_devices == 1
    assert sm._chunk is sc._chunk


def test_mesh_engine_validates_config():
    avail = len(jax.devices())
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        MeshServeEngine(CFG, mesh_serve(mesh_devices=avail + 1))
    with pytest.raises(ValueError, match="unknown mesh routing"):
        MeshServeEngine(CFG, mesh_serve(mesh_routing="random"))
    with pytest.raises(ValueError, match="steal_threshold"):
        MeshServeEngine(CFG, mesh_serve(steal_threshold=0))
    with pytest.raises(TypeError, match="MeshTelemetry"):
        MeshServeEngine(CFG, mesh_serve(), telemetry=ServeTelemetry())


def test_mesh_engine_rejects_resized_telemetry():
    tele = MeshTelemetry(n_devices=2)
    with pytest.raises(ValueError, match="one MeshTelemetry"):
        MeshServeEngine(CFG, mesh_serve(mesh_devices=1), telemetry=tele)


def test_client_mesh_backend_matches_inline():
    from repro.client import FlexaClient, SoloSpec, available_backends
    assert "mesh" in available_backends()
    p = nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=0)
    with FlexaClient(backend="mesh", solver=CFG,
                     serve=mesh_serve(mesh_devices=0)) as client:
        r = client.run(SoloSpec(problem=p))
        stats = client.stats()
    ref = FlexaClient(backend="inline", solver=CFG).run(
        SoloSpec(problem=p))
    np.testing.assert_allclose(np.asarray(r.x), np.asarray(ref.x),
                               atol=1e-5)
    # the client wired up the right telemetry for the backend
    assert stats["telemetry"]["mesh"]["devices"] == NDEV
    assert _conservation_holds(stats["telemetry"])


def test_staging_payload_never_aliases_host_buffers():
    """Regression for a staging race: jnp.asarray zero-copies aligned
    numpy buffers on CPU, so a device payload aliasing a staging buffer
    would let the next tick's admission scribble over data an async
    dispatch is still reading.  Admit under load (queue > slots, every
    visible device), then check no payload array shares memory with any
    staging buffer, and the slab's data with no request's arrays (the
    rows are written into the slab, not referenced)."""
    probs = FAMILY_BATCHES["lasso"]()
    eng = MeshServeEngine(CFG, mesh_serve(slab_capacity=1,
                                          mesh_devices=0))
    reqs = [to_request(p) for p in probs]
    ids = [eng.submit(r) for r in reqs]
    eng.step()                               # admissions staged + shipped
    for slab in eng._slabs.values():
        assert not slab._stage_rows          # every staged row shipped
        host = [slab._stage_c, slab._stage_x0, slab._stage_ids,
                slab._stage_active, slab._stage_tol, slab._admit]
        for arr in slab._payload:
            view = np.asarray(arr)           # zero-copy view on CPU
            assert not any(np.shares_memory(view, h) for h in host)
        given = [a for r in reqs for a in (r.A, r.b)]
        for arr in slab.slab.data:
            view = np.asarray(arr)
            assert not any(np.shares_memory(view, g) for g in given)
    resps = eng.drain()
    assert sorted(resps) == sorted(ids)      # load run still completes


# ------------------------------------------------------------------ #
# Multi-device: the device-count-invariance contract                 #
# ------------------------------------------------------------------ #
def _hard(seed):
    return nesterov_instance(m=20, n=64, nnz_frac=0.3, c=0.3, seed=seed)


def _easy(seed):
    return nesterov_instance(m=20, n=64, nnz_frac=0.05, c=2.0, seed=seed)


def _forced_steal_run():
    """12 requests, capacity 1/device over 4 devices, round-robin
    routing, and every request routed to device 0 is hard: devices 1-3
    drain their easy queues long before device 0 drains its hard ones,
    so the drain tail *must* steal.  Deterministic by construction."""
    probs = [(_hard if i % 4 == 0 else _easy)(seed=i) for i in range(12)]
    cfg = SolverConfig(max_iters=900, tol=1e-6, tau_adapt=False)
    eng = MeshServeEngine(cfg, ServeConfig(
        slab_capacity=1, chunk_iters=16, mesh_devices=4,
        mesh_routing="round_robin", steal_threshold=1))
    ids = [eng.submit(to_request(p)) for p in probs]
    resps = eng.drain()
    return ids, resps, eng


@multi_device
@pytest.mark.parametrize("family", sorted(FAMILY_BATCHES))
def test_mesh_matches_single_device_continuous_all_families(family):
    """The invariance contract: a request's answer does not depend on
    the device count.  Mesh over 4 devices (parallel service) vs a
    capacity-1 single-device continuous engine (fully serial service),
    all four problem families.  Same per-device slot count on both
    sides: a per-slot trajectory depends only on the request's own data
    and PRNG stream, so with the schedule as the only difference the
    fixed-budget results agree to fp32 noise (a *different* per-block
    shape would change XLA's vectorization instead — that is a compiler
    artifact, not a scheduling one, and not what this test pins)."""
    probs = FAMILY_BATCHES[family]()
    cfg = SolverConfig(max_iters=150, tol=-1.0, tau_adapt=False)
    em = MeshServeEngine(cfg, ServeConfig(
        slab_capacity=1, chunk_iters=16, mesh_devices=4))
    ec = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=1, chunk_iters=16))
    im = [em.submit(to_request(p)) for p in probs]
    ic = [ec.submit(to_request(p)) for p in probs]
    rm, rc = em.drain(), ec.drain()
    for a, b in zip(im, ic):
        assert rm[a].iters == rc[b].iters
        np.testing.assert_allclose(np.asarray(rm[a].x),
                                   np.asarray(rc[b].x), atol=1e-5,
                                   err_msg=f"{family} request {a}")


@multi_device
def test_admitted_rows_land_on_their_owning_device_only():
    """An admission writes its rows into the shard of the device that
    owns its slot (slot // S_dev) and nowhere else: the other devices'
    shards are bitwise unchanged and every shard stays on its device."""
    probs = FAMILY_BATCHES["lasso"]()
    eng = MeshServeEngine(CFG, ServeConfig(
        slab_capacity=2, chunk_iters=16, mesh_devices=4,
        mesh_routing="round_robin"))
    reqs = [to_request(p) for p in probs[:2]]
    for r in reqs:
        eng.submit(r)
    eng.step()                       # round robin: device 0, device 1
    assert [rec["device"] for rec in eng.audit] == [0, 1]
    slab, = eng._slabs.values()
    devices = list(slab._rows.mesh.devices.flat)
    before = {sh.device: np.array(sh.data)
              for sh in slab.slab.data[0].addressable_shards}
    new = probs[2]
    rows = (np.asarray(new.data["A"]), np.asarray(new.data["b"]))
    slot = 2 * 2 + 1                 # device 2, local slot 1
    slab._write_rows(slot, rows)
    for j, arr in enumerate(slab.slab.data):
        assert arr.sharding == slab._rows
        shards = arr.addressable_shards
        assert [sh.device for sh in shards] == devices
        np.testing.assert_array_equal(np.asarray(shards[2].data)[1],
                                      rows[j])
    for sh in slab.slab.data[0].addressable_shards:
        got = np.asarray(sh.data)
        if sh.device == devices[2]:
            np.testing.assert_array_equal(got[0], before[sh.device][0])
        else:
            np.testing.assert_array_equal(got, before[sh.device])
    np.testing.assert_array_equal(
        np.asarray(slab.slab.data[0].addressable_shards[0].data)[0],
        reqs[0].A)


@multi_device
def test_device_count_invariance_across_mesh_sizes():
    """Same requests through meshes of 1, 2 and 4 devices (different
    total capacity, co-tenancy and admission schedule): identical
    iteration counts, results within 1e-5 pairwise."""
    probs = FAMILY_BATCHES["lasso"]()
    cfg = SolverConfig(max_iters=1200, tol=1e-7, tau_adapt=False)
    runs = {}
    for ndev in (1, 2, 4):
        eng = MeshServeEngine(cfg, ServeConfig(
            slab_capacity=1, chunk_iters=16, mesh_devices=ndev))
        ids = [eng.submit(to_request(p)) for p in probs]
        resps = eng.drain()
        runs[ndev] = ([resps[i].iters for i in ids],
                      [np.asarray(resps[i].x) for i in ids])
    base_iters, base_x = runs[1]
    for ndev in (2, 4):
        iters, xs = runs[ndev]
        assert iters == base_iters
        for a, b in zip(xs, base_x):
            assert float(np.abs(a - b).max()) <= 1e-5


@multi_device
def test_mesh_bitwise_deterministic_at_fixed_device_count():
    """Fixed seed + submission order + device count reproduces results,
    audit, steal log and telemetry counts bitwise across two fresh
    engines (wall-clock fields excluded — they are the only
    nondeterminism allowed)."""
    ids1, r1, e1 = _forced_steal_run()
    ids2, r2, e2 = _forced_steal_run()
    assert ids1 == ids2
    assert e1.audit == e2.audit
    assert e1.steal_log == e2.steal_log
    for i in ids1:
        assert r1[i].iters == r2[i].iters
        np.testing.assert_array_equal(np.asarray(r1[i].x),
                                      np.asarray(r2[i].x))
    s1, s2 = e1.telemetry.snapshot(), e2.telemetry.snapshot()
    assert s1["mesh"]["steals"] == s2["mesh"]["steals"]
    assert s1["mesh"]["routed"] == s2["mesh"]["routed"]
    for p1, p2 in zip(s1["mesh"]["per_device"], s2["mesh"]["per_device"]):
        for k in ("chunks", "chunk_iters", "row_iters", "live_iters"):
            assert p1[k] == p2[k]


@multi_device
def test_steals_happen_and_each_request_served_exactly_once():
    from collections import Counter
    ids, resps, eng = _forced_steal_run()
    assert len(eng.steal_log) >= 1           # the setup forces stealing
    assert sorted(resps) == sorted(ids)
    counts = Counter(rec["req_id"] for rec in eng.audit)
    assert sorted(counts) == sorted(ids)
    assert all(c == 1 for c in counts.values())   # stealing moves queue
    # entries, never duplicates an admission
    stolen = {rec["req_id"] for rec in eng.steal_log}
    by_id = {rec["req_id"]: rec for rec in eng.audit}
    for rid in stolen:
        assert by_id[rid]["stolen_from"] is not None
        assert by_id[rid]["device"] != by_id[rid]["stolen_from"]


@multi_device
def test_steal_only_when_idle_and_victim_eligible():
    ids, resps, eng = _forced_steal_run()
    threshold = eng.serve.steal_threshold
    for rec in eng.steal_log:
        assert rec["thief_queue_len"] == 0   # thief had no local work
        assert rec["victim_queue_len_before"] >= threshold
        assert rec["thief"] != rec["victim"]


@multi_device
def test_mesh_rollup_conservation_end_to_end():
    ids, resps, eng = _forced_steal_run()
    snap = eng.telemetry.snapshot()
    assert _conservation_holds(snap)
    assert snap["mesh"]["steals"] == len(eng.steal_log)
    assert snap["mesh"]["routed"] == len(ids)     # no warm_from re-routes
    # every device did chunk work (the sharded step runs lock-step)
    assert all(p["chunks"] > 0 for p in snap["mesh"]["per_device"])


@multi_device
@pytest.mark.parametrize("policy", ["priority", "deadline"])
def test_starvation_freedom_under_ordered_policies(policy):
    """A lowest-priority / latest-deadline request behind a steady
    backlog still completes: the queues drain monotonically, and
    stealing only ever moves a request's admission *earlier*."""
    probs = [_easy(seed=s) for s in range(10)]
    cfg = SolverConfig(max_iters=100, tol=-1.0, tau_adapt=False)
    eng = MeshServeEngine(cfg, ServeConfig(
        slab_capacity=1, chunk_iters=16, mesh_devices=4, policy=policy))
    kw = (dict(priority=0) if policy == "priority"
          else dict(deadline=1e9))
    ids = [eng.submit(to_request(probs[0], **kw))]     # the starvee
    ids += [eng.submit(to_request(p,
                                  priority=9, deadline=float(s)))
            for s, p in enumerate(probs[1:], 1)]
    resps = eng.drain()
    assert sorted(resps) == sorted(ids)
    assert all(resps[i].iters == 100 for i in ids)
    # and the starvee really was scheduled last
    admit = {rec["req_id"]: rec["admit_tick"] for rec in eng.audit}
    assert admit[ids[0]] == max(admit.values())


# ------------------------------------------------------------------ #
# Tier-1 multi-device coverage on a 1-device host                    #
# ------------------------------------------------------------------ #
SUBPROC_SRC = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    from collections import Counter
    from repro.config.base import ServeConfig, SolverConfig
    from repro.problems.lasso import nesterov_instance
    from repro.serve import (ContinuousSolverEngine, MeshServeEngine,
                             SolveRequest)
    probs = [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
             for s in range(8)]
    reqs = [SolveRequest(A=np.asarray(p.data["A"]),
                         b=np.asarray(p.data["b"]),
                         c=float(p.g_weight)) for p in probs]
    cfg = SolverConfig(max_iters=600, tol=1e-6, tau_adapt=False)
    em = MeshServeEngine(cfg, ServeConfig(slab_capacity=1, chunk_iters=16,
                                          mesh_devices=4))
    ec = ContinuousSolverEngine(cfg, ServeConfig(slab_capacity=1,
                                                 chunk_iters=16))
    im = [em.submit(r) for r in reqs]
    ic = [ec.submit(r) for r in reqs]
    em.step()
    # first tick: request i admitted to device i, its rows on that
    # device's shard alone
    A = next(iter(em._slabs.values())).slab.data[0]
    rows_on_owner = all(
        np.array_equal(np.asarray(sh.data)[0], reqs[d].A)
        for d, sh in enumerate(A.addressable_shards)) and [
        sh.device for sh in A.addressable_shards] == list(
        A.sharding.mesh.devices.flat)
    rm, rc = em.drain(), ec.drain()
    snap = em.telemetry.snapshot()
    per = snap["mesh"]["per_device"]
    keys = ("chunks", "chunk_iters", "row_iters", "live_iters",
            "advanced_iters")
    led = em.telemetry.ledger()
    print(json.dumps({
        "ledger_exact": (led.conserved() and led.live_iters == sum(
            rm[a].iters for a in im) and led.freeze_iters ==
            snap["continuous"]["live_iters"] - led.live_iters),
        "max_diff": max(float(np.abs(np.asarray(rm[a].x) -
                                     np.asarray(rc[b].x)).max())
                        for a, b in zip(im, ic)),
        "iters_equal": all(rm[a].iters == rc[b].iters
                           for a, b in zip(im, ic)),
        "one_service": sorted(Counter(
            r["req_id"] for r in em.audit).values()) == [1] * len(im),
        "conservation": all(
            snap["continuous"][k] == sum(p[k] for p in per)
            for k in keys),
        "devices": snap["mesh"]["devices"],
        "rows_on_owner": rows_on_owner,
    }))
""")


@pytest.mark.slow
def test_mesh_four_device_subprocess():
    """The sharded path on a forced 4-device host, independent of how
    many devices this process sees — tier-1's multi-device coverage."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SUBPROC_SRC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["max_diff"] <= 1e-5
    assert rec["iters_equal"] and rec["one_service"]
    assert rec["conservation"]
    assert rec["ledger_exact"]
    assert rec["rows_on_owner"]


def test_mesh_slab_never_migrates():
    """``ServeConfig.compact_drain`` is a continuous-engine feature:
    a mesh slab's slot layout IS the device placement (slot s lives on
    device s // S_dev), so drain-tail resizing must be a no-op there —
    same answers, zero migrations, capacities untouched."""
    probs = FAMILY_BATCHES["lasso"]()
    em = MeshServeEngine(CFG, mesh_serve(compact_drain=True))
    e0 = MeshServeEngine(CFG, mesh_serve())
    im = [em.submit(to_request(p)) for p in probs]
    i0 = [e0.submit(to_request(p)) for p in probs]
    rm, r0 = em.drain(), e0.drain()
    assert em.telemetry.migrations == 0
    for slab in em._slabs.values():
        assert slab.capacity == slab._base_capacity
        assert not slab._migration_allowed()
    for a, b in zip(im, i0):
        np.testing.assert_array_equal(np.asarray(rm[a].x),
                                      np.asarray(r0[b].x))
    assert not any(rec.get("migrations") for rec in em.audit)
