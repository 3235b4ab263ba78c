"""Sparse designs on the main path: the column-compressed layout's
products, its slabs, and requests served through the client.

Small sizes throughout (m = 300, n = 1,000, about 0.8% dense, Zipf
column counts), made by the benchmark's own generator
(``bench/gen/text_sparse.py``), whose planted optimum is checked here
too.
"""
import re
import sys
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.client import BatchSpec, FlexaClient, PathSpec, SoloSpec
from repro.client.backends import _dims
from repro.client.errors import UnsupportedWorkloadError
from repro.config.base import ServeConfig, SolverConfig
from repro.obs import Tracer, tracing
from repro.problems.lasso import make_lasso, nesterov_instance
from repro.kernels import ref
from repro.kernels.spmv import BLOCK, TILE
from repro.problems.sparse import (CSCDesign, block_layout, capacity_bucket,
                                   gate_designs, stack_designs,
                                   tile_padding_bound)
from repro.remote.protocol import ProtocolError, encode_problem
from repro.serve import SolveRequest
import repro.solvers.batched as B

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check  # noqa: E402
from bench.gen import text_sparse  # noqa: E402
from bench.gen.nesterov import pool_keys  # noqa: E402

SMALL = {"m": 300, "n": 1000, "c": 1.0, "nnz_mean": 2500, "nnz_spread": 0.1}
SUPPORT = [0.005, 0.005, 0.01, 0.01, 0.02, 0.02]
TOL, MAX_ITERS = 2e-3, 2000


@pytest.fixture(scope="module")
def pool():
    """Six instances as a tenant sends them: host arrays, trimmed."""
    return text_sparse.make(SMALL, SUPPORT, 7, 2 ** 40 + 3).to_host()


def _client(backend="continuous", S=4, **solver):
    return FlexaClient(backend=backend,
                       solver=SolverConfig(**{"tol": TOL,
                                              "max_iters": MAX_ITERS,
                                              **solver}),
                       serve=ServeConfig(slab_capacity=S, chunk_iters=16))


def _serve(client, problems):
    tickets = [client.submit(SoloSpec(p)) for p in problems]
    while client.pending:
        client.step()
    return [client.result(t) for t in tickets]


def csc_from_dense(A, capacity=None) -> CSCDesign:
    """A dense (m, n) array's column-compressed design (host arrays),
    padded to ``capacity`` stored entries when given."""
    A = np.asarray(A, np.float32)
    m, n = A.shape
    cols, rows = np.nonzero(A.T)
    col_ptr = np.zeros((n + 1,), np.int32)
    np.cumsum(np.bincount(cols, minlength=n), out=col_ptr[1:])
    d = CSCDesign(A[rows, cols], rows.astype(np.int32), col_ptr, (m, n))
    return d if capacity is None else d.padded(capacity)


def todense(d) -> np.ndarray:
    """A stored (blocked) design as its (m, n) float32 array."""
    return np.asarray(jnp.zeros(d.shape, jnp.float32).at[
        d.rows, d.cols].add(d.values))


# ------------------------------------------------------------------ #
# Layout and products                                                 #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kernels", ["ref", "interpret"])
@pytest.mark.parametrize("padded", [False, True])
def test_sparse_products_equal_dense(pool, padded, kernels, monkeypatch):
    """A·x, Aᵀ·r and ‖aⱼ‖² of the stored layout equal the dense products
    (in float64) to float32 accuracy, with the capacity bucket's padding
    and with twice that, on the XLA path and on the Pallas kernel's
    (interpreted)."""
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    rng = np.random.default_rng(0)
    for i in range(len(pool)):
        A = CSCDesign(pool.values[i], pool.rows[i], pool.col_ptr[i],
                      (pool.m, pool.n))
        cap = capacity_bucket(A.capacity, pool.m, pool.n)
        A = A.padded(2 * cap if padded else cap)
        A = block_layout(A.values, A.rows, A.col_ptr, A.shape)
        dense = np.asarray(pool.data(i)[0], np.float64)
        x = rng.standard_normal(pool.n).astype(np.float32)
        r = rng.standard_normal(pool.m).astype(np.float32)
        ax, atr = dense @ x, dense.T @ r
        np.testing.assert_allclose(np.asarray(A.matvec(jnp.asarray(x))), ax,
                                   atol=1e-5 * np.abs(ax).max())
        np.testing.assert_allclose(np.asarray(A.rmatvec(jnp.asarray(r))),
                                   atr, atol=1e-5 * np.abs(atr).max())
        csq = (dense ** 2).sum(axis=0)
        np.testing.assert_allclose(np.asarray(A.col_sq()), csq,
                                   rtol=1e-5, atol=1e-12)
        np.testing.assert_array_equal(todense(A), dense)


def test_block_layout_keeps_every_entry_and_pads_per_block_pair():
    """Over several block pairs, empty columns and padding included:
    the stored layout holds exactly the design's entries, each tile's
    entries lie in its own block pair, and each pair is topped up by
    fewer than TILE zero entries (never per column)."""
    rng = np.random.default_rng(3)
    m, n = BLOCK + 500, 2 * BLOCK + 300
    A = np.where(rng.random((m, n)) < 0.002,
                 rng.standard_normal((m, n)), 0.0).astype(np.float32)
    A[:, 7:40] = 0.0                       # empty columns
    A[:, 1] = rng.standard_normal(m)       # a column as long as m
    d = csc_from_dense(A)
    nnz = d.nnz
    cap = capacity_bucket(nnz, m, n)
    assert cap >= nnz + tile_padding_bound(m, n)
    p = d.padded(cap)
    s = block_layout(p.values, p.rows, p.col_ptr, p.shape)
    np.testing.assert_array_equal(todense(s), A)
    vals = np.asarray(s.values).reshape(-1, TILE)
    rb = np.asarray(s.rows).reshape(-1, TILE) // BLOCK
    cb = np.asarray(s.cols).reshape(-1, TILE) // BLOCK
    live = vals != 0
    assert live.sum() == nnz
    for t in range(vals.shape[0]):
        assert (rb[t][live[t]] == int(s.tile_rb[t])).all()
        assert (cb[t][live[t]] == int(s.tile_cb[t])).all()
    # each pair's entries fill ⌈held / TILE⌉ tiles; the tiles past the
    # last pair hold zeros only
    used = live.any(axis=1)
    pair = np.asarray(s.tile_rb) * 100 + np.asarray(s.tile_cb)
    for p_ in set(pair[used]):
        held = live[pair == p_].sum()
        assert (used & (pair == p_)).sum() == -(-held // TILE)
    empty = np.flatnonzero(~used)
    assert not empty.size or not used[empty[0]:].any()
    # exactly the tiles past the last pair carry the block index -1
    held = sum(-(-live[pair == p_].sum() // TILE) for p_ in set(pair[used]))
    past = np.arange(vals.shape[0]) >= held
    assert past.any()
    np.testing.assert_array_equal(np.asarray(s.tile_rb) < 0, past)
    np.testing.assert_array_equal(np.asarray(s.tile_cb) < 0, past)
    assert (np.asarray(s.tile_rb)[~past] >= 0).all()


def _pool_stack(pool, k, capacity):
    return stack_designs(
        [CSCDesign(pool.values[i], pool.rows[i], pool.col_ptr[i],
                   (pool.m, pool.n)) for i in range(k)], capacity)


_PRODUCTS = {"matvec": lambda d, v: d.matvec(v),
             "rmatvec": lambda d, v: d.rmatvec(v),
             "col_sq": lambda d, v: d.col_sq()}


@pytest.mark.parametrize("product", sorted(_PRODUCTS))
def test_gated_products_equal_the_ungated_ones_on_live_designs(
        pool, product, monkeypatch):
    """A vmapped product over a stack with a mixed live mask (the Pallas
    kernel, interpreted) gives the ungated call's outputs bit for bit on
    the live designs and zeros on the gated ones; padded to 8 × the
    bucket, most of each design's tile steps hold no entries."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    k = 4
    stack = _pool_stack(pool, k, 8 * 4096)
    live = np.array([False, True, False, True])
    n_in = pool.n if product == "matvec" else pool.m
    v = jnp.asarray(np.random.default_rng(5).standard_normal((k, n_in)),
                    jnp.float32)
    f = jax.jit(jax.vmap(_PRODUCTS[product]))
    gated, = gate_designs((stack,), lambda: jnp.asarray(live))
    got, full = np.asarray(f(gated, v)), np.asarray(f(stack, v))
    np.testing.assert_array_equal(got[live], full[live])
    assert not got[~live].any()
    # the ungated stack equals each design's own call to float32 accuracy
    for i in range(k):
        one = jax.tree_util.tree_map(lambda a: a[i], stack)
        np.testing.assert_allclose(
            full[i], np.asarray(_PRODUCTS[product](one, v[i])),
            rtol=1e-6, atol=1e-6 * np.abs(full[i]).max())


@pytest.mark.parametrize("product", ["matvec", "rmatvec"])
def test_trailing_tiles_with_the_sentinel_match_the_reference(
        product, monkeypatch):
    """Over several block pairs and many trailing tiles, the kernel
    (interpreted) computes what ``ref.blocked_product_ref`` does."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    rng = np.random.default_rng(11)
    m, n = BLOCK + 300, 2 * BLOCK + 100
    A = np.where(rng.random((m, n)) < 0.001,
                 rng.standard_normal((m, n)), 0.0).astype(np.float32)
    d = csc_from_dense(A)
    p = d.padded(4 * capacity_bucket(d.nnz, m, n))
    s = block_layout(p.values, p.rows, p.col_ptr, p.shape)
    assert (np.asarray(s.tile_rb) < 0).sum() > 16      # trailing steps
    if product == "matvec":
        v = jnp.asarray(rng.standard_normal(n), jnp.float32)
        want = ref.blocked_product_ref(s.values, s.cols, s.rows, v, m)
    else:
        v = jnp.asarray(rng.standard_normal(m), jnp.float32)
        want = ref.blocked_product_ref(s.values, s.rows, s.cols, v, n)
    got = np.asarray(_PRODUCTS[product](s, v))
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def test_capacity_bucket_is_the_next_power_of_two():
    """The bucket holds the nnz and the tile padding bound: designs of
    the rcv1 cell (1.35–1.65 M nonzeros) share the 2²¹ bucket."""
    assert tile_padding_bound(300, 1000) == TILE - 1
    assert capacity_bucket(1, 300, 1000) == 1024
    assert capacity_bucket(2, 300, 1000) == 2048
    assert capacity_bucket(1_350_000, 20_242, 47_236) == capacity_bucket(
        1_650_000, 20_242, 47_236) == 2 ** 21


# ------------------------------------------------------------------ #
# Generator                                                           #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("support", [0.005, 0.01, 0.02])
def test_generator_plants_a_lasso_optimum(support):
    """The planted x* satisfies the Lasso KKT conditions on the sparse
    design: 2aⱼᵀ(Ax* − b) = −c·sign(x*ⱼ) on the support, |·| ≤ c off
    it, and V(x*) is the closed-form V*.  The design has the
    configuration's pattern: every column holds an entry, none more
    than m, nnz within the spread."""
    m, n, c = SMALL["m"], SMALL["n"], SMALL["c"]
    base, sign = pool_keys(3, 2 ** 40 + 5, 1)
    vals, rows, ptr, b, xs, vs = text_sparse.make_instances(
        base, sign, jnp.asarray([support]), m=m, n=n,
        cap=text_sparse.capacity(SMALL), nnz_mean=3000.0, nnz_spread=0.1,
        c=c)
    A = np.asarray(text_sparse.densify(vals[0], rows[0], ptr[0], m=m, n=n),
                   np.float64)
    counts = np.diff(np.asarray(ptr[0]))
    assert counts.min() >= 1 and counts.max() <= m
    assert (A != 0).sum() == counts.sum()
    assert 0.9 * 3000 - n <= counts.sum() <= 1.1 * 3000
    x, bb = np.asarray(xs[0], np.float64), np.asarray(b[0], np.float64)
    grad = 2.0 * A.T @ (A @ x - bb)
    on = x != 0
    assert on.sum() == round(support * n)
    np.testing.assert_allclose(grad[on], -c * np.sign(x[on]), atol=1e-4)
    assert np.all(np.abs(grad[~on]) <= c * (1 + 1e-4))
    v = float(np.sum((A @ x - bb) ** 2) + c * np.abs(x).sum())
    assert abs(v - float(vs[0])) <= 1e-6 * float(vs[0])


# ------------------------------------------------------------------ #
# Served through the client                                          #
# ------------------------------------------------------------------ #
#: Limits of the served answers against the plain dense reference
#: (``bench/reference.py``) on the densified design, at the answer's own
#: iteration count.  The program's products are float32 segment sums in
#: another order than the reference's float32 matmul, so the two part by
#: float32 rounding alone: they read about 1e-7 here.  A design rounded
#: to bfloat16 (3 significant digits) reads about 2e-3.  Each limit sits
#: two orders of magnitude from both.
OBJ_LIMIT, X_LIMIT = 1e-5, 1e-4


def _answers(results):
    return [(i, np.asarray(r.x), r.iters, r.status, r.converged)
            for i, r in enumerate(results)]


def test_continuous_sparse_requests_agree_with_the_reference(pool):
    client = _client(S=4)
    results = _serve(client, [pool.problem(i) for i in range(len(pool))])
    assert all(r.converged and r.status == "ok" for r in results)
    numbers = check.compare(pool, _answers(results), TOL, MAX_ITERS)
    assert numbers["obj_dev"] <= OBJ_LIMIT
    assert numbers["x_dev"] <= X_LIMIT
    assert numbers["iters_short"] <= 0.0
    # The same comparison fails a design rounded to bfloat16.
    control = []
    for i in range(len(pool)):
        x, k, _ = check.reference(pool.data(i), pool.c, TOL, MAX_ITERS,
                                  precision="bf16")
        control.append((i, x, k, "ok", True))
    numbers = check.compare(pool, control, TOL, MAX_ITERS)
    assert numbers["obj_dev"] > OBJ_LIMIT and numbers["x_dev"] > X_LIMIT


def test_dense_and_sparse_requests_land_in_separate_slabs(pool):
    """One client, both layouts: two slabs (one per signature), and the
    dense answers are bitwise those of a client serving dense alone."""
    dense = [nesterov_instance(m=30, n=80, nnz_frac=0.1, seed=s)
             for s in range(3)]
    sparse = [pool.problem(i) for i in range(3)]
    mixed = _client(S=2)
    got = _serve(mixed, [p for pair in zip(dense, sparse) for p in pair])
    engine = mixed._backend._eng
    layouts = sorted(spec.layout for spec in engine._slabs)
    assert layouts == ["csc", "dense"]
    alone = _serve(_client(S=2), dense)
    for r_mixed, r_alone in zip(got[0::2], alone):
        np.testing.assert_array_equal(np.asarray(r_mixed.x),
                                      np.asarray(r_alone.x))
        assert r_mixed.iters == r_alone.iters


def test_unequal_designs_of_one_bucket_share_one_slab(pool):
    """The six designs differ in nnz (within ±10%) but share the nnz
    bucket: one slab, one row-writer program for all admissions."""
    nnz = {pool.nnz(i) for i in range(len(pool))}
    assert len(nnz) == len(pool)
    specs = {B.BatchedProblemSpec.of(pool.problem(i))
             for i in range(len(pool))}
    assert len(specs) == 1
    spec, = specs
    assert spec.layout == "csc" and spec.nnz_cap == 4096
    write = B.make_row_writer(spec)
    compiled = write._cache_size()
    client = _client(S=3)               # a slab shape no other test uses
    _serve(client, [pool.problem(i) for i in range(len(pool))])
    assert list(client._backend._eng._slabs) == [spec]
    assert write._cache_size() == compiled + 1


def test_nnz_pad_share_counter_equals_the_hand_count(pool):
    client = _client(S=2)
    _serve(client, [pool.problem(i) for i in range(len(pool))])
    tele = client.telemetry
    stored = sum(pool.nnz(i) for i in range(len(pool)))
    assert tele.nnz_stored == stored
    assert tele.nnz_capacity == 4096 * len(pool)
    share = client.telemetry.snapshot()["sparse"]["nnz_pad_share"]
    assert share == pytest.approx(1.0 - stored / (4096 * len(pool)))


def test_upload_span_carries_nnz_and_capacity(pool):
    tracer = Tracer()
    with tracing(tracer):
        _serve(_client(S=4), [pool.problem(i) for i in range(3)])
    ups = [s for s in tracer.spans if s.name == "serve.upload"]
    assert ups
    assert sum(s.args["nnz"] for s in ups) == sum(pool.nnz(i)
                                                 for i in range(3))
    assert sum(s.args["nnz_cap"] for s in ups) == 3 * 4096
    spec = B.BatchedProblemSpec.of(pool.problem(0))
    row = 8 * 4096 + 4 * (pool.n + 1) + 4 * pool.m
    assert B.shipped_row_bytes(spec) == row
    for s in ups:
        assert s.args["bytes"] > s.args["rows"] * row


def test_sparse_products_carry_their_scopes_in_the_chunk_program(pool):
    spec = B.BatchedProblemSpec.of(pool.problem(0))
    cfg = SolverConfig(tol=TOL, max_iters=MAX_ITERS)
    S = 2
    slab = B.slab_alloc(spec, cfg, S)
    z = jnp.zeros((S,), jnp.float32)
    args = (slab, jnp.ones((S,), bool), jnp.zeros((S,), bool), z,
            jnp.zeros((S, spec.n)), jnp.zeros((S,), jnp.int32),
            jnp.ones((S, spec.n)), z)
    hlo = B.make_chunk_stepper(spec, cfg, 4).lower(*args).compile().as_text()
    scopes = {part for name in re.findall(r'op_name="([^"]+)"', hlo)
              for part in re.sub(r"vmap\((\w+)\)", r"\1", name).split("/")}
    assert {"spmv", "spmv_t", "grad", "objective", "splice"} <= scopes


def test_solo_batch_and_wave_agree_with_the_continuous_engine(pool):
    """The inline solo step and the inline batch program run the same
    vmapped iteration on the same stored layout as the continuous
    engine: at a fixed iteration budget they answer alike."""
    cfg = {"tol": -1.0, "max_iters": 40, "tau_adapt": False}
    probs = [pool.problem(i) for i in range(3)]
    ref = _serve(_client(S=2, **cfg), probs)
    solo = [_client("inline", **cfg).run(SoloSpec(p)) for p in probs]
    batch = _client("inline", **cfg).run(BatchSpec(problems=probs))
    for j, r in enumerate(ref):
        assert r.iters == 40
        for x in (solo[j].x, batch.x[j]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(r.x),
                                       atol=1e-5)


#: Requests submitted before each of the first ticks (pool indices):
#: arrivals that fill the S = 3 slab, queue, and leave it part empty.
SCRIPT = ([0, 1], [], [2, 3, 4], [], [5], [1])


def _scripted_run(pool):
    """SCRIPT through the continuous engine on the Pallas kernel
    (interpreted), traced; the chunk programs are built for this run
    alone.  Returns the engine and the tracer."""
    B.make_chunk_stepper.clear()
    try:
        client, tracer = _client(S=3), Tracer()
        with tracing(tracer):
            for batch in SCRIPT:
                for i in batch:
                    client.submit(SoloSpec(pool.problem(i)))
                client.step()
            while client.pending:
                client.step()
    finally:
        B.make_chunk_stepper.clear()
    return client._backend._eng, tracer


@pytest.fixture(scope="module")
def scripted(pool):
    """The scripted run with the gate, and with the gate forced open
    (the ungated products every slot computed before the gate)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNELS", "interpret")
        gated = _scripted_run(pool)
        mp.setattr(B, "gate_designs",
                   partial(gate_designs, force_open=True))
        opened = _scripted_run(pool)
    return gated, opened


def test_gate_leaves_every_answer_bitwise_as_the_open_gate(scripted):
    (gated, _), (opened, _) = scripted
    assert len(gated._responses) == sum(map(len, SCRIPT))
    assert sorted(gated._responses) == sorted(opened._responses)
    for req_id, got in gated._responses.items():
        want = opened._responses[req_id]
        np.testing.assert_array_equal(got.x, want.x)
        assert (got.iters, got.stat, got.status) == (
            want.iters, want.stat, want.status)


def _gated_by_hand(eng, tick: int, S: int = 3) -> int:
    """Slots that enter tick ``tick``'s chunk stopped, from the audit
    log alone: those not holding a request admitted by then and not
    yet evicted."""
    return S - sum(rec["admit_tick"] <= tick <= rec["evict_tick"]
                   for rec in eng.audit)


def test_gate_counters_equal_the_hand_count(scripted):
    (eng, tracer), _ = scripted
    chunks = [s for s in tracer.spans if s.name == "serve.chunk"]
    assert chunks
    by_hand = [_gated_by_hand(eng, s.args["tick"]) for s in chunks]
    assert [s.args["gated"] for s in chunks] == by_hand
    assert 0 < sum(by_hand) < 3 * len(chunks)
    tele = eng.telemetry
    assert tele.gated_slot_chunks == sum(by_hand)
    assert tele.slot_chunks == 3 * len(chunks)
    sparse = tele.snapshot()["sparse"]
    assert sparse["gate_share"] == sum(by_hand) / (3 * len(chunks))


def test_dense_chunk_spans_and_snapshot_carry_no_gate():
    client = _client(S=2)
    tracer = Tracer()
    with tracing(tracer):
        _serve(client, [nesterov_instance(m=30, n=80, nnz_frac=0.1, seed=s)
                        for s in range(3)])
    chunks = [s for s in tracer.spans if s.name == "serve.chunk"]
    assert chunks and not any("gated" in s.args for s in chunks)
    tele = client.telemetry
    assert tele.gated_slot_chunks == tele.slot_chunks == 0
    assert "sparse" not in tele.snapshot()


def _dense_programs():
    """The dense programs the gate helper is traced through: the chunk
    (splice and iterations; watchdog off and on) and the batched
    run-to-convergence solver, lowered from shapes."""
    from repro.obs.health import HealthConfig
    spec = B.BatchedProblemSpec(m=20, n=48)
    cfg = SolverConfig(tol=1e-4, max_iters=50)
    S, n = 4, spec.n
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    slab = jax.eval_shape(lambda: B.slab_alloc(spec, cfg, S))
    sd = jax.ShapeDtypeStruct
    args = (slab, sd((S,), b), sd((S,), b), sd((S,), f32), sd((S, n), f32),
            sd((S,), i32), sd((S, n), f32), sd((S,), f32))
    carry = (sd((S,), f32), sd((S,), i32))
    health = HealthConfig(stall_window=3)
    data = (sd((S, spec.m, n), f32), sd((S, spec.m), f32))
    return [
        B._build_chunk_stepper(spec, cfg, 4).lower(*args).as_text(),
        B._build_chunk_stepper(spec, cfg, 4, health=health).lower(
            *args, *carry).as_text(),
        B._build_batched_solver(spec, cfg).lower(
            data, sd((S,), f32), sd((S, n), f32)).as_text(),
    ]


def test_dense_programs_lower_as_without_the_gate(monkeypatch):
    with_gate = _dense_programs()
    monkeypatch.setattr(B, "gate_designs", lambda data, live, **_: data)
    assert _dense_programs() == with_gate


def test_mesh_backend_serves_sparse_designs_as_the_continuous_one(pool):
    probs = [pool.problem(i) for i in range(4)]
    ref = _serve(_client(S=2), probs)
    mesh = FlexaClient(backend="mesh",
                       solver=SolverConfig(tol=TOL, max_iters=MAX_ITERS),
                       serve=ServeConfig(slab_capacity=2, chunk_iters=16,
                                         mesh_devices=1))
    tracer = Tracer()
    with tracing(tracer):
        got = _serve(mesh, probs)
    for r, m in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(m.x), np.asarray(r.x))
        assert m.iters == r.iters
    # the gate counters roll up from the device's own count
    eng = mesh._backend._eng
    ticks = [s.args["tick"] for s in tracer.spans if s.name == "serve.chunk"]
    sparse = mesh.telemetry.snapshot()["sparse"]
    assert sparse["gated_slot_chunks"] == sum(
        _gated_by_hand(eng, t, S=2) for t in ticks)
    assert sparse["slot_chunks"] == 2 * len(ticks)


# ------------------------------------------------------------------ #
# Shapes, not copies; what stays out of scope                        #
# ------------------------------------------------------------------ #
def test_dims_and_validation_read_shapes_only(pool, monkeypatch):
    p = pool.problem(0)
    assert _dims(p) == (pool.m, pool.n)

    def refuse(*a, **k):
        raise AssertionError("the design was copied to read its shape")
    dev = make_lasso(jnp.ones((4, 6)), jnp.ones(4), 1.0)
    monkeypatch.setattr(np, "asarray", refuse)
    assert _dims(p) == (pool.m, pool.n)
    assert _dims(dev) == (4, 6)


def _malformed(A, fault):
    rows, ptr = np.array(A.rows), np.array(A.col_ptr)
    if fault == "short_rows":
        return CSCDesign(A.values, rows[:-1], ptr, A.shape)
    if fault == "row_past_m":
        rows[5] = A.m
    elif fault == "negative_row":
        rows[0] = -1
    elif fault == "falling_col_ptr":
        ptr[3], ptr[4] = ptr[4], ptr[3] - 1
    elif fault == "col_ptr_past_capacity":
        ptr[-1] = A.capacity + 1
    elif fault == "col_ptr_not_from_zero":
        ptr[0] = 1
    return CSCDesign(A.values, rows, ptr, A.shape)


@pytest.mark.parametrize("fault", ["short_rows", "row_past_m",
                                   "negative_row", "falling_col_ptr",
                                   "col_ptr_past_capacity",
                                   "col_ptr_not_from_zero"])
def test_malformed_sparse_request_is_rejected_before_device_work(pool,
                                                                 fault):
    """A design whose arrays do not describe an (m, n) design is
    refused at submission (a row out of range or a wrong column pointer
    would otherwise be dropped or misplaced on the device, and the
    answer be wrong)."""
    p = pool.problem(0)
    bad = _malformed(p.data["A"], fault)
    client = _client()
    with pytest.raises(ValueError, match="sparse design|col_ptr|row"):
        client._backend._engine().submit(
            SolveRequest(A=bad, b=np.asarray(p.data["b"]), c=1.0))
    assert not client.pending


def test_sparse_design_is_not_sent_over_the_wire(pool):
    with pytest.raises(ProtocolError, match="sparse design"):
        encode_problem(pool.problem(0))


def test_paths_over_sparse_designs_are_unsupported(pool):
    with pytest.raises(UnsupportedWorkloadError, match="sparse design"):
        _client().submit(PathSpec(problem=pool.problem(0), n_points=4))


def test_sparse_layout_is_refused_for_dense_only_families():
    A = csc_from_dense(np.eye(4, dtype=np.float32))
    with pytest.raises(ValueError, match="dense design"):
        B.BatchedProblemSpec.for_design(A, n=4, block_size=1, g_kind="l1",
                                        family="logreg")


def test_make_lasso_takes_a_sparse_design(pool):
    """The solo constructor keeps a sparse design as given and estimates
    L_F = 2·λmax(AᵀA) by its own products, as the dense path does."""
    A = CSCDesign(pool.values[0], pool.rows[0], pool.col_ptr[0],
                  (pool.m, pool.n))
    b = pool.b[0]
    sparse = make_lasso(A, b, 1.0)
    dense = make_lasso(np.asarray(pool.data(0)[0]), b, 1.0)
    assert sparse.data["A"] is A
    assert sparse.lipschitz == pytest.approx(dense.lipschitz, rel=1e-3)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(pool.n),
                    jnp.float32)
    np.testing.assert_allclose(float(sparse.f(x)), float(dense.f(x)),
                               rtol=1e-5)
