"""Sparse designs on the main path: the column-compressed layout's
products, its slabs, and requests served through the client.

Small sizes throughout (m = 300, n = 1,000, about 0.8% dense, Zipf
column counts), made by the benchmark's own generator
(``bench/gen/text_sparse.py``), whose planted optimum is checked here
too.
"""
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.client import BatchSpec, FlexaClient, PathSpec, SoloSpec
from repro.client.backends import _dims
from repro.client.errors import UnsupportedWorkloadError
from repro.config.base import ServeConfig, SolverConfig
from repro.obs import Tracer, tracing
from repro.problems.lasso import make_lasso, nesterov_instance
from repro.kernels.spmv import BLOCK, TILE
from repro.problems.sparse import (CSCDesign, block_layout, capacity_bucket,
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


def test_mesh_backend_serves_sparse_designs_as_the_continuous_one(pool):
    probs = [pool.problem(i) for i in range(4)]
    ref = _serve(_client(S=2), probs)
    mesh = FlexaClient(backend="mesh",
                       solver=SolverConfig(tol=TOL, max_iters=MAX_ITERS),
                       serve=ServeConfig(slab_capacity=2, chunk_iters=16,
                                         mesh_devices=1))
    for r, m in zip(ref, _serve(mesh, probs)):
        np.testing.assert_array_equal(np.asarray(m.x), np.asarray(r.x))
        assert m.iters == r.iters


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
