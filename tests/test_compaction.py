"""Differential harness for compacted active-set execution.

The freeze mask zeroes a screened block's update but still burns its
FLOPs: every masked-dense KKT round multiplies the full (m, n) design.
``PathSpec(compact=True)`` instead gathers the certified active blocks
into a dense tile layout sized to a power-of-two *capacity bucket*
(``repro.solvers.compaction``), so the device program width tracks the
support — and the compile cache stays bounded by the bucket count, not
the support history.

This module is the acceptance instrument for that machinery:

* **pack/unpack properties** (hypothesis-optional, fixed-grid fallback):
  round-trip identity, stable ascending ordering under ties, bucket
  choice monotone in the active count, and gradient-masking equivalence
  — a compacted solve on a random support equals the masked-dense solve;
* **differential path replays**: every scenario runs compact-vs-dense
  with ≤1e-5 per-λ agreement, identical supports, strictly fewer device
  FLOPs, and program widths bounded by the bucket count;
* **bucket-transition determinism**: two identical compacted runs are
  bitwise equal (per-λ), including across capacity-bucket transitions;
* **serve replay**: the continuous engine with ``compact_drain`` on
  serves the same trace to the same answers (≤1e-5) with every request
  served exactly once;
* a **golden fixed-seed compacted trajectory** mirroring
  ``tests/golden/path_lasso_V.json`` — regenerate intentionally with:

      PYTHONPATH=src python tests/test_compaction.py --regen
"""
import json
import math
from pathlib import Path

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False

from repro.client import FlexaClient, PathSpec, UnsupportedWorkloadError
from repro.config.base import ServeConfig, SolverConfig
from repro.problems.lasso import nesterov_instance
from repro.solvers.compaction import bucket_capacity, make_plan
import repro.solvers.batched as B

GOLDEN_DIR = Path(__file__).resolve().parent / "golden"
GOLDEN = GOLDEN_DIR / "path_lasso_compact_V.json"

#: Same instance/budget family as tests/test_path.py: fixed τ, tol 1e-7
#: (honest stationarity at stopping) so the 1e-5 gates have margin.
INSTANCE = dict(m=30, n=96, nnz_frac=0.1, c=1.0, seed=0)
CFG = SolverConfig(tol=1e-7, max_iters=4000, tau_adapt=False)
GRID = dict(n_points=10, lam_min_ratio=0.05)


def _path(problem, *, compact, cfg=CFG, **grid):
    grid = {**GRID, **grid}
    return FlexaClient(solver=cfg).run(PathSpec(
        problem=problem, warm=True, screen=True, compact=compact, **grid))


# ------------------------------------------------------------------ #
# Pack/unpack properties                                             #
# ------------------------------------------------------------------ #
#: Fixed fallback supports: empty, singleton, ties at both ends, dense.
MASK_CASES = [
    np.zeros(16, bool),
    np.eye(16, dtype=bool)[3],
    np.array([1, 1, 0, 0] * 4, bool),
    np.ones(16, bool),
    np.array([0] * 15 + [1], bool),
]


def _masks():
    if HAVE_HYPOTHESIS:
        strategies = given(
            st.lists(st.booleans(), min_size=1, max_size=40)
            .map(lambda bs: np.asarray(bs, bool)))
        return lambda f: settings(max_examples=60, deadline=None)(
            strategies(f))
    return pytest.mark.parametrize("mask", MASK_CASES)


@_masks()
def test_pack_unpack_roundtrip(mask):
    """unpack(pack(x)) restores every active block exactly and leaves
    inactive blocks at the scatter base."""
    bs = 4
    n_blocks = mask.size
    rng = np.random.default_rng(n_blocks)
    x = rng.standard_normal(n_blocks * bs).astype(np.float32)
    base = rng.standard_normal(n_blocks * bs).astype(np.float32)
    plan = make_plan(mask, bs)
    out = np.asarray(plan.unpack_vector(plan.pack_vector(x), base,
                                        force="ref"), np.float32)
    coord = np.repeat(mask, bs)
    np.testing.assert_array_equal(out[coord], x[coord])
    np.testing.assert_array_equal(out[~coord], base[~coord])
    # default base is zeros
    out0 = np.asarray(plan.unpack_vector(plan.pack_vector(x),
                                         force="ref"))
    np.testing.assert_array_equal(out0[~coord], 0.0)


@pytest.mark.parametrize("mask", MASK_CASES)
def test_pack_ordering_stable_under_ties(mask):
    """Packed block order is the ascending original order — no
    permutation freedom, so a repack at the same support is bitwise
    reproducible."""
    plan = make_plan(mask, 4)
    k = int(mask.sum())
    idx = np.asarray(plan.block_idx)
    np.testing.assert_array_equal(idx[:k], np.flatnonzero(mask))
    assert np.all(idx[k:] == -1)
    inv = np.asarray(plan.inverse)
    assert np.all(inv[~mask] == -1)
    np.testing.assert_array_equal(inv[mask], np.arange(k))


def test_bucket_capacity_monotone_and_bounded():
    """Bucket choice is monotone in the active count, a power of two,
    ≥ the count, and capped at n_blocks (the dense fallback)."""
    n_blocks = 16
    caps = [bucket_capacity(c, n_blocks) for c in range(n_blocks + 5)]
    assert caps == sorted(caps)                      # monotone
    for count, cap in enumerate(caps):
        assert cap >= max(count if count <= n_blocks else n_blocks, 1)
        assert cap <= n_blocks
        assert cap & (cap - 1) == 0                  # power of two
    assert bucket_capacity(0, n_blocks) == 1
    assert bucket_capacity(n_blocks, n_blocks) == n_blocks
    # at most log2(n_blocks)+1 distinct buckets ever exist
    assert len(set(caps)) <= int(math.log2(n_blocks)) + 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gradient_masking_equivalence_random_support(seed):
    """A compacted solve on a random certified support equals the
    masked-dense solve on the full program — the foundational identity
    the path driver's per-round repack relies on."""
    from repro.problems.families import build_problem, get_family

    p = nesterov_instance(m=24, n=64, nnz_frac=0.2, c=0.35, seed=seed)
    bs, n = p.block_size, p.n
    n_blocks = n // bs
    rng = np.random.default_rng(seed)
    mask = rng.uniform(size=n_blocks) < 0.4
    mask[rng.integers(n_blocks)] = True              # never empty
    coord = np.repeat(mask, bs).astype(np.float32)
    # Pin τ to one positive scalar so both programs run the identical
    # per-coordinate stepsize (the driver does the same via tau0_pin).
    cfg = SolverConfig(tol=1e-8, max_iters=4000, tau_adapt=False,
                       tau0=0.5)
    dense = B._solve_batched([p], cfg=cfg,
                             active=coord[None, :])
    plan = make_plan(mask, bs)
    fam = get_family("lasso")
    A = np.asarray(p.data["A"], np.float32)
    Ac = np.asarray(plan.pack_columns(A, force="ref"), np.float32)
    pc = build_problem("lasso", [Ac, np.asarray(p.data["b"], np.float32)],
                       float(p.g_weight), n=plan.n_compact,
                       block_size=bs, g_kind=p.g_kind)
    comp = B._solve_batched(
        [pc], cfg=cfg,
        active=np.asarray(plan.pack_mask(coord), np.float32)[None, :])
    x_back = np.asarray(plan.unpack_vector(comp.x[0], force="ref"))
    np.testing.assert_allclose(x_back, np.asarray(dense.x[0]), atol=1e-5)


# ------------------------------------------------------------------ #
# Differential path replays                                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compact_path_matches_dense(seed):
    """The compacted path equals the masked-dense path ≤1e-5 per λ with
    identical supports, strictly fewer device FLOPs, and program widths
    bounded by the bucket count."""
    p = nesterov_instance(**{**INSTANCE, "seed": seed})
    dense = _path(p, compact=False)
    comp = _path(p, compact=True)
    np.testing.assert_allclose(comp.x, dense.x, atol=1e-5)
    np.testing.assert_array_equal(comp.support, dense.support)
    assert np.all(comp.converged)
    assert comp.meta["compact"] and not dense.meta["compact"]
    # FLOP accounting: compaction must shrink the matvec currency
    assert 0 < comp.device_flops < dense.device_flops
    # every executed program width is a bucket (power-of-two blocks,
    # coordinates = blocks × block_size), and the number of distinct
    # widths — the compile-cache footprint — is bounded by the bucket
    # count log2(n_blocks)+1
    bs = p.block_size
    n_blocks = p.n // bs
    widths = comp.meta["program_widths"]
    for w in widths:
        blocks = w // bs
        assert w % bs == 0 and blocks & (blocks - 1) == 0
    assert len(widths) <= int(math.log2(n_blocks)) + 1
    assert dense.meta["program_widths"] == [p.n]


def test_compact_path_bitwise_deterministic_across_buckets():
    """Two identical compacted runs are per-λ bitwise equal — including
    across capacity-bucket transitions (the repack order is pinned, the
    per-bucket programs are pure functions of the packed operands)."""
    p = nesterov_instance(**INSTANCE)
    a = _path(p, compact=True)
    b = _path(p, compact=True)
    np.testing.assert_array_equal(a.x, b.x)
    assert a.device_flops == b.device_flops
    assert a.meta["program_widths"] == b.meta["program_widths"]
    # the scenario actually exercises >1 bucket, else vacuous
    assert len(a.meta["program_widths"]) > 1


def test_compact_requires_screening():
    p = nesterov_instance(**INSTANCE)
    with pytest.raises(Exception, match="screen"):
        FlexaClient(solver=CFG).run(PathSpec(
            problem=p, screen=False, compact=True, **GRID))


def test_compact_rejected_by_serving_backends():
    """Compaction is an inline-path feature; the serve engines compact
    at the slab level (ServeConfig.compact_drain) instead."""
    p = nesterov_instance(**INSTANCE)
    client = FlexaClient(solver=CFG, backend="continuous",
                         serve=ServeConfig(slab_capacity=4,
                                           chunk_iters=16))
    with pytest.raises(UnsupportedWorkloadError, match="compact"):
        client.run(PathSpec(problem=p, compact=True, **GRID))


def test_compact_lam_batched_matches_dense():
    """λ-chunked compacted sweep (union support per chunk) still meets
    the 1e-5 gate against the plain dense path."""
    p = nesterov_instance(**INSTANCE)

    def chunked(compact):
        return FlexaClient(solver=CFG).run(PathSpec(
            problem=p, warm=True, screen=True, compact=compact,
            lam_batch=4, **GRID))

    dense = chunked(False)
    comp = chunked(True)
    np.testing.assert_allclose(comp.x, _path(p, compact=False).x,
                               atol=1e-5)
    # apples-to-apples at the same λ-chunking, packing the chunk's
    # union support must still shrink the matvec currency
    assert 0 < comp.device_flops < dense.device_flops


# ------------------------------------------------------------------ #
# Serve replay (drain-tail slab compaction)                          #
# ------------------------------------------------------------------ #
def test_serve_replay_compact_drain_matches_dense():
    """Same trace through the continuous engine with compact_drain
    on/off: answers agree ≤1e-5 and each request is served exactly once
    (the slab-level mirror of the path differential)."""
    from collections import Counter

    from repro.serve import ContinuousSolverEngine
    from repro.serve.engine import SolveRequest

    probs = [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
             for s in range(6)]
    cfg = SolverConfig(max_iters=4000, tol=1e-7, seed=0)

    def run(compact):
        eng = ContinuousSolverEngine(cfg, ServeConfig(
            slab_capacity=8, chunk_iters=8, compact_drain=compact))
        ids = [eng.submit(SolveRequest(
            A=np.asarray(p.data["A"]), b=np.asarray(p.data["b"]),
            c=float(p.g_weight), block_size=p.block_size))
            for p in probs]
        return eng, ids, eng.drain()

    e0, ids0, r0 = run(False)
    e1, ids1, r1 = run(True)
    assert e0.telemetry.migrations == 0
    assert e1.telemetry.migrations >= 1          # tail actually shrank
    for i0, i1 in zip(ids0, ids1):
        np.testing.assert_allclose(r1[i1].x, r0[i0].x, atol=1e-5)
    counts = Counter(rec["req_id"] for rec in e1.audit)
    assert sorted(counts) == sorted(ids1)
    assert all(v == 1 for v in counts.values())


# ------------------------------------------------------------------ #
# Golden fixed-seed compacted trajectory                             #
# ------------------------------------------------------------------ #
GOLDEN_RTOL = 5e-4           # same rationale as tests/test_path.py


def _golden_record(r):
    return {
        "instance": INSTANCE,
        "grid": GRID,
        "cfg": {"tol": CFG.tol, "max_iters": CFG.max_iters,
                "tau_adapt": CFG.tau_adapt},
        "lam_max": float(r.lam_max),
        "lambdas": [float(l) for l in r.lambdas],
        "V": [float(v) for v in r.V],
        "support": [int(s) for s in r.support],
        "program_widths": list(r.meta["program_widths"]),
        "device_flops": int(r.device_flops),
    }


def test_compact_trajectory_matches_golden():
    assert GOLDEN.exists(), (
        f"golden file {GOLDEN} missing — regenerate with "
        "`PYTHONPATH=src python tests/test_compaction.py --regen`")
    gold = json.loads(GOLDEN.read_text())
    assert gold["instance"] == INSTANCE and gold["grid"] == GRID, \
        "golden file was generated for a different instance/grid"
    r = _path(nesterov_instance(**INSTANCE), compact=True)
    assert gold["lam_max"] == pytest.approx(r.lam_max, rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(r.V), np.asarray(gold["V"]), rtol=GOLDEN_RTOL,
        err_msg="compacted per-λ objective trajectory drifted from "
                "tests/golden — if the compaction math changed "
                "intentionally, regenerate (see module docstring)")
    assert gold["support"] == [int(s) for s in r.support]
    # bucket schedule is part of the pinned behavior: a drift means the
    # capacity policy (not just the math) changed
    assert gold["program_widths"] == list(r.meta["program_widths"])


def regenerate() -> None:
    r = _path(nesterov_instance(**INSTANCE), compact=True)
    GOLDEN_DIR.mkdir(parents=True, exist_ok=True)
    GOLDEN.write_text(json.dumps(_golden_record(r), indent=1))
    print(f"wrote {GOLDEN} ({r.n_points} points, "
          f"widths {r.meta['program_widths']}, "
          f"flops {r.device_flops})")


if __name__ == "__main__":
    import sys
    if "--regen" in sys.argv:
        regenerate()
    else:
        print(__doc__)
