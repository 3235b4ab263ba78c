"""Regularization-path benchmark: cold grids vs warm-started, screened
homotopy (``repro.path``), plus K-fold cross-validation through the
continuous-batching serve engine.

Columns (all solving the SAME ≥20-point geometric λ-grid, same solver
budget, identical final solutions up to the 1e-5 exactness gate):

* ``cold_batched``  — the λ-grid as ONE batched wave
  (``solve_path(lam_batch=P, warm=False, screen=False)``): how the
  pre-path engines solve a known grid.  Device row-iterations =
  P × (slowest point) — the wave freeze-waste pathology from
  ``BENCH_serve.json``, now across λ-heterogeneity (easy big-λ points
  are held hostage by the hard small-λ tail).  This is the baseline the
  acceptance gate compares against.
* ``cold_solo``     — one λ at a time from zeros, Σ iterations (the most
  charitable cold accounting: zero batching waste, but also zero device
  parallelism — it trades all throughput away).
* ``warm``          — sequential homotopy, warm starts only.
* ``warm_screened`` — homotopy + sequential strong rule + KKT recheck
  (the ``repro.path`` default).  Frozen blocks are reported as
  ``active_frac`` — the per-iteration FLOP fraction a column-sparse
  kernel could exploit (the compiled program itself stays dense and
  fixed-shape by design).

A *device row-iteration* is one slab-row advanced one FLEXA iteration —
the deterministic work currency of ``repro.serve.metrics``, immune to
timer noise; wall times are reported alongside but never gated.

A note the numbers force on us: per-point, warm starts do NOT reliably
reduce iterations for this *parallel* method — the warm-start error
x*(λₖ₋₁) − x*(λₖ) points along exactly the flattest (λ-sensitive)
directions of the restricted Hessian, so it decays at the worst-case
rate, while a cold start's error is mostly fast modes.  The homotopy
chain wins on *device work for the whole grid*: it never pays the wave's
P × max freeze waste, and its screening certifies the per-λ active sets
(the FLOP story + exact solutions).  Both cold accountings are reported
so the trade is visible.

The CV scenario sweeps the shared λ-grid per fold two ways: lockstep
(``solve_path_batched`` — one compiled program, all folds per point) and
through ``ContinuousSolverEngine.submit_path`` (K concurrent
PathRequests interleaving in one slab), then picks λ by mean validation
MSE.

Artifact: ``results/bench/BENCH_path.json`` with the ``accept`` block
(≥20-point grid, ≥2× row-iteration ratio vs cold_batched, ≤1e-5 per-λ
deviation vs the cold ``solve_batched`` reference).

Run: ``PYTHONPATH=src python benchmarks/path_bench.py`` (≈ half a
minute); ``--smoke`` is the seconds-scale CI gate (deterministic
criteria only).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.client import CVSpec, FlexaClient, PathSpec
from repro.config.base import ServeConfig, SolverConfig
from repro.problems.lasso import make_lasso, nesterov_instance

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

RATIO_GATE = 2.0          # cold_batched / warm_screened row-iterations
EXACT_GATE = 1e-5         # per-λ max |x_ws − x_cold|


def _col(r, name: str) -> dict:
    return {
        "mode": name,
        "row_iters": int(r.row_iters),
        "iters_per_lambda": [int(i) for i in r.iters],
        "support": [int(s) for s in r.support],
        "active_blocks": [int(a) for a in r.active_blocks],
        "converged": bool(np.all(r.converged)),
        "wall_s": round(float(r.meta["wall_s"]), 4),
    }


def run_path_columns(m: int, n: int, nnz: float, seed: int, P: int,
                     ratio: float, cfg: SolverConfig) -> dict:
    p = nesterov_instance(m=m, n=n, nnz_frac=nnz, c=1.0, seed=seed)
    client = FlexaClient(solver=cfg)
    kw = dict(n_points=P, lam_min_ratio=ratio)
    cold_b = client.run(PathSpec(problem=p, warm=False, screen=False,
                                 lam_batch=P, **kw))
    cold_s = client.run(PathSpec(problem=p, warm=False, screen=False,
                                 **kw))
    warm = client.run(PathSpec(problem=p, warm=True, screen=False, **kw))
    ws = client.run(PathSpec(problem=p, warm=True, screen=True, **kw))

    dev = np.max(np.abs(ws.x - cold_s.x), axis=1)
    dev_cb = float(np.max(np.abs(ws.x - cold_b.x)))
    n_blocks = p.n_blocks
    active_frac = float(np.mean(
        [a / n_blocks for a in ws.active_blocks]))
    ratio_vs_batched = cold_b.row_iters / max(1, ws.row_iters)
    ratio_vs_solo = cold_s.row_iters / max(1, ws.row_iters)
    return {
        "instance": {"m": m, "n": n, "nnz_frac": nnz, "seed": seed,
                     "lam_max": float(ws.lam_max)},
        "grid": {"points": P, "lam_min_ratio": ratio,
                 "lambdas": [float(l) for l in ws.lambdas]},
        "columns": {
            "cold_batched": _col(cold_b, "cold_batched"),
            "cold_solo": _col(cold_s, "cold_solo"),
            "warm": _col(warm, "warm"),
            "warm_screened": {
                **_col(ws, "warm_screened"),
                "screened_out": [r.screened_out for r in ws.screened],
                "kkt_rounds": [r.kkt_rounds for r in ws.screened],
                "kkt_violations": [r.violations for r in ws.screened],
                "active_frac_mean": round(active_frac, 4),
            },
        },
        "equivalence": {
            "max_dev_vs_cold_solo": float(dev.max()),
            "max_dev_vs_cold_batched": dev_cb,
            "dev_per_lambda": [float(d) for d in dev],
        },
        "accept": {
            "grid_points": P,
            "grid_points_ok": P >= 20,
            "row_iters_cold_batched": int(cold_b.row_iters),
            "row_iters_cold_solo": int(cold_s.row_iters),
            "row_iters_warm_screened": int(ws.row_iters),
            "ratio_vs_cold_batched": round(ratio_vs_batched, 3),
            "ratio_vs_cold_solo": round(ratio_vs_solo, 3),
            "ratio_ok": bool(ratio_vs_batched >= RATIO_GATE),
            "max_dev": float(dev.max()),
            "exact_ok": bool(dev.max() <= EXACT_GATE),
        },
    }


# ------------------------------------------------------------------ #
# K-fold cross-validation over the serve engine                      #
# ------------------------------------------------------------------ #
def make_cv_folds(m_total: int, n: int, s: int, K: int, seed: int,
                  noise: float = 0.5):
    """Planted sparse regression split into K row-folds."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m_total, n)).astype(np.float32)
    x_true = np.zeros(n, np.float32)
    sup = rng.choice(n, size=s, replace=False)
    x_true[sup] = rng.uniform(0.5, 1.5, s) * rng.choice([-1, 1], s)
    b = A @ x_true + noise * rng.standard_normal(m_total).astype(
        np.float32)
    # Equal-sized folds (drop the remainder rows): every fold's training
    # matrix then shares ONE shape signature — one slab, one compile.
    idx = rng.permutation(m_total)[:K * (m_total // K)]
    folds = np.array_split(idx, K)
    out = []
    for f in folds:
        val = np.zeros(m_total, bool)
        val[f] = True
        out.append((A[~val], b[~val], A[val], b[val]))
    return out, x_true


def run_cv(m_total: int, n: int, s: int, K: int, P: int, ratio: float,
           seed: int, cfg: SolverConfig, serve: ServeConfig) -> dict:
    folds, _ = make_cv_folds(m_total, n, s, K, seed)
    train_probs = [make_lasso(A, b, c=1.0, name=f"cv_fold{i}")
                   for i, (A, b, _, _) in enumerate(folds)]
    validation = [(Av, bv) for (_, _, Av, bv) in folds]
    spec = CVSpec(problems=train_probs, validation=validation,
                  n_points=P, lam_min_ratio=ratio)

    # Lockstep sweep: one compiled batched program, all folds per point.
    t0 = time.perf_counter()
    cv_lock = FlexaClient(solver=cfg).run(spec)
    lock_wall = time.perf_counter() - t0
    grid = cv_lock.lambdas

    # The same spec through the continuous backend (each fold chains its
    # own warm-started, screened points; the slab interleaves them) —
    # one CVSpec, two schedulers, identical answers.
    serve_client = FlexaClient(backend="continuous", solver=cfg,
                               serve=serve)
    t0 = time.perf_counter()
    cv_serve = serve_client.run(spec)
    serve_wall = time.perf_counter() - t0
    tele = serve_client.telemetry.snapshot()

    dev_serve_vs_lockstep = max(
        float(np.max(np.abs(cv_serve.folds[i].x - cv_lock.folds[i].x)))
        for i in range(K))
    mean_mse = cv_lock.scores_mean
    best = cv_lock.best_index
    assert cv_serve.best_index == best

    return {
        "folds": K, "m_total": m_total, "n": n, "true_support": s,
        "grid_points": len(grid),
        "lambdas": [float(l) for l in grid],
        "val_mse_mean": [round(float(v), 5) for v in mean_mse],
        "best_lambda": float(grid[best]),
        "best_lambda_index": best,
        "lockstep": {
            "sweep_row_iters": int(
                cv_lock.folds[0].meta["sweep_row_iters"]),
            "wall_s": round(lock_wall, 3),
        },
        "serve": {
            "chunk_row_iters": int(tele["continuous"]["row_iters"]),
            "occupancy_mean": round(
                float(tele["continuous"]["occupancy_mean"]), 4),
            "requests": int(tele["requests"]),
            "wall_s": round(serve_wall, 3),
            "max_dev_vs_lockstep": dev_serve_vs_lockstep,
        },
        "serve_matches_lockstep": bool(dev_serve_vs_lockstep <= 1e-4),
    }


def main(m: int = 60, n: int = 256, nnz: float = 0.1, seed: int = 0,
         points: int = 24, lam_min_ratio: float = 0.05,
         max_iters: int = 6000, smoke: bool = False,
         skip_cv: bool = False) -> dict:
    if smoke:
        m, n, points, max_iters = 40, 128, 20, 4000
    # tol 1e-7 / fixed τ: the exactness gate needs honest stationarity
    # (the §4 adaptive controller can inflate τ and stop early — see
    # docs/paths.md); 1e-6 stopping would leave ~1e-5 fp32 gaps.
    cfg = SolverConfig(tol=1e-7, max_iters=max_iters, tau_adapt=False)

    out = {"config": {"m": m, "n": n, "nnz_frac": nnz, "seed": seed,
                      "points": points, "lam_min_ratio": lam_min_ratio,
                      "tol": cfg.tol, "max_iters": max_iters,
                      "smoke": smoke},
           "path": run_path_columns(m, n, nnz, seed, points,
                                    lam_min_ratio, cfg)}
    if not skip_cv:
        Kf, Pcv = (3, 10) if smoke else (4, 16)
        out["cv"] = run_cv(m_total=2 * m, n=n, s=max(4, n // 20), K=Kf,
                           P=Pcv, ratio=0.1, seed=seed, cfg=cfg,
                           serve=ServeConfig(slab_capacity=4,
                                             chunk_iters=50))

    RESULTS.mkdir(parents=True, exist_ok=True)
    artifact = RESULTS / "BENCH_path.json"
    artifact.write_text(json.dumps(out, indent=1))

    acc = out["path"]["accept"]
    print(f"path: P={acc['grid_points']} "
          f"cold_batched={acc['row_iters_cold_batched']} "
          f"cold_solo={acc['row_iters_cold_solo']} "
          f"warm_screened={acc['row_iters_warm_screened']} "
          f"ratio={acc['ratio_vs_cold_batched']}x "
          f"(solo {acc['ratio_vs_cold_solo']}x) "
          f"max_dev={acc['max_dev']:.2e}")
    if "cv" in out:
        cv = out["cv"]
        print(f"cv: {cv['folds']} folds x {cv['grid_points']} pts -> "
              f"best λ={cv['best_lambda']:.4f} "
              f"serve_dev={cv['serve']['max_dev_vs_lockstep']:.1e} "
              f"occupancy={cv['serve']['occupancy_mean']}")
    print(f"wrote {artifact}")

    ok = acc["grid_points_ok"] and acc["ratio_ok"] and acc["exact_ok"]
    if "cv" in out:
        ok = ok and out["cv"]["serve_matches_lockstep"]
    out["accept_ok"] = bool(ok)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--m", type=int, default=60)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--nnz", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points", type=int, default=24)
    ap.add_argument("--lam-min-ratio", type=float, default=0.05)
    ap.add_argument("--max-iters", type=int, default=6000)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI gate (deterministic criteria)")
    ap.add_argument("--skip-cv", action="store_true")
    a = ap.parse_args()
    from repro.launch.runtime import device_banner
    print(device_banner())
    art = main(m=a.m, n=a.n, nnz=a.nnz, seed=a.seed, points=a.points,
               lam_min_ratio=a.lam_min_ratio, max_iters=a.max_iters,
               smoke=a.smoke, skip_cv=a.skip_cv)
    # Gate only at the CLI (the CI smoke step): library callers like
    # benchmarks/run.py read accept_ok from the artifact instead, so an
    # acceptance miss never aborts an aggregate run half-way.
    if not art["accept_ok"]:
        raise SystemExit(
            f"path bench acceptance FAILED: {art['path']['accept']}")
