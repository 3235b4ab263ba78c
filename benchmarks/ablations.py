"""Ablations over Algorithm 1's flexibility knobs (paper §3/§4 features).

* ``rho``     — selection greediness: ρ ∈ {0.1, 0.5, 0.9} vs full Jacobi.
  (Paper finding: greedy subsets beat updating everything.)
* ``tau``     — the §4 τ controller on/off.
* ``inexact`` — exact vs inexact (inner prox-gradient) subproblem solves on
  group Lasso (Theorem 1(v) feature).
* ``surrogate`` — linear (5) vs exact-block (6) P_i.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.client import FlexaClient, SoloSpec
from repro.config.base import SolverConfig
from repro.problems.group_lasso import nesterov_group_instance
from repro.problems.lasso import nesterov_instance

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

#: --smoke divides the instance dimensions / iteration budgets so the
#: whole ablation table runs in seconds on CI (rankings, not numbers).
SMOKE_DIV = 8


def _run(problem, cfg: SolverConfig) -> dict:
    """One client solo solve, timed; rel err needs the planted V*."""
    t0 = time.perf_counter()
    r = FlexaClient(solver=cfg).run(SoloSpec(problem=problem))
    wall = time.perf_counter() - t0
    rel = (r.history["V"][-1] - problem.v_star) / problem.v_star \
        if problem.v_star else None
    return {"iters": r.iters, "wall_s": round(wall, 3),
            "rel_err": None if rel is None else float(rel),
            "sel_frac_mean": float(np.mean(r.history["sel_frac"]))}


def ablate_rho(max_iters=400, div=1) -> list[dict]:
    p = nesterov_instance(m=400 // div, n=2000 // div, nnz_frac=0.1,
                          c=1.0, seed=0)
    rows = []
    for rho in (0.1, 0.5, 0.9):
        rows.append({"variant": f"greedy rho={rho}",
                     **_run(p, SolverConfig(max_iters=max_iters, tol=0,
                                            rho=rho))})
    rows.append({"variant": "full jacobi",
                 **_run(p, SolverConfig(max_iters=max_iters, tol=0,
                                        jacobi=True))})
    return rows


def ablate_tau(max_iters=400, div=1) -> list[dict]:
    p = nesterov_instance(m=400 // div, n=2000 // div, nnz_frac=0.1,
                          c=1.0, seed=0)
    return [
        {"variant": "tau adaptive (paper §4)",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0))},
        {"variant": "tau fixed",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0,
                                tau_adapt=False))},
    ]


def ablate_inexact(max_iters=600, div=1) -> list[dict]:
    p = nesterov_group_instance(m=200 // div, n_blocks=160 // div,
                                block_size=5,
                                nnz_frac=0.15, c=1.0, seed=0)
    return [
        {"variant": "exact subproblems",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0))},
        {"variant": "inexact (Thm 1(v) inner prox-grad)",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0,
                                surrogate="newton_cg",
                                inexact_alpha1=0.5))},
    ]


def ablate_surrogate(max_iters=400, div=1) -> list[dict]:
    p = nesterov_instance(m=400 // div, n=2000 // div, nnz_frac=0.1,
                          c=1.0, seed=0)
    return [
        {"variant": "exact_block (choice (6))",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0))},
        {"variant": "linear (choice (5))",
         **_run(p, SolverConfig(max_iters=max_iters, tol=0,
                                surrogate="linear"))},
    ]


def main(smoke: bool = False) -> dict:
    RESULTS.mkdir(parents=True, exist_ok=True)
    div = SMOKE_DIV if smoke else 1
    iters = (lambda n: max(50, n // (4 if smoke else 1)))
    out = {
        "rho": ablate_rho(iters(400), div),
        "tau": ablate_tau(iters(400), div),
        "inexact": ablate_inexact(iters(600), div),
        "surrogate": ablate_surrogate(iters(400), div),
    }
    (RESULTS / "ablations.json").write_text(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    from repro.launch.runtime import device_banner
    print(device_banner())
    for k, rows in main().items():
        print(f"== {k}")
        for r in rows:
            print("  ", r)
