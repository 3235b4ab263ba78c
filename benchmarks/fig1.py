"""Paper Fig. 1 reproduction: Lasso solver races on Nesterov instances.

Four instance groups exactly as in §4:
  (a) medium size, low sparsity    — n=10000, m=2000, 20% nnz
  (b) medium size, medium sparsity — n=10000, m=2000, 10% nnz
  (c) medium size, high sparsity   — n=10000, m=2000,  5% nnz
  (d) large size, high sparsity    — n=100000, m=5000,  5% nnz

Every algorithm now runs through the client front door
(``repro.client.FlexaClient`` — inline backend, one ``SoloSpec`` per
run), so the race is a single loop over registry method names — same Problem, same iteration/tolerance budget, same
``SolverResult`` contract.  Metric: relative error (V−V*)/V* vs wall time
(V* is exact — planted instances), plus time/iterations to reach
1e-2/1e-4/1e-6.

Artifacts (``results/bench/``):

* ``<group>.json``       — summary rows per (group, seed, algo);
* ``BENCH_solvers.json`` — the full trajectory artifact: for every run the
  per-iteration ``V``/``time`` series (what Fig. 1 actually plots), the
  summary rows, a ``batched`` section measuring the multi-instance
  engine (one compiled program for B instances vs B facade solves —
  the serving amortization the ROADMAP asks for), and a
  ``selection_ablation`` section racing the Step-S.3 rules (greedy vs
  Jacobi vs the arXiv:1407.4504 random/hybrid sketches vs cyclic) to the
  same optimum on the fig1b instance.

The container is a single CPU core (the paper used a 32-core node), so the
default scale divides the instance dimensions by ``--scale`` (8 by default;
``--scale 1`` reproduces the paper's sizes verbatim).  Rankings are
scale-stable — verified by tests at miniature scale.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.client import BatchSpec, FlexaClient, SoloSpec
from repro.config.base import SolverConfig
from repro.problems.lasso import nesterov_instance

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

GROUPS = {
    "fig1a_med_low": dict(m=2000, n=10_000, nnz=0.20, realizations=3),
    "fig1b_med_mid": dict(m=2000, n=10_000, nnz=0.10, realizations=3),
    "fig1c_med_high": dict(m=2000, n=10_000, nnz=0.05, realizations=3),
    "fig1d_large_high": dict(m=5000, n=100_000, nnz=0.05, realizations=1),
}
THRESHOLDS = (1e-2, 1e-4, 1e-6)

# The Fig. 1 field as (label, registry method, method-specific options).
# FPA = the paper's FLEXA configuration (greedy ρ=0.5, exact-block
# surrogate, Eq. (4) step, §4 τ-controller) — all defaults of SolverConfig.
def _field(n_processors: int):
    return [
        ("FPA", "flexa", {}),
        ("FISTA", "fista", {}),
        ("GRock1", "grock", {"P": 1}),
        (f"GRockP{n_processors}", "grock", {"P": n_processors}),
        ("GS", "gauss_seidel", {}),
        ("ADMM", "admm", {"rho": 10.0}),
    ]


def time_to(history_v, history_t, v_star, thr):
    rel = (np.asarray(history_v) - v_star) / v_star
    idx = np.nonzero(rel <= thr)[0]
    if idx.size == 0:
        return None, None
    return history_t[idx[0]], int(idx[0]) + 1


def run_group(name: str, spec: dict, scale: int, max_iters: int,
              n_processors: int = 16):
    """Race the whole field on one instance group.

    Returns (summary rows, trajectory records) — trajectories carry the raw
    per-iteration (V, time) series for the BENCH_solvers.json artifact.
    """
    m = max(50, spec["m"] // scale)
    n = max(200, spec["n"] // scale)
    rows, trajs = [], []
    for seed in range(spec["realizations"]):
        p = nesterov_instance(m=m, n=n, nnz_frac=spec["nnz"], c=1.0,
                              seed=seed)
        for algo, method, options in _field(n_processors):
            # GS iterations are full n-coordinate sweeps — budget fewer.
            iters = max(10, max_iters // 10) if method == "gauss_seidel" \
                else max_iters
            cfg = SolverConfig(max_iters=iters, tol=0)
            t0 = time.perf_counter()
            r = FlexaClient(solver=cfg).run(SoloSpec(
                problem=p, method=method, options=options))
            wall = time.perf_counter() - t0
            rel_final = (r.history["V"][-1] - p.v_star) / p.v_star
            row = {"group": name, "seed": seed, "algo": algo,
                   "method": method, "m": m, "n": n, "iters": r.iters,
                   "wall_s": round(wall, 3),
                   "rel_err_final": float(rel_final)}
            for thr in THRESHOLDS:
                t, it = time_to(r.history["V"], r.history["time"],
                                p.v_star, thr)
                row[f"t_{thr:.0e}"] = None if t is None else round(t, 4)
                row[f"it_{thr:.0e}"] = it
            rows.append(row)
            trajs.append({
                "group": name, "seed": seed, "algo": algo,
                "v_star": p.v_star,
                "V": [float(v) for v in r.history["V"]],
                "time": [round(float(t), 5) for t in r.history["time"]],
            })
    return rows, trajs


def run_batched(scale: int, n_instances: int = 8,
                max_iters: int = 400) -> dict:
    """Multi-instance engine vs a Python loop of facade solves.

    Same B instances, same budget: the sequential path pays per-instance
    dispatch and host-loop stepping; the batched path is one compiled
    vmap + while_loop program (tau_adapt off for cross-driver
    reproducibility — see repro.solvers.batched).
    """
    m = max(40, 2000 // scale // 4)
    n = max(160, 10_000 // scale // 4)
    cfg = SolverConfig(max_iters=max_iters, tol=1e-6, tau_adapt=False)
    probs = [nesterov_instance(m=m, n=n, nnz_frac=0.1, c=1.0, seed=s)
             for s in range(n_instances)]

    client = FlexaClient(solver=cfg)          # inline session
    t0 = time.perf_counter()
    seq = [client.run(SoloSpec(problem=p)) for p in probs]
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    rb = client.run(BatchSpec(problems=probs))   # includes compilation
    t_batched_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    rb = client.run(BatchSpec(problems=probs))   # compiled-program reuse
    t_batched_warm = time.perf_counter() - t0

    max_dx = max(
        float(np.abs(np.asarray(r.x) - np.asarray(rb.x[i])).max())
        for i, r in enumerate(seq))
    return {
        "B": n_instances, "m": m, "n": n,
        "sequential_s": round(t_seq, 3),
        "batched_cold_s": round(t_batched_cold, 3),
        "batched_warm_s": round(t_batched_warm, 3),
        "speedup_warm": round(t_seq / max(t_batched_warm, 1e-9), 2),
        "max_abs_diff_vs_sequential": max_dx,
        "converged": [bool(v) for v in np.asarray(rb.converged)],
    }


SELECTION_RULES = ("greedy", "full", "southwell", "topk", "random",
                   "hybrid", "cyclic")


def run_selection_ablation(scale: int, max_iters: int = 4000,
                           tol: float = 1e-6) -> dict:
    """Race the Step-S.3 selection rules on the fig1b Lasso instance.

    Greedy is the paper's FPA; full is Jacobi; southwell the serial
    extreme; random/hybrid are the arXiv:1407.4504 sketch rules; cyclic
    the essentially-cyclic shuffle.  Same instance, same tolerance: the
    record shows every rule reaching the same planted optimum, with the
    iteration count measuring what the selection quality buys (random
    rules visit blocks blindly, so they trade extra iterations for not
    depending on the error-bound ranking; per-iteration cost is identical
    in this dense implementation — see repro.core.selection).
    """
    m = max(50, 2000 // scale)
    n = max(200, 10_000 // scale)
    p = nesterov_instance(m=m, n=n, nnz_frac=0.10, c=1.0, seed=0)
    rows = []
    for rule in SELECTION_RULES:
        cfg = SolverConfig(max_iters=max_iters, tol=tol, selection=rule,
                           sel_k=max(8, n // 16), sel_p=0.25, seed=0)
        t0 = time.perf_counter()
        r = FlexaClient(solver=cfg).run(SoloSpec(problem=p))
        wall = time.perf_counter() - t0
        rel = (r.history["V"][-1] - p.v_star) / p.v_star
        rows.append({
            "selection": rule, "iters": r.iters,
            "converged": bool(r.converged),
            "rel_err_final": float(rel),
            "wall_s": round(wall, 3),
            "mean_sel_frac": float(np.mean(r.history["sel_frac"])),
            "V": [float(v) for v in r.history["V"]],
        })
    return {"group": "fig1b_med_mid", "m": m, "n": n, "nnz": 0.10,
            "max_iters": max_iters, "tol": tol, "rows": rows}


def main(scale: int = 8, max_iters: int = 500, groups=None,
         with_batched: bool = True, with_selection: bool = True
         ) -> list[dict]:
    RESULTS.mkdir(parents=True, exist_ok=True)
    all_rows, all_trajs = [], []
    for name, spec in GROUPS.items():
        if groups and name not in groups:
            continue
        rows, trajs = run_group(name, spec, scale, max_iters)
        all_rows.extend(rows)
        all_trajs.extend(trajs)
        (RESULTS / f"{name}.json").write_text(json.dumps(rows, indent=2))

    artifact = {"scale": scale, "max_iters": max_iters,
                "summary": all_rows, "trajectories": all_trajs}
    if with_batched:
        artifact["batched"] = run_batched(scale)
    if with_selection:
        artifact["selection_ablation"] = run_selection_ablation(scale)
    (RESULTS / "BENCH_solvers.json").write_text(
        json.dumps(artifact, indent=2))
    return all_rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--max-iters", type=int, default=500)
    ap.add_argument("--no-batched", action="store_true",
                    help="skip the multi-instance engine measurement")
    ap.add_argument("--no-selection", action="store_true",
                    help="skip the selection-rule ablation")
    args = ap.parse_args()
    from repro.launch.runtime import device_banner
    print(device_banner())
    for row in main(scale=args.scale, max_iters=args.max_iters,
                    with_batched=not args.no_batched,
                    with_selection=not args.no_selection):
        print(row)
