"""Client front-door smoke: every workload kind on every backend.

The seconds-scale CI gate for ``repro.client``: one tiny spec of each
workload kind (solo, batch, path, CV) runs through each registered
backend (inline, continuous, mesh), and every backend's answer is
checked against the inline reference on *deterministic* criteria only —
max |Δx| within the stack's 1e-5 tol-stopping envelope (bitwise is
asserted nowhere here; that is the test suite's job) plus convergence
and λ-selection agreement.  No wall-clock comparisons: this step exists
so the client wiring and the engine adapters can't rot, not to measure
anything.

Artifact: ``results/bench/BENCH_client.json`` — the full kind × backend
deviation matrix.

Run: ``PYTHONPATH=src python benchmarks/client_smoke.py`` (≈15 s).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.client import (BatchSpec, CVSpec, FlexaClient, PathSpec,
                          SoloSpec, available_backends)
from repro.config.base import ServeConfig, SolverConfig
from repro.problems.lasso import make_lasso, nesterov_instance

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

TOL = 1e-5
CFG = SolverConfig(tol=1e-7, max_iters=3000, tau_adapt=False)
SERVE = ServeConfig(slab_capacity=4, chunk_iters=50)


def _specs() -> dict:
    solo = nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0, seed=0)
    batch = [nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0, seed=s)
             for s in range(3)]
    rng = np.random.default_rng(0)
    x_true = np.zeros(48, np.float32)
    x_true[rng.choice(48, 5, replace=False)] = 1.0
    folds, val = [], []
    for i in range(2):
        A = rng.standard_normal((24, 48)).astype(np.float32)
        Av = rng.standard_normal((12, 48)).astype(np.float32)
        folds.append(make_lasso(
            A, A @ x_true + 0.3 * rng.standard_normal(24).astype(
                np.float32), c=1.0, name=f"smoke_f{i}"))
        val.append((Av, Av @ x_true))
    return {
        "solo": SoloSpec(problem=solo),
        "batch": BatchSpec(problems=batch),
        "path": PathSpec(problem=solo, n_points=4, lam_min_ratio=0.1),
        "cv": CVSpec(problems=folds, validation=val, n_points=4,
                     lam_min_ratio=0.1),
    }


def _x_of(kind: str, result) -> np.ndarray:
    if kind == "cv":
        return np.stack([f.x for f in result.folds])
    return np.asarray(result.x)


def main() -> dict:
    specs = _specs()
    matrix: dict[str, dict] = {k: {} for k in specs}
    refs = {}
    ok = True
    # Inline first: it is the reference the serve backends diff against.
    # "remote" needs a live server process — benchmarks/remote_smoke.py
    # owns that matrix.
    backends = ["inline"] + [b for b in available_backends()
                             if b not in ("inline", "remote")]
    for backend in backends:
        client = FlexaClient(backend=backend, solver=CFG, serve=SERVE)
        for kind, spec in specs.items():
            result = client.run(spec)
            cell = {"converged": True}
            if kind in ("solo", "batch"):
                cell["converged"] = bool(
                    np.asarray(result.converged).all())
            if backend == "inline":
                refs[kind] = result
                cell["max_dev_vs_inline"] = 0.0
            else:
                dev = float(np.abs(_x_of(kind, result)
                                   - _x_of(kind, refs[kind])).max())
                cell["max_dev_vs_inline"] = dev
                cell["dev_ok"] = dev <= TOL
                ok &= cell["dev_ok"]
            if kind == "cv":
                cell["best_index"] = result.best_index
                same = result.best_index == refs["cv"].best_index
                cell["selection_ok"] = bool(same)
                ok &= same
            ok &= cell["converged"]
            matrix[kind][backend] = cell
            print(f"[{backend:>10}] {kind:<5} "
                  f"dev={cell['max_dev_vs_inline']:.2e} "
                  f"converged={cell['converged']}")

    artifact = {"tolerance": TOL, "matrix": matrix, "ok": bool(ok),
                "backends": list(available_backends()),
                "solver_cfg": {"tol": CFG.tol, "max_iters": CFG.max_iters,
                               "tau_adapt": CFG.tau_adapt}}
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_client.json"
    out.write_text(json.dumps(artifact, indent=2))
    print(f"wrote {out}")
    return artifact


if __name__ == "__main__":
    art = main()
    if not art["ok"]:
        raise SystemExit(f"client smoke FAILED: {json.dumps(art['matrix'])}")
