"""Remote solver-service smoke: the wire adds no error and drains clean.

The seconds-scale CI gate for ``repro.remote``: the server runs on a
loopback port in this process (``InProcessServer`` — one process holds
the device, so the server and the inline reference share it), every
workload kind (solo, batch, path, CV) × two problem families runs
through ``FlexaClient(backend="remote")``, and each answer is diffed
against the inline reference — deterministic criteria only, the same
1e-5 envelope the in-process backend matrix gates on.  The run ends
with a graceful-drain check: a drain (the SIGTERM path) with the last
ticket in flight must complete that ticket, flush a schema-versioned
telemetry snapshot, print ``DRAINED`` and exit 0.  The subprocess
server and its signal handling are covered by ``tests/test_remote.py``.

Artifact: ``results/bench/BENCH_remote.json`` — the kind × family
deviation matrix plus the drain record.

Run: ``PYTHONPATH=src python benchmarks/remote_smoke.py`` (≈30 s).
"""
from __future__ import annotations

import contextlib
import io
import json
import tempfile
from pathlib import Path

import numpy as np

from repro.client import (BatchSpec, CVSpec, ClientConfig, FlexaClient,
                          PathSpec, SoloSpec)
from repro.config.base import SolverConfig
from repro.problems.lasso import nesterov_instance
from repro.problems.logreg import random_logreg_instance
from repro.remote.server import InProcessServer

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

TOL = 1e-5
#: The fixed-τ calibration the in-process equivalence matrix uses.
CFG = SolverConfig(tol=1e-7, max_iters=4000, tau_adapt=False)
SERVER_ARGS = ["--tol", "1e-7", "--max-iters", "4000", "--no-tau-adapt"]

FAMILIES = ("lasso", "group_lasso")


def _instance(family: str, seed: int):
    if family == "lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed)
    if family == "group_lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed, block_size=4)
    return random_logreg_instance(m=24, n=48, nnz_frac=0.15, c=0.5,
                                  seed=seed)


def _specs(family: str) -> dict:
    grid = dict(n_points=4, lam_min_ratio=0.1)
    folds = [_instance(family, s) for s in range(2)]
    val = [(np.asarray(_instance(family, 7 + s).data["A"]),
            np.asarray(_instance(family, 7 + s).data["b"]))
           for s in range(2)]
    return {
        "solo": SoloSpec(problem=_instance(family, 0)),
        "batch": BatchSpec(problems=[_instance(family, s)
                                     for s in range(3)]),
        "path": PathSpec(problem=_instance(family, 0), **grid),
        "cv": CVSpec(problems=folds, validation=val, **grid),
    }


def _x_of(kind: str, result) -> np.ndarray:
    if kind == "cv":
        return np.stack([np.asarray(f.x) for f in result.folds])
    return np.asarray(result.x)


def main() -> dict:
    snap_file = Path(tempfile.mkdtemp()) / "drain_snapshot.json"
    server_out = io.StringIO()          # READY / DRAINED handshake lines
    with contextlib.redirect_stdout(server_out):
        server = InProcessServer([*SERVER_ARGS,
                                  "--telemetry-out", str(snap_file)])
    url = server.url
    matrix: dict[str, dict] = {}
    ok = True
    try:
        inline = FlexaClient(backend="inline", solver=CFG)
        remote = FlexaClient(config=ClientConfig(
            backend="remote", remote_url=url, remote_tenant="bench",
            solver=CFG))
        for family in FAMILIES:
            matrix[family] = {}
            for kind, spec in _specs(family).items():
                ref = inline.run(spec)
                got = remote.run(spec)
                dev = float(np.abs(_x_of(kind, got)
                                   - _x_of(kind, ref)).max())
                cell = {"max_dev_vs_inline": dev, "dev_ok": dev <= TOL}
                if kind == "cv":
                    same = got.best_index == ref.best_index
                    cell["selection_ok"] = bool(same)
                    ok &= same
                ok &= cell["dev_ok"]
                matrix[family][kind] = cell
                print(f"[remote/{family:>11}] {kind:<5} dev={dev:.2e} "
                      f"ok={cell['dev_ok']}")

        # Graceful drain with a ticket in flight — the ticket completes,
        # telemetry flushes, DRAINED prints, exit code 0.
        t = remote.submit(SoloSpec(problem=_instance("lasso", 3)))
        with contextlib.redirect_stdout(server_out):
            server.begin_drain()
            drained_res = remote.result(t)
            exit_code = server.join(timeout_s=120)
        snap = json.loads(snap_file.read_text())
        drain = {
            "inflight_completed": bool(drained_res.converged),
            "exit_code": exit_code,
            "drained_printed": "DRAINED" in server_out.getvalue(),
            "snapshot_schema": snap.get("schema"),
            "completed": snap.get("telemetry", {}).get("completed"),
        }
        drain_ok = (drain["inflight_completed"]
                    and drain["exit_code"] == 0
                    and drain["drained_printed"]
                    and drain["snapshot_schema"] == 1)
        drain["ok"] = bool(drain_ok)
        ok &= drain_ok
        print(f"[remote/drain] completed={drain['completed']} "
              f"exit={drain['exit_code']} ok={drain['ok']}")
    finally:
        if server.exit_code is None:
            server.begin_drain()
            server.join(timeout_s=120)

    cells = [c for fam in matrix.values() for c in fam.values()]
    artifact = {
        "tolerance": TOL,
        "matrix": matrix,
        "drain": drain,
        "accept": {
            "max_dev": max(c["max_dev_vs_inline"] for c in cells),
            "cells_ok": sum(1 for c in cells if c["dev_ok"]),
            "cells": len(cells),
        },
        "ok": bool(ok),
        "solver_cfg": {"tol": CFG.tol, "max_iters": CFG.max_iters,
                       "tau_adapt": CFG.tau_adapt},
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS / "BENCH_remote.json"
    out_path.write_text(json.dumps(artifact, indent=2))
    print(f"wrote {out_path}")
    return artifact


if __name__ == "__main__":
    art = main()
    if not art["ok"]:
        raise SystemExit(
            f"remote smoke FAILED: {json.dumps(art['matrix'])}")
