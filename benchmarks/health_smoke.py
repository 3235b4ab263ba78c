"""Numerical-health watchdog smoke gates (``BENCH_health.json``).

Drives ``ContinuousSolverEngine`` with the watchdog enabled and two
fault injections, gating the quarantine contract deterministically
(seconds-scale, CI-safe — no wall-clock compares):

* **NaN injection**: one request warm-started from an all-NaN ``x0``
  among healthy neighbours must be quarantined with status
  ``"diverged"`` on its first chunk (``evict_tick − admit_tick ≤ 1``),
  while every healthy neighbour completes ``"ok"`` and converged.
* **Stall injection**: a run with ``gamma0=0`` and ``tau_adapt=False``
  makes the FLEXA damping identically zero, so the ‖x̂−x‖∞ stat never
  decreases; the watchdog must evict with status ``"stalled"`` within
  ``stall_patience + 1`` chunks of admission.
* **Exactly-once audit**: every request — quarantined or healthy —
  closes exactly one audit record, with the verdict recorded on it.
* **Determinism**: replaying each scenario yields bit-identical
  solutions, iteration counts and audit tick numbers.
* **Conservation**: telemetry quarantine counters equal the engine's
  typed ``SolveFailure`` list, split by status.
"""
import argparse
import json
import sys
import warnings
from collections import Counter
from pathlib import Path

import numpy as np

# Allow `python benchmarks/health_smoke.py` (repo root not on sys.path).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from repro.client.specs import solve_request_of
from repro.config.base import ServeConfig, SolverConfig
from repro.obs.health import bitwise_equal
from repro.problems.lasso import nesterov_instance
from repro.serve.continuous import ContinuousSolverEngine

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"


def _run(cfg, serve, requests):
    """Drain one engine; returns (responses, audit, failures, snapshot)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # legacy-API notice
        eng = ContinuousSolverEngine(cfg, serve)
    ids = [eng.submit(r) for r in requests]
    resps = eng.drain()
    return ([resps[i] for i in ids], eng.audit, list(eng.failures),
            eng.telemetry.snapshot())


def _audit_ok(audit, n_requests):
    """Exactly-once service: one closed record per request, verdict set."""
    per_req = Counter(rec["req_id"] for rec in audit)
    return (len(per_req) == n_requests
            and all(c == 1 for c in per_req.values())
            and all(rec["evict_tick"] is not None and "status" in rec
                    for rec in audit))


def _quarantine_ticks(audit, req_ids):
    return {rid: rec["evict_tick"] - rec["admit_tick"]
            for rec in audit for rid in req_ids if rec["req_id"] == rid}


def _identical(a_resps, b_resps, a_audit, b_audit):
    if len(a_resps) != len(b_resps):
        return False
    for ra, rb in zip(a_resps, b_resps):
        if not (bitwise_equal(np.asarray(ra.x), np.asarray(rb.x))
                and ra.iters == rb.iters and ra.status == rb.status):
            return False
    ticks = [(r["req_id"], r["admit_tick"], r["evict_tick"], r["status"])
             for r in a_audit]
    return ticks == [(r["req_id"], r["admit_tick"], r["evict_tick"],
                      r["status"]) for r in b_audit]


def main(n_healthy: int = 5, m: int = 24, n: int = 64,
         stall_patience: int = 3, seed: int = 0) -> dict:
    problems = [nesterov_instance(m=m, n=n, nnz_frac=0.1, c=1.0,
                                  seed=seed + i)
                for i in range(n_healthy)]
    serve = ServeConfig(slab_capacity=4, chunk_iters=25, watchdog=True,
                        stall_patience=stall_patience)

    # -- NaN injection: healthy neighbours + one all-NaN warm start ----
    cfg = SolverConfig(max_iters=400, tol=1e-5, tau_adapt=False)
    nan_reqs = [solve_request_of(p) for p in problems]
    nan_reqs.insert(1, solve_request_of(
        problems[0], x0=np.full(n, np.nan, np.float32)))
    nan_idx = 1
    resps, audit, failures, snap = _run(cfg, serve, nan_reqs)
    resps2, audit2, _, _ = _run(cfg, serve, nan_reqs)

    nan_resp = resps[nan_idx]
    nan_ticks = _quarantine_ticks(audit, [nan_idx])[nan_idx]
    healthy = [r for i, r in enumerate(resps) if i != nan_idx]
    nan_rec = {
        "requests": len(nan_reqs),
        "status": nan_resp.status,
        "quarantine_tick": int(nan_ticks),
        "healthy_ok": bool(all(r.status == "ok" and r.converged
                               for r in healthy)),
        "failures": [{"req_id": f.req_id, "status": f.status,
                      "iters": f.iters} for f in failures],
        "telemetry_health": snap.get("health", {}),
        "audit_exactly_once": _audit_ok(audit, len(nan_reqs)),
        "deterministic": _identical(resps, resps2, audit, audit2),
    }

    # -- Stall injection: gamma0=0 freezes the iterate, stat never
    # decreases, so every request stalls after `stall_patience` chunks.
    stall_cfg = SolverConfig(max_iters=400, tol=1e-12, gamma0=0.0,
                             tau_adapt=False)
    stall_reqs = [solve_request_of(p) for p in problems[:3]]
    s_resps, s_audit, s_failures, s_snap = _run(stall_cfg, serve,
                                                stall_reqs)
    s_resps2, s_audit2, _, _ = _run(stall_cfg, serve, stall_reqs)
    s_ticks = _quarantine_ticks(s_audit, list(range(len(stall_reqs))))
    stall_rec = {
        "requests": len(stall_reqs),
        "statuses": [r.status for r in s_resps],
        "quarantine_tick": int(max(s_ticks.values())),
        "failures": [{"req_id": f.req_id, "status": f.status,
                      "iters": f.iters} for f in s_failures],
        "telemetry_health": s_snap.get("health", {}),
        "audit_exactly_once": _audit_ok(s_audit, len(stall_reqs)),
        "deterministic": _identical(s_resps, s_resps2, s_audit,
                                    s_audit2),
    }

    by_status = Counter(f.status for f in failures + s_failures)
    tele_div = (snap.get("health", {}).get("diverged", 0)
                + s_snap.get("health", {}).get("diverged", 0))
    tele_stall = (snap.get("health", {}).get("stalled", 0)
                  + s_snap.get("health", {}).get("stalled", 0))

    artifact = {
        "stall_patience": stall_patience,
        "serve_cfg": {"slab_capacity": serve.slab_capacity,
                      "chunk_iters": serve.chunk_iters},
        "instance": {"m": m, "n": n},
        "nan": nan_rec,
        "stall": stall_rec,
        "acceptance": {
            "nan_status_ok": nan_rec["status"] == "diverged",
            "nan_within_bound_ok": nan_rec["quarantine_tick"] <= 1,
            "nan_healthy_ok": nan_rec["healthy_ok"],
            "stall_status_ok": all(s == "stalled"
                                   for s in stall_rec["statuses"]),
            "stall_within_bound_ok":
                stall_rec["quarantine_tick"] <= stall_patience + 1,
            "audit_exactly_once_ok": bool(
                nan_rec["audit_exactly_once"]
                and stall_rec["audit_exactly_once"]),
            "deterministic_ok": bool(nan_rec["deterministic"]
                                     and stall_rec["deterministic"]),
            "counters_conserved_ok": bool(
                by_status.get("diverged", 0) == tele_div
                and by_status.get("stalled", 0) == tele_stall
                and tele_div + tele_stall == len(failures)
                + len(s_failures)),
        },
    }
    artifact["gate"] = sorted(artifact["acceptance"])

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_health.json"
    out.write_text(json.dumps(artifact, indent=2))
    print(f"[health] nan: {nan_rec['status']} in "
          f"{nan_rec['quarantine_tick']} tick(s)  "
          f"stall: {stall_rec['statuses']} in "
          f"{stall_rec['quarantine_tick']} tick(s)  "
          f"deterministic={artifact['acceptance']['deterministic_ok']}")
    print(f"wrote {out}")
    return artifact


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--healthy", type=int, default=5)
    ap.add_argument("--stall-patience", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    art = main(n_healthy=args.healthy,
               stall_patience=args.stall_patience, seed=args.seed)
    failed = [k for k in art["gate"] if not art["acceptance"][k]]
    if failed:
        raise SystemExit(f"acceptance failed on {failed}: "
                         f"{art['acceptance']}")
