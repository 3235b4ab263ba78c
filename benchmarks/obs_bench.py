"""Observability overhead + determinism gates (``BENCH_obs.json``).

Replays the serve bench's heavy-tail trace through the continuous
backend three ways — untraced, traced, traced again — and gates the
``repro.obs`` contracts:

* **bitwise identity**: the traced replay returns bit-identical
  solutions and iteration counts to the untraced one (tracing is
  host-side only — it must never perturb device programs);
* **trace determinism**: two traced replays under the same injected
  clock export byte-identical JSONL;
* **schema**: every exported event carries exactly the span/instant key
  sets (``repro.obs.trace.SPAN_KEYS`` / ``INSTANT_KEYS``);
* **ledger conservation**: the session telemetry's unified
  ``CostLedger`` satisfies row = live + padding + freeze;
* **artifact**: a Perfetto-loadable Chrome trace-event file is written
  to ``results/bench/obs_trace.json``.

Overhead (traced vs untraced wall time and row-iters/s) is *recorded*
in every mode but *gated* (≤5%) only in the full run — wall-clock
comparisons on shared CI runners are timer-noise-flaky, so the
``--smoke`` CI step checks the deterministic criteria above only (the
PR 3 rule: no wall-clock compares in CI).
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# Allow `python benchmarks/obs_bench.py` (repo root not on sys.path then).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.serve_load import TRACES, build_instance, replay_ticks
from repro.config.base import ServeConfig, SolverConfig
from repro.obs import Tracer, bitwise_equal, tracing
from repro.obs.trace import INSTANT_KEYS, SPAN_KEYS

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

#: Overhead budget for the full run: tracing on may cost at most this
#: fraction of row-iteration throughput on the heavy-tail trace.
MAX_OVERHEAD = 0.05


class CountClock:
    """Injected tracer clock: 0.0, 1.0, 2.0, ... — no wall-clock state,
    so traced runs are byte-reproducible."""

    def __init__(self):
        self.t = -1.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def _replay(trace, problems, cfg, serve, tracer=None):
    """One continuous-backend replay; returns (xs, iters, telemetry,
    wall_s, jsonl)."""
    t0 = time.perf_counter()
    if tracer is None:
        client, tickets, tele, _ = replay_ticks(
            trace, problems, "continuous", cfg, serve)
    else:
        with tracing(tracer):
            client, tickets, tele, _ = replay_ticks(
                trace, problems, "continuous", cfg, serve)
    wall = time.perf_counter() - t0
    results = [client.result(t) for t in tickets]
    xs = np.stack([np.asarray(r.x) for r in results])
    iters = np.asarray([r.iters for r in results])
    jsonl = tracer.to_jsonl() if tracer is not None else None
    return xs, iters, tele, wall, jsonl


def _schema_ok(tracer: Tracer) -> bool:
    keysets = {"X": SPAN_KEYS, "i": INSTANT_KEYS}
    return all(tuple(e) == keysets[e["ph"]] for e in tracer.events())


def main(requests: int = 48, seed: int = 0, m: int = 64, n: int = 256,
         max_iters: int = 2500, slab_capacity: int = 8,
         chunk_iters: int = 100, reps: int = 3,
         smoke: bool = False) -> dict:
    if smoke:
        # Seconds-scale CI step: enough requests to exercise admission,
        # chunking, eviction and backfill, one timing rep (recorded,
        # not gated).
        requests, max_iters, reps = 16, 1200, 1
    cfg = SolverConfig(max_iters=max_iters, tol=1e-7, tau_adapt=False)
    serve = ServeConfig(slab_capacity=slab_capacity,
                        chunk_iters=chunk_iters)
    trace = TRACES["heavy_tail"](requests, seed)
    problems = [build_instance(t, m, n) for t in trace]

    # Warm the compile caches so every timed replay — and the traced
    # runs' compile-event stream — is steady-state.
    _replay(trace, problems, cfg, serve)

    # Timed untraced replays (best-of-reps floors scheduler noise).
    base_walls, base_xs, base_iters, base_tele = [], None, None, None
    for _ in range(reps):
        base_xs, base_iters, base_tele, wall, _ = _replay(
            trace, problems, cfg, serve)
        base_walls.append(wall)

    # Timed traced replays under an injected clock.
    traced_walls, jsonls = [], []
    tracer = None
    traced_xs = traced_iters = traced_tele = None
    for _ in range(max(2, reps)):       # ≥2 for the determinism compare
        tracer = Tracer(clock=CountClock())
        traced_xs, traced_iters, traced_tele, wall, jsonl = _replay(
            trace, problems, cfg, serve, tracer=tracer)
        traced_walls.append(wall)
        jsonls.append(jsonl)

    # Watchdog-on replay: the numerical-health pass rides the same
    # one-per-tick readback and must not perturb a healthy workload —
    # solutions and iteration counts stay bit-identical (gated), and
    # the extra device work stays inside the same 5% budget (full run).
    import dataclasses
    serve_wd = dataclasses.replace(serve, watchdog=True, stall_patience=10)
    _replay(trace, problems, cfg, serve_wd)     # warm the watchdog program
    wd_walls = []
    wd_xs = wd_iters = wd_tele = None
    for _ in range(reps):
        wd_xs, wd_iters, wd_tele, wall, _ = _replay(
            trace, problems, cfg, serve_wd)
        wd_walls.append(wall)
    wd_quarantined = sum(
        wd_tele.snapshot().get("health", {}).get(k, 0)
        for k in ("diverged", "stalled"))

    base_wall = float(min(base_walls))
    traced_wall = float(min(traced_walls))
    wd_wall = float(min(wd_walls))
    wd_overhead = (wd_wall / base_wall - 1.0) if base_wall else None
    row_iters = base_tele.snapshot()["continuous"]["row_iters"]
    thr_base = row_iters / base_wall if base_wall else None
    thr_traced = row_iters / traced_wall if traced_wall else None
    overhead = (traced_wall / base_wall - 1.0) if base_wall else None

    led = traced_tele.ledger()
    RESULTS.mkdir(parents=True, exist_ok=True)
    perfetto = RESULTS / "obs_trace.json"
    tracer.to_chrome(perfetto)

    artifact = {
        "smoke": smoke, "requests": requests, "seed": seed,
        "trace": "heavy_tail",
        "instance": {"m": m, "n": n},
        "solver_cfg": {"max_iters": max_iters, "tol": cfg.tol,
                       "tau_adapt": cfg.tau_adapt},
        "serve_cfg": {"slab_capacity": slab_capacity,
                      "chunk_iters": chunk_iters},
        "reps": reps,
        "wall_s": {"untraced": base_wall, "traced": traced_wall,
                   "watchdog": wd_wall},
        "row_iters": int(row_iters),
        "row_iters_per_s": {"untraced": thr_base, "traced": thr_traced},
        "overhead_frac": overhead,
        "max_overhead_frac": MAX_OVERHEAD,
        "watchdog": {"stall_patience": serve_wd.stall_patience,
                     "quarantined": int(wd_quarantined),
                     "overhead_frac": wd_overhead},
        "events": tracer.counts(),
        "ledger": led.as_dict(),
        "perfetto_artifact": str(perfetto),
        "acceptance": {
            # Byte-level compare (repro.obs.health.bitwise_equal), not
            # np.array_equal: heavy-tail traces can contain diverged
            # (all-NaN) solves, and NaN != NaN would fail the identity
            # check on bit-identical arrays.
            "bitwise_identity_ok": bool(
                bitwise_equal(base_xs, traced_xs)
                and bitwise_equal(base_iters, traced_iters)),
            # Healthy workload, watchdog enabled: same bits as the
            # legacy program — the health pass reads iteration outputs,
            # never feeds back.
            "watchdog_identity_ok": bool(
                wd_quarantined == 0
                and bitwise_equal(base_xs, wd_xs)
                and bitwise_equal(base_iters, wd_iters)),
            "trace_deterministic_ok": bool(
                jsonls[0] == jsonls[1] and len(jsonls[0]) > 0),
            "trace_schema_ok": bool(_schema_ok(tracer)),
            "ledger_conserved_ok": bool(led.conserved()),
            "perfetto_artifact_ok": perfetto.exists(),
            "overhead_ok": bool(overhead is not None
                                and overhead <= MAX_OVERHEAD),
            "watchdog_overhead_ok": bool(wd_overhead is not None
                                         and wd_overhead <= MAX_OVERHEAD),
        },
    }
    # Smoke gates only the deterministic criteria; the full run gates
    # the 5% overhead budgets as well.
    det = ["bitwise_identity_ok", "watchdog_identity_ok",
           "trace_deterministic_ok", "trace_schema_ok",
           "ledger_conserved_ok", "perfetto_artifact_ok"]
    artifact["gate"] = det if smoke else det + ["overhead_ok",
                                               "watchdog_overhead_ok"]

    out = RESULTS / "BENCH_obs.json"
    out.write_text(json.dumps(artifact, indent=2))
    print(f"[obs] untraced {base_wall:.3f}s  traced {traced_wall:.3f}s  "
          f"overhead {overhead * 100:+.2f}%  "
          f"events {sum(artifact['events'].values())}  "
          f"util {led.as_dict()['utilization']:.3f}")
    print(f"wrote {out} and {perfetto}")
    return artifact


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--max-iters", type=int, default=2500)
    ap.add_argument("--slab-capacity", type=int, default=8)
    ap.add_argument("--chunk-iters", type=int, default=100)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI configuration (deterministic "
                         "gates only; overhead recorded, not gated)")
    args = ap.parse_args()
    from repro.launch.runtime import device_banner
    print(device_banner())
    art = main(requests=args.requests, seed=args.seed, m=args.m,
               n=args.n, max_iters=args.max_iters,
               slab_capacity=args.slab_capacity,
               chunk_iters=args.chunk_iters, reps=args.reps,
               smoke=args.smoke)
    failed = [k for k in art["gate"] if not art["acceptance"][k]]
    if failed:
        raise SystemExit(f"acceptance failed on {failed}: "
                         f"{art['acceptance']}")
