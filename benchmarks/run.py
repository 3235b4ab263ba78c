"""Benchmark driver: one benchmark per paper table/figure.

All solver benchmarks go through the unified ``repro.solvers.solve`` facade
(one loop over registry method names — adding a solver to the registry adds
it to the race), and ``fig1`` additionally measures the batched
multi-instance engine (``repro.solvers.solve_batched``): B instances in one
compiled program vs B sequential facade solves.

Prints ``name,us_per_call,derived`` CSV rows:
  * fig1 groups  — per-algorithm wall time; derived = time-to-1e-4 rel err
  * batched      — multi-instance engine; derived = warm speedup vs loop
  * ablations    — per-variant wall time; derived = final rel err
  * serve_load   — continuous engine under arrival traces; derived =
                   p99 latency and device row iterations
  * path         — λ-path engine; derived = row-iteration ratio vs cold
  * lm_step      — per-arch train-step time; derived = decode-step time

Full JSON artifacts land in ``results/bench/`` and every ``BENCH_*.json``
is aggregated into the CSV: ``BENCH_solvers.json`` (written by
``fig1.main`` — full per-iteration (V, time) trajectories, summary rows,
the ``batched`` amortization record), ``BENCH_serve.json``
(``serve_load.main`` — arrival-trace replays),
``BENCH_path.json`` (``path_bench.main`` — regularization-path columns +
the CV-over-serve scenario), ``BENCH_compaction.json``
(``compaction_bench.main`` — masked-dense vs capacity-bucketed compacted
execution) and ``BENCH_health.json`` (``health_smoke.main`` —
numerical-health watchdog fault-injection gates).  ``--skip-serve`` /
``--skip-path`` / ``--skip-lm`` drop the slower sections.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# Allow `python benchmarks/run.py` (repo root not on sys.path then).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=8,
                    help="instance divisor vs paper size (1 = paper size)")
    ap.add_argument("--max-iters", type=int, default=400)
    ap.add_argument("--skip-lm", action="store_true")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument("--skip-path", action="store_true")
    ap.add_argument("--skip-remote", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="run every section at its seconds-scale CI "
                         "configuration (fig1 shrinks to one group, "
                         "ablations divide their instances, serve/path "
                         "use their smoke gates)")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero if any section's deterministic "
                         "acceptance criteria failed (checked at the "
                         "END, so one miss never truncates the run)")
    args = ap.parse_args()
    failures: list[str] = []

    from repro.launch.runtime import device_banner, use_compile_cache
    use_compile_cache()
    print(device_banner())
    print("name,us_per_call,derived")

    from benchmarks import fig1
    if args.smoke:
        rows = fig1.main(scale=32, max_iters=150,
                         groups=("fig1b_med_mid",), with_selection=False)
    else:
        rows = fig1.main(scale=args.scale, max_iters=args.max_iters)
    for r in rows:
        t4 = r.get("t_1e-04")
        derived = f"t(1e-4)={t4}s" if t4 is not None else \
            f"rel_final={r['rel_err_final']:.2e}"
        print(f"{r['group']}/{r['algo']}/seed{r['seed']},"
              f"{r['wall_s'] * 1e6 / max(1, r['iters']):.0f},{derived}")

    # The batched record fig1.main just wrote into BENCH_solvers.json.
    artifact = json.loads(
        (Path(fig1.RESULTS) / "BENCH_solvers.json").read_text())
    bat = artifact.get("batched")
    if bat:
        per_call = bat["batched_warm_s"] * 1e6 / bat["B"]
        print(f"batched_engine/B{bat['B']},{per_call:.0f},"
              f"speedup_warm={bat['speedup_warm']}x")

    # Selection-rule ablation (greedy vs random/hybrid/cyclic — S.3).
    sel = artifact.get("selection_ablation")
    if sel:
        for r in sel["rows"]:
            print(f"selection/{r['selection']},"
                  f"{r['wall_s'] * 1e6 / max(1, r['iters']):.0f},"
                  f"iters={r['iters']} rel={r['rel_err_final']:.2e}")

    from benchmarks import ablations
    out = ablations.main(smoke=args.smoke)
    for section, rows in out.items():
        for r in rows:
            rel = r.get("rel_err")
            print(f"ablate_{section}/{r['variant'].replace(' ', '_')},"
                  f"{r['wall_s'] * 1e6 / max(1, r['iters']):.0f},"
                  f"rel={'n/a' if rel is None else f'{rel:.2e}'}")

    if not args.skip_serve:
        # Continuous engine under arrival traces (writes
        # BENCH_serve.json).
        from benchmarks import serve_load
        art = serve_load.main(smoke=args.smoke)
        failures += [f"serve:{k}" for k in art["gate"]
                     if not art["acceptance"][k]]
        for trace, rec in art["traces"].items():
            cont = rec["continuous"]
            wall = cont.get("makespan_s") or 0.0
            per_req = wall * 1e6 / max(1, cont.get("requests") or 1)
            print(f"serve/{trace},{per_req:.0f},"
                  f"p99={cont['latency_p99_s']} "
                  f"row_iters={cont['row_iters']}")

        # Observability overhead + determinism gates (writes
        # BENCH_obs.json; --smoke gates the deterministic criteria only,
        # the full run adds the 5% tracing-overhead budget).
        from benchmarks import obs_bench
        art = obs_bench.main(smoke=args.smoke)
        failures += [f"obs:{k}" for k in art["gate"]
                     if not art["acceptance"][k]]
        per_evt = (art["wall_s"]["traced"] * 1e6
                   / max(1, sum(art["events"].values())))
        print(f"obs/heavy_tail,{per_evt:.0f},"
              f"overhead={art['overhead_frac']:+.4f} "
              f"util={art['ledger']['utilization']}")

    if not args.skip_path:
        # λ-path engine columns + CV-over-serve (writes BENCH_path.json).
        from benchmarks import path_bench
        art = path_bench.main(smoke=args.smoke)
        if not art["accept_ok"]:
            failures.append("path:accept_ok")
        acc = art["path"]["accept"]
        for mode, col in art["path"]["columns"].items():
            per = col["wall_s"] * 1e6 / max(1, col["row_iters"])
            print(f"path/{mode},{per:.1f},row_iters={col['row_iters']}")
        print(f"path/accept,0,ratio={acc['ratio_vs_cold_batched']}x "
              f"max_dev={acc['max_dev']:.1e} "
              f"ok={art['accept_ok']}")
        if "cv" in art:
            cv = art["cv"]
            print(f"path/cv,{cv['serve']['wall_s'] * 1e6:.0f},"
                  f"best_lambda={cv['best_lambda']:.4g} "
                  f"folds={cv['folds']}")

        # Compacted active-set execution vs the masked-dense path
        # (writes BENCH_compaction.json; gates are deterministic —
        # device-FLOP ratio + 1e-5 equivalence + bitwise replay).
        from benchmarks import compaction_bench
        art = compaction_bench.main(smoke=args.smoke)
        if not art["accept_ok"]:
            failures.append("compaction:accept_ok")
        acc = art["path"]["accept"]
        for mode, col in art["path"]["columns"].items():
            per = col["wall_s"] * 1e6 / max(1, col["row_iters"])
            print(f"compaction/{mode},{per:.1f},"
                  f"device_flops={col['device_flops']}")
        print(f"compaction/accept,0,ratio={acc['flop_ratio']}x "
              f"max_dev={acc['max_dev']:.1e} "
              f"widths={'/'.join(map(str, acc['program_widths']))} "
              f"ok={art['accept_ok']}")
        if "serve_drain" in art:
            sd = art["serve_drain"]
            print(f"compaction/serve_drain,0,"
                  f"migrations={sd['migrations']} "
                  f"max_dev={sd['max_dev']:.1e}")

    # Numerical-health watchdog fault-injection gates (writes
    # BENCH_health.json; always seconds-scale and fully deterministic).
    from benchmarks import health_smoke
    art = health_smoke.main()
    failures += [f"health:{k}" for k in art["gate"]
                 if not art["acceptance"][k]]
    print(f"health/nan,0,status={art['nan']['status']} "
          f"tick={art['nan']['quarantine_tick']}")
    print(f"health/stall,0,tick={art['stall']['quarantine_tick']} "
          f"patience={art['stall_patience']}")

    if not args.skip_remote:
        # Solver-service smoke: in-process server on a loopback port,
        # remote-backend equivalence vs inline + graceful-drain gate
        # (writes BENCH_remote.json; deterministic criteria only).
        from benchmarks import remote_smoke
        art = remote_smoke.main()
        if not art["ok"]:
            failures.append("remote:ok")
        acc = art["accept"]
        print(f"remote/equivalence,0,max_dev={acc['max_dev']:.1e} "
              f"cells={acc['cells_ok']}/{acc['cells']}")
        print(f"remote/drain,0,completed={art['drain']['completed']} "
              f"ok={art['drain']['ok']}")

    if not args.skip_lm:
        from benchmarks import lm_step
        for r in lm_step.main():
            print(f"lm_step/{r['arch']},{r['train_us']},"
                  f"decode_us={r['decode_us']}")

    if args.gate and failures:
        raise SystemExit(f"acceptance failed: {failures}")


if __name__ == "__main__":
    main()
