#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 bench/sweep.py --workload m2k.serve.poisson --rates 0.5,1,2 --seconds 30

One process; for each rate, a window of the cell's traffic at that rate.
A rate is sustained where the service keeps up: answers per second
match the offered rate, and the queue wait of the window's last third
is no longer than that of its first third.  The cell's rate is then
set once, by hand, in its traffic file, at about four fifths of the
highest rate sustained.  One JSON line per rate goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(args.workload)
    _, peak = run.start_jax(cell)
    for rate in (float(r) for r in args.rates.split(",")):
        t = {**traffic, "rate_per_s": rate}
        _, rec, _, _, release = run.answer_window(
            cell, config, t, args.seed, args.seconds, False, peak)
        release()
        gc.collect()
        reqs = rec["requests"]
        third = args.seconds / 3

        def wait(lo, hi):
            w = [r["done"] - r["due"] for r in reqs
                 if r["done"] is not None and lo <= r["due"] < hi]
            return statistics.median(w) if w else None
        print(json.dumps({
            "rate_per_s": rate, "attempted": rec["attempted"],
            "unanswered": rec["unanswered"], **rec["e2e"],
            "latency_p50_first_third_s": wait(0, third),
            "latency_p50_last_third_s": wait(2 * third, args.seconds),
            "drain_s": rec["drain_s"],
            "generator_late_max_s": rec["generator_late_max_s"],
            "iters_by_group": rec["iters_by_group"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
