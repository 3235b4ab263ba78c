#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: a window of the cell's own traffic
through the program, then the numbers of ``bench/check.py`` for the
program's answers (the lower reading), and for the plain reference put
in the program's place on the same instances (which has to read
higher):

* ``bf16`` — the control: A and x rounded to bfloat16;
* ``high`` — three bfloat16 passes;
* ``early`` — a fault: float32 as the configuration states, stopped at
  ten times the request's tolerance and answered as converged (cells
  with a tolerance only).

One JSON line per seed and side goes to standard output, with the
cell's verdict on it (``correct``) under the limits of its traffic file.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

#: Side → (precision, factor on the request's tolerance).
SIDES = {"bf16": ("bf16", 1.0), "high": ("high", 1.0),
         "early": ("highest", 10.0)}


def readings(cell, config, traffic, seed, seconds, peak, sides):
    from bench import check

    _, record, answers, pool, release = run.answer_window(
        cell, config, traffic, seed, seconds, False, peak)
    release()
    gc.collect()
    tol, max_iters = traffic["solver"]["tol"], traffic["solver"]["max_iters"]
    limits = traffic["check"]
    numbers = check.compare(pool, answers, tol, max_iters)
    not_ok = sum(1 for a in answers if a[3] != "ok")
    rows = [{"seed": seed, "side": "program",
             "correct": check.verdict(numbers, limits, record["unanswered"],
                                      not_ok)[0],
             "iters": {int(a[0]): a[2] for a in answers}, **numbers}]
    instances = sorted({a[0] for a in answers})
    for side in sides:
        precision, factor = SIDES[side]
        if factor != 1.0 and tol <= 0:
            continue
        ctl = {i: check.reference(pool.data(i), pool.c, tol * factor,
                                  max_iters, precision=precision)
               for i in instances}
        ctl_answers = [(i, x, k, "ok", k < max_iters)
                       for i, (x, k, _) in ctl.items()]
        numbers = check.compare(pool, ctl_answers, tol, max_iters)
        rows.append({"seed": seed, "side": side,
                     "correct": check.verdict(numbers, limits, 0, 0)[0],
                     "iters": {int(i): v[1] for i, v in ctl.items()},
                     **numbers})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sides", default="bf16,early")
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(args.workload)
    _, peak = run.start_jax(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in readings(cell, config, traffic, seed, args.seconds, peak,
                            [s for s in args.sides.split(",") if s]):
            print(json.dumps(row, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
