#!/usr/bin/env python3
"""Dress rehearsal of every cell of ``BENCHMARK.json`` on the CPU.

    python3 bench/rehearse.py [--seconds 3] [--trace 0|1]

Each cell runs end to end through ``bench/run.py``'s code, in this one
process, at a tiny size (the rows, columns and rates below) on four
virtual CPU devices, so that a cell on four chips runs its mesh too.
It finds wrong paths, arguments and control flow before a chip is
asked for.  It prints whether each run was correct, and no metric: a
CPU run measures nothing of the chip.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

#: Tiny shapes per configuration, and what each loop needs to fit them.
TINY = {"m": 40, "n": 200}
TINY_TRAFFIC = {"rate_per_s": 4.0, "pool_per_group": 2, "pool": 2,
                "drain_limit_s": 30, "trace_seconds": 0.5}
#: A device kind for the readers' arithmetic; a rehearsal prints none of it.
FAKE_PEAK = {"hbm_bytes_per_s": 1.0e11}


def rehearse(cell: str, seconds: float, trace: int, seed: int = 7) -> dict:
    return run.execute(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        rehearsal={"config": TINY, "traffic": TINY_TRAFFIC,
                   "peak": FAKE_PEAK})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    bad = 0
    for cell in bench["workloads"]:
        res = rehearse(cell["name"], args.seconds, args.trace)
        read = sorted(res["metrics"])
        print(f"{cell['name']}: correct={res['correct']} "
              f"attempted={res['attempted']} failed={res['failed']} "
              f"metrics read={read}", flush=True)
        bad += not res["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
