"""Dress rehearsal of the sparse cell, ``rcv1.serve.poisson``, on the CPU.

    python3 -m pytest -q bench/test_bench_sparse.py

The cell runs end to end through ``bench/run.py``'s code at a small
size (300 × 1,000, about 1% dense, the configuration's Zipf columns and
nnz spread): a sound run is correct, and with the timed path broken
underneath — an altered answer, a program that stops at ten times the
request's tolerance — the run reports ``correct`` false.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import rehearse  # noqa: E402  (sets JAX_PLATFORMS=cpu before jax loads)
import run  # noqa: E402

from test_bench import _answer_altered, _stops_early  # noqa: E402

CELL = "rcv1.serve.poisson"
SMALL = {"m": 300, "n": 1000, "nnz_mean": 3000}


def _rehearse(**traffic):
    from repro.solvers import cache
    cache.clear_all()
    return run.execute(
        ["--workload", CELL, "--seed", str(2 ** 33 + 9), "--seconds", "2",
         "--trace", "1"],
        rehearsal={"config": SMALL,
                   "traffic": {**rehearse.TINY_TRAFFIC, **traffic},
                   "peak": rehearse.FAKE_PEAK})


def test_sound_sparse_run_is_correct():
    res = _rehearse()
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    # The admission counter is read; the roofline needs a device trace.
    assert 0.0 < res["metrics"]["nnz_pad_share.rcv1"]["value"] < 100.0


@pytest.mark.parametrize("fault", [_answer_altered, _stops_early],
                         ids=["answer_altered", "stops_early"])
def test_fault_makes_sparse_run_incorrect(fault, monkeypatch):
    fault(monkeypatch)
    res = _rehearse(drain_limit_s=3)
    assert res["correct"] is False
