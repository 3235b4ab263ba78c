"""Checks of the correctness check's choice of reference by problem
family, on the CPU at small sizes.

    python3 -m pytest -q bench/test_families.py

* the plain logistic reference (``bench/reference_logreg.py``) follows
  the program's ``logreg`` iteration, solo and served;
* ``check.compare`` on a logistic pool that plants no optimum passes the
  program's served answers, and rejects the bfloat16 control and a
  solve stopped at ten times the tolerance;
* a family with no reference file stops the check with an error that
  names the file;
* on a Lasso pool, the numbers through the dispatch are those of direct
  calls of ``bench/reference.py``;
* ``bag_of_words`` is the design of the sparse Lasso pool.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import rehearse  # noqa: E402,F401  (sets JAX_PLATFORMS=cpu before jax loads)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, reference, reference_logreg  # noqa: E402
from bench import pool as pools  # noqa: E402
from bench.gen import text_sparse  # noqa: E402
from bench.gen.nesterov import pool_keys  # noqa: E402

TOL, MAX_ITERS = 1e-4, 3000
#: Limits for the logistic pool below, set between the readings on the
#: CPU over three pools of four instances each: the program's served
#: answers read at most obj_dev 4.6e-7, x_dev 1.7e-4, iters_short 0.008;
#: the bf16 control at least obj_dev 1.9e-3, x_dev 0.021, iters_short
#: 0.83; the stop at 10 × tol iters_short 0.66 or more.
LOGREG_LIMITS = {"obj_dev": 1e-4, "x_dev": 5e-3, "iters_short": 0.3}


def _logreg(seed: int, m: int = 80, n: int = 240, c: float = 0.3):
    from repro.problems.logreg import random_logreg_instance
    return random_logreg_instance(m=m, n=n, nnz_frac=0.1, c=c, seed=seed)


class LogregPool:
    """Dense logistic instances, stated to the check as a pool: family
    ``logreg``, no planted optimum, ``data(i) = (Z,)``."""
    family = "logreg"
    v_star = None

    def __init__(self, problems, c: float):
        self.problems, self.c = problems, c

    def __len__(self) -> int:
        return len(self.problems)

    def data(self, i: int):
        return (jnp.asarray(self.problems[i].data["Z"]),)


def _solve(problems, backend: str):
    from repro.client import FlexaClient, SoloSpec
    from repro.config.base import ServeConfig, SolverConfig
    client = FlexaClient(backend=backend,
                         solver=SolverConfig(tol=TOL, max_iters=MAX_ITERS),
                         serve=ServeConfig(slab_capacity=4, chunk_iters=16))
    tickets = [client.submit(SoloSpec(p)) for p in problems]
    while client.pending:
        client.step()
    return [client.result(t) for t in tickets]


# ------------------------------------------------------------------ #
# The logistic reference against the program                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("backend", ["inline", "continuous"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_logreg_reference_follows_program(backend, seed):
    """The same iteration in float32, its sums in another order:

    * the stopping iteration within 2%: near the stop ‖ẑ − x‖∞ creeps
      under the tolerance slowly, so last-bit differences move it by a
      few of several hundred iterations (0–6 of 379–617 read);
    * after as many iterations as the program ran, x within 1e-3 of
      ‖x_ref‖∞ and V within 1e-5 of V(x_ref): reduction order alone
      reads at most 1.4e-4 and 6.5e-7."""
    p = _logreg(seed, m=60, n=200, c=0.5)
    Z, c = p.data["Z"], jnp.float32(0.5)
    r, = _solve([p], backend)
    assert r.converged
    _, k_ref, _ = reference_logreg.solve(Z, c, jnp.float32(TOL),
                                         jnp.int32(MAX_ITERS))
    assert abs(r.iters - int(k_ref)) <= 0.02 * int(k_ref)
    x_k, _, _ = reference_logreg.solve(Z, c, jnp.float32(-1.0),
                                       jnp.int32(r.iters))
    x_k, x = np.asarray(x_k), np.asarray(r.x)
    assert np.max(np.abs(x - x_k)) <= 1e-3 * np.max(np.abs(x_k))
    v = float(reference_logreg.objective(Z, c, jnp.asarray(x)))
    v_k = float(reference_logreg.objective(Z, c, jnp.asarray(x_k)))
    assert abs(v - v_k) <= 1e-5 * v_k


# ------------------------------------------------------------------ #
# The check on a pool with no planted optimum                         #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def logreg_pool():
    return LogregPool([_logreg(100 + j) for j in range(4)], 0.3)


def _reference_answers(pool, tol, precision):
    answers = []
    for i in range(len(pool)):
        x, k, _ = check.reference(pool.data(i), pool.c, tol, MAX_ITERS,
                                  precision=precision, family=pool.family)
        answers.append((i, x, k, "ok", k < MAX_ITERS))
    return answers


def test_logreg_check_passes_program(logreg_pool):
    answers = [(i, np.asarray(r.x), r.iters, r.status, r.converged)
               for i, r in enumerate(_solve(logreg_pool.problems,
                                            "continuous"))]
    sound = check.compare(logreg_pool, answers, TOL, MAX_ITERS)
    assert sound["answers"] == len(logreg_pool)
    assert check.verdict(sound, LOGREG_LIMITS, 0, 0)[0] is True


@pytest.mark.parametrize("side,precision,factor,fails_on", [
    ("bf16", "bf16", 1.0, ("obj_dev", "x_dev", "iters_short")),
    ("early", "highest", 10.0, ("iters_short",))])
def test_logreg_check_rejects(logreg_pool, side, precision, factor,
                              fails_on):
    numbers = check.compare(
        logreg_pool, _reference_answers(logreg_pool, TOL * factor,
                                        precision), TOL, MAX_ITERS)
    ok, checks = check.verdict(numbers, LOGREG_LIMITS, 0, 0)
    assert ok is False
    assert all(checks[k]["value"] > checks[k]["limit"] for k in fails_on)


def test_scale_without_planted_optimum(logreg_pool):
    """With no V*, obj_dev is divided by the reference's own value where
    it meets the tolerance."""
    data, c = logreg_pool.data(0), logreg_pool.c
    x_ref, k_ref, v_ref = check.reference(data, c, TOL, MAX_ITERS,
                                          family="logreg")
    x = 0.9 * x_ref
    numbers = check.compare(logreg_pool, [(0, x, k_ref, "ok", True)], TOL,
                            MAX_ITERS)
    v = float(reference_logreg.objective(*data, c, jnp.asarray(x)))
    assert numbers["obj_dev"] == abs(v - v_ref) / v_ref > 0


# ------------------------------------------------------------------ #
# Dispatch                                                            #
# ------------------------------------------------------------------ #
def test_family_without_reference_names_the_file():
    class Pool(LogregPool):
        family = "no_such_family"
    pool = Pool([_logreg(0, m=20, n=40)], 0.5)
    with pytest.raises(FileNotFoundError,
                       match="bench/reference_no_such_family.py"):
        check.compare(pool, [(0, np.zeros(40), 1, "ok", True)], TOL, 10)
    with pytest.raises(FileNotFoundError,
                       match="bench/reference_no_such_family.py"):
        check.reference(pool.data(0), pool.c, TOL, 10,
                        family="no_such_family")


def test_lasso_dispatch_is_the_direct_reference():
    """A pool that states no family is a Lasso pool, checked by
    ``bench/reference.py`` as before the dispatch: the same numbers, to
    the bit, as direct calls."""
    tol, max_iters = 2e-3, 2000
    pool = pools.make({"m": 40, "n": 200, "c": 1.0}, [0.05, 0.1], 0,
                      2 ** 40 + 3)
    assert not hasattr(pool, "family")
    answers = []
    for i in range(len(pool)):
        x, k, _ = reference.solve(*pool.data(i), jnp.float32(pool.c),
                                  jnp.float32(10 * tol),
                                  jnp.int32(max_iters))
        answers.append((i, np.asarray(x) * (0.9 + 0.1 * i), int(k), "ok",
                        True))
    got = check.compare(pool, answers, tol, max_iters)
    dev, x_dev, short, ref_iters = [], [], [], {}
    for i, x, k, _, _ in answers:
        A, b = pool.data(i)
        c = jnp.float32(pool.c)
        _, k_ref, _ = reference.solve(A, b, c, jnp.float32(tol),
                                      jnp.int32(max_iters))
        ref_iters[i] = int(k_ref)
        x_k, _, _ = reference.solve(A, b, c, jnp.float32(-1.0),
                                    jnp.int32(k))
        v_k = float(reference.objective(A, b, pool.c, x_k))
        v = float(reference.objective(A, b, pool.c, jnp.asarray(x)))
        x_k = np.asarray(x_k)
        dev.append(abs(v - v_k) / pool.v_star[i])
        x_dev.append(float(np.max(np.abs(x - x_k)))
                     / float(np.max(np.abs(x_k))))
        short.append((int(k_ref) - k) / int(k_ref))
    assert got["obj_dev"] == max(dev) > 0
    assert got["x_dev"] == max(x_dev) > 0
    assert got["iters_short"] == max(short) > 0
    assert got["ref_iters"] == ref_iters


# ------------------------------------------------------------------ #
# The bag-of-words design                                             #
# ------------------------------------------------------------------ #
def test_bag_of_words_is_the_sparse_lasso_design():
    cfg = {"m": 300, "n": 1000, "nnz_mean": 3000, "nnz_spread": 0.1,
           "c": 1.0}
    seed = 2 ** 33 + 9
    pool = text_sparse.make(cfg, [0.01, 0.02], 3, seed)
    base, _ = pool_keys(3, seed, len(pool))
    for i in range(len(pool)):
        keys = tuple(jax.random.split(base[i], 9)[:6])
        vals, rows, cols, col_ptr = text_sparse.bag_of_words(
            keys, m=300, n=1000, cap=text_sparse.capacity(cfg),
            nnz_mean=3000.0, nnz_spread=0.1)
        np.testing.assert_array_equal(rows, pool.rows[i])
        np.testing.assert_array_equal(col_ptr, pool.col_ptr[i])
        np.testing.assert_array_equal(np.asarray(vals) != 0,
                                      np.asarray(pool.values[i]) != 0)
        nnz = int(col_ptr[-1])
        assert np.all(np.asarray(cols)[:nnz]
                      == np.repeat(np.arange(1000), np.diff(col_ptr)))
        row_sq = np.zeros(300)
        np.add.at(row_sq, np.asarray(rows), np.asarray(vals, np.float64)
                  ** 2)
        np.testing.assert_allclose(row_sq[row_sq > 0], 1.0, rtol=1e-5)
