"""Sparse Lasso instances shaped like a bag-of-words design, made on the
device, with Nesterov's planted optimum.

The design (the shape and density of LIBSVM's rcv1.binary; nothing is
downloaded, so each choice below is an assumption, listed under
``assumed`` in the configuration):

1. nnz: the configuration's mean, drawn per instance within ± its
   spread (uniform), so the designs of one pool are unequal as tenants'
   designs are.
2. Column counts follow a Zipf law of exponent 1 over a random ranking
   of the columns: the column of rank r holds round(C / (r + 1))
   entries, capped at m and at least 1, with C set (by bisection) so
   the counts sum to the instance's nnz.  Like word frequencies, a few
   columns are dense and most hold a handful of entries.
3. Column j's rows are π((oⱼ + sⱼ t) mod m), t = 0, 1, …: π a random
   permutation of the rows, oⱼ a random offset and sⱼ a random stride
   prime to m, so a column's rows are distinct and the columns' row
   sets are scattered.
4. The entries of B are N(0, 1), each row then scaled to unit norm, as
   the rows (documents) of rcv1's tf-idf features are.

Then Nesterov's construction (``bench/gen/nesterov.py``) on the sparse
B: A = B diag(scale) keeps B's pattern, x* is planted on the s columns
of largest |Bᵀy|, b = A x* + y, and V* = ‖y‖² + c‖x*‖₁ in closed form.
The run's seed flips the signs of rows and columns, as there.

An instance is stored column by column — ``values`` and int32 ``rows``
padded to a static capacity, ``col_ptr`` (n + 1,) — in one jitted call
for the whole pool; nothing is made on the host.  :class:`Pool` hands
the program its designs (``problem``) and the plain reference the same
data densified on the device (``data``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen.nesterov import pool_keys


def zipf_counts(target, *, m: int, n: int, iters: int = 64):
    """Counts by rank, (n,) int32, summing to at most ``target``
    (traced): Zipf of exponent 1, each in [1, m]."""
    inv = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32)

    def counts(C):
        return jnp.clip(jnp.round(C * inv), 1, m).astype(jnp.int32)

    def body(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        fits = jnp.sum(counts(mid)) <= target
        return jnp.where(fits, mid, lo), jnp.where(fits, hi, mid)
    lo, _ = jax.lax.fori_loop(0, iters, body,
                              (jnp.float32(0.0), jnp.float32(m * n)))
    return counts(lo)


def _strides(m: int) -> np.ndarray:
    """Strides prime to m: each makes t ↦ (o + s t) mod m one-to-one."""
    s = np.arange(1, m, dtype=np.int64)
    return s[np.gcd(s, m) == 1].astype(np.int32)


def _one(base_key, sign_key, support, *, m: int, n: int, cap: int,
         nnz_mean: float, nnz_spread: float, c: float):
    (k_nnz, k_rank, k_perm, k_off, k_stride, k_val, k_y, k_theta,
     k_xi) = jax.random.split(base_key, 9)
    target = nnz_mean * (1.0 + nnz_spread * jax.random.uniform(
        k_nnz, (), jnp.float32, -1.0, 1.0))
    by_rank = zipf_counts(target, m=m, n=n)
    counts = jnp.zeros((n,), jnp.int32).at[
        jax.random.permutation(k_rank, n)].set(by_rank)
    col_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    nnz = col_ptr[n]

    p = jnp.arange(cap, dtype=jnp.int32)
    marks = jnp.zeros((cap + 1,), jnp.int32).at[col_ptr[1:]].add(1)
    cols = jnp.minimum(jnp.cumsum(marks[:cap]), n - 1)
    valid = p < nnz
    t = p - col_ptr[cols]
    perm = jax.random.permutation(k_perm, m).astype(jnp.int32)
    off = jax.random.randint(k_off, (n,), 0, m, jnp.int32)
    table = jnp.asarray(_strides(m))
    stride = table[jax.random.randint(k_stride, (n,), 0, table.shape[0])]
    rows = jnp.where(valid, perm[(off[cols] + stride[cols] * t) % m], 0)
    vals = jnp.where(valid, jax.random.normal(k_val, (cap,), jnp.float32),
                     0.0)
    row_sq = jax.ops.segment_sum(vals * vals, rows, num_segments=m)
    vals = vals / jnp.sqrt(jnp.maximum(row_sq[rows], 1e-30))

    def rmatvec(v, r):
        return jax.ops.segment_sum(v * r[rows], cols, num_segments=n,
                                   indices_are_sorted=True)

    def matvec(v, x):
        return jax.ops.segment_sum(v * x[cols], rows, num_segments=m)

    y = jax.random.normal(k_y, (m,), jnp.float32)
    y = y / jnp.sqrt(jnp.sum(y * y))
    u = rmatvec(vals, y)
    au = jnp.abs(u)
    s = jnp.maximum(1, jnp.round(support * n)).astype(jnp.int32)
    on = jnp.argsort(jnp.argsort(-au)) < s
    half_c = 0.5 * c
    theta = jax.random.uniform(k_theta, (n,), jnp.float32)
    shrink = jnp.where(au > half_c * theta, half_c * theta / au, 1.0)
    scale = jnp.where(on, half_c / au, shrink)
    values = vals * scale[cols]
    xi = jax.random.uniform(k_xi, (n,), jnp.float32)
    x_star = jnp.where(on, xi * jnp.sign(u), 0.0)
    b = matvec(values, x_star) + y
    v_star = jnp.sum(y * y) + c * jnp.sum(jnp.abs(x_star))

    k_r, k_s = jax.random.split(sign_key)
    r = jax.random.rademacher(k_r, (m,), jnp.float32)
    sg = jax.random.rademacher(k_s, (n,), jnp.float32)
    return (values * r[rows] * sg[cols], rows, col_ptr, b * r, x_star * sg,
            v_star)


@partial(jax.jit, static_argnames=("m", "n", "cap", "nnz_mean",
                                   "nnz_spread", "c"))
def make_instances(base_keys, sign_keys, support, *, m: int, n: int,
                   cap: int, nnz_mean: float, nnz_spread: float,
                   c: float = 1.0):
    """``len(support)`` instances, one per (base key, sign key, planted
    support fraction): stacked ``values``/``rows`` (P, cap), ``col_ptr``
    (P, n + 1), ``b`` (P, m), ``x_star`` (P, n), ``v_star`` (P,)."""
    return jax.vmap(partial(_one, m=m, n=n, cap=cap, nnz_mean=nnz_mean,
                            nnz_spread=nnz_spread, c=c))(
        base_keys, sign_keys, support)


@partial(jax.jit, static_argnames=("m", "n"))
def densify(values, rows, col_ptr, *, m: int, n: int):
    """One instance's design as an (m, n) float32 array."""
    cap = values.shape[0]
    marks = jnp.zeros((cap + 1,), jnp.int32).at[col_ptr[1:]].add(1)
    cols = jnp.minimum(jnp.cumsum(marks[:cap]), n - 1)
    return jnp.zeros((m, n), jnp.float32).at[rows, cols].add(values)


def capacity(config: dict) -> int:
    """A static capacity that holds every instance's nnz: the next power
    of two above the largest draw."""
    top = float(config["nnz_mean"]) * (1.0 + float(config["nnz_spread"]))
    return 1 << int(np.ceil(np.log2(top + 1)))


@dataclass
class Pool:
    """Sparse instances: per instance ``values``, ``rows`` (length nnz
    once on the host, padded while on the device) and ``col_ptr``."""
    values: list
    rows: list
    col_ptr: list
    b: list
    v_star: np.ndarray
    support: list              # planted support fraction of each
    c: float
    m: int
    n: int

    def __len__(self) -> int:
        return len(self.support)

    def nnz(self, i: int) -> int:
        return int(np.asarray(self.col_ptr[i])[-1])

    def data(self, i: int):
        """Instance ``i``'s ``(A, b)`` on the default device, A dense:
        what the plain reference reads."""
        return (densify(jnp.asarray(self.values[i]),
                        jnp.asarray(self.rows[i]),
                        jnp.asarray(self.col_ptr[i]), m=self.m, n=self.n),
                jnp.asarray(self.b[i]))

    def to_host(self) -> "Pool":
        """The same pool as host arrays trimmed to each nnz, as a
        tenant's request carries them; frees the device's."""
        nnz = [self.nnz(i) for i in range(len(self))]
        vals = [np.asarray(v[:k]) for v, k in zip(self.values, nnz)]
        rows = [np.asarray(r[:k]) for r, k in zip(self.rows, nnz)]
        ptr = [np.asarray(p) for p in self.col_ptr]
        b = [np.asarray(v) for v in self.b]
        for arr in self.values + self.rows + self.col_ptr + self.b:
            if isinstance(arr, jax.Array):
                arr.delete()
        return Pool(vals, rows, ptr, b, self.v_star, self.support, self.c,
                    self.m, self.n)

    def problem(self, i: int):
        """Instance ``i`` as the program's :class:`Problem`, its design a
        ``CSCDesign`` of the arrays where they are."""
        from repro.problems.families import build_problem
        from repro.problems.sparse import CSCDesign

        A = CSCDesign(self.values[i], self.rows[i], self.col_ptr[i],
                      (self.m, self.n))
        p = build_problem("lasso", (A, self.b[i]), self.c, n=self.n,
                          block_size=1, g_kind="l1")
        p.name = (f"text_sparse_lasso(m={self.m},n={self.n},"
                  f"nnz={self.nnz(i)},support={self.support[i]:g})[{i}]")
        p.v_star = float(self.v_star[i])
        return p


def make(config: dict, support: list, pool_key: int, seed: int) -> Pool:
    """``len(support)`` instances of ``config``'s shape, one jitted
    call."""
    m, n = int(config["m"]), int(config["n"])
    base, sign = pool_keys(pool_key, seed, len(support))
    values, rows, col_ptr, b, _, v_star = make_instances(
        base, sign, jnp.asarray(support, jnp.float32), m=m, n=n,
        cap=capacity(config), nnz_mean=float(config["nnz_mean"]),
        nnz_spread=float(config["nnz_spread"]), c=float(config["c"]))
    jax.block_until_ready(values)
    P = len(support)
    return Pool([values[i] for i in range(P)], [rows[i] for i in range(P)],
                [col_ptr[i] for i in range(P)], [b[i] for i in range(P)],
                np.asarray(v_star, np.float64), list(support),
                float(config["c"]), m, n)
