"""A cell's instances: made on the device in one call, then handed to the
program as the traffic says (device arrays, or host arrays as a tenant's
request carries them)."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.gen.nesterov import make_instances, pool_keys


@dataclass
class Pool:
    """Instances as lists of arrays, on the device or on the host."""
    A: list                    # P arrays (m, n)
    b: list                    # P arrays (m,)
    v_star: np.ndarray         # (P,) planted optimal values
    nnz: list                  # (P,) nnz fraction of each instance
    c: float

    def __len__(self) -> int:
        return len(self.nnz)

    def data(self, i: int):
        """Instance ``i``'s ``(A, b)`` on the default device."""
        return jnp.asarray(self.A[i]), jnp.asarray(self.b[i])

    def to_host(self) -> "Pool":
        """The same pool with its arrays on the host; frees the device's."""
        A = [np.asarray(a) for a in self.A]
        b = [np.asarray(v) for v in self.b]
        for arr in self.A + self.b:
            arr.delete()
        return Pool(A, b, self.v_star, self.nnz, self.c)

    def problem(self, i: int):
        """Instance ``i`` as the program's :class:`Problem`."""
        from repro.problems.families import build_problem

        m, n = self.A[i].shape
        p = build_problem("lasso", (self.A[i], self.b[i]), self.c, n=int(n),
                          block_size=1, g_kind="l1")
        p.name = f"nesterov_lasso(m={m},n={n},nnz={self.nnz[i]:g})[{i}]"
        p.v_star = float(self.v_star[i])
        return p


def make(config: dict, nnz: list, pool_key: int, seed: int) -> Pool:
    """``len(nnz)`` instances of ``config``'s shape, one jitted call."""
    base, sign = pool_keys(pool_key, seed, len(nnz))
    A, b, _, v_star = make_instances(
        base, sign, jnp.asarray(nnz, jnp.float32), m=int(config["m"]),
        n=int(config["n"]), c=float(config["c"]))
    jax.block_until_ready(A)
    return Pool(A, b, np.asarray(v_star, np.float64), list(nnz),
                float(config["c"]))
