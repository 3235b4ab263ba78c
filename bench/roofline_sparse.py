"""Bytes a sparse-design iteration needs, and the chunk program's share
of that roofline.

One iteration of Algorithm 1 on the Lasso reads its design at least
once: one float32 value and one int32 row index per nonzero, and the
n + 1 int32 column pointers, so ``8 · nnz + 4 · (n + 1)`` bytes.  The
least time of the window is that count over every live
slot-iteration (each answer's iterations at its own instance's nnz) at
the chip's peak HBM bandwidth (``bench/peaks.json``).  The count comes
from shapes and iteration counts the benchmark knows, never from the
program or the compiler.
"""
from __future__ import annotations


def iteration_bytes(nnz: int, n: int) -> int:
    """Least HBM bytes of one iteration on a design of ``nnz``
    nonzeros."""
    return 8 * int(nnz) + 4 * (int(n) + 1)


def share(rec: dict, program: str = "jit_chunk") -> float | None:
    """Percent of the roofline reached by the XLA module ``program``
    over the answered requests; ``None`` where it did not run or the
    record lacks the instances' nnz."""
    trace, nnz = rec.get("trace"), rec.get("nnz")
    if not trace or not nnz:
        return None
    device_s = sum(mod["device_s"] for name, mod in trace["modules"].items()
                   if name == program)
    if device_s <= 0.0:
        return None
    n = rec["config"]["n"]
    least = sum(r["iters"] * iteration_bytes(nnz[r["instance"]], n)
                for r in rec["requests"] if r["iters"] is not None)
    return 100.0 * least / rec["peak"]["hbm_bytes_per_s"] / device_s
