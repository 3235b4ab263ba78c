"""Bytes an iteration needs, and the shares the per-layer readers report.

One iteration of Algorithm 1 on the Lasso with m < n needs A once:
Ax and Aᵀ(Ax − b) can share one pass over A, and every other operand
is a vector.  So the least time of ``iters`` iterations is
``iters · m · n · 4 B`` at the chip's peak HBM bandwidth
(``bench/peaks.json``).  The count comes from shapes and iteration
counts the benchmark knows, never from the program or the compiler.
"""
from __future__ import annotations


def iteration_bytes(m: int, n: int) -> int:
    """Least HBM bytes of one float32 iteration: one read of A."""
    return 4 * int(m) * int(n)


def share(rec: dict, program: str, iterations) -> float | None:
    """Percent of the roofline reached by the XLA module ``program``:
    least time of ``iterations(runs)`` iterations over the module's
    device time in the trace.  ``None`` when it did not run."""
    trace = rec.get("trace")
    if not trace:
        return None
    runs, device_s = 0, 0.0
    for name, mod in trace["modules"].items():
        if name == program:
            runs += mod["count"]
            device_s += mod["device_s"]
    if runs == 0 or device_s <= 0.0:
        return None
    cfg = rec["config"]
    least_s = iterations(runs) * iteration_bytes(cfg["m"], cfg["n"]) \
        / rec["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s


def idle_share(rec: dict) -> float | None:
    """Percent of the traced window in which no operation ran, averaged
    over the chips; ``None`` without a device in the trace."""
    trace = rec.get("trace")
    if not trace or not trace["busy_s"] or trace["window_s"] <= 0:
        return None
    busy = sum(trace["busy_s"]) / len(trace["busy_s"])
    return 100.0 * (1.0 - busy / trace["window_s"])
