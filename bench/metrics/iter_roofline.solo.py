"""The solo step program's share of its HBM roofline (iteration kernel).

Least time: one read of A (m·n float32) per iteration at the chip's peak
bandwidth, the least any implementation of Algorithm 1 needs for m < n.
Iterations are the step program's runs in the traced slice; time is
their device time.  Nothing when the trace holds no such program.
"""
from bench.roofline import share


def read(rec):
    return share(rec, "jit_family_step", lambda runs: runs)
