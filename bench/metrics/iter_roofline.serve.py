"""The chunk program's share of its HBM roofline (iteration kernel).

Least time: one read of A (m·n float32) per live slot-iteration (the
iterations of every answer, the trace covering the whole window and
drain) at the chip's peak bandwidth; time is the chunk program's device
time.  Nothing when the trace holds no such program.
"""
from bench.roofline import share


def read(rec):
    return share(rec, "jit_chunk", lambda runs: sum(rec["iters"]))
