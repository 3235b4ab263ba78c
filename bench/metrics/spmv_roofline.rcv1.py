"""The chunk program's share of its HBM roofline on sparse designs
(iteration kernel): one read of each answer's design (values, row
indices and column pointers) per live slot-iteration at the chip's peak
bandwidth, over ``jit_chunk``'s device time (``bench/roofline_sparse.py``).
Nothing when the trace holds no such program."""
from bench.roofline_sparse import share


def read(rec):
    return share(rec)
