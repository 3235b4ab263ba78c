"""Share of the slab capacity that the window's admitted sparse designs
occupied without a nonzero to store (admission): 1 − nonzeros stored ÷
capacity occupied, from the program's admission counters.  Nothing where
the program keeps no such counters or admitted no sparse design."""


def read(rec):
    cap = rec.get("nnz_capacity")
    if not cap:
        return None
    return 100.0 * (1.0 - rec["nnz_stored"] / cap)
