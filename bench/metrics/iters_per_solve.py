"""Mean iterations of Algorithm 1 per answer in the window (solver)."""


def read(rec):
    iters = rec.get("iters") or []
    return sum(iters) / len(iters) if iters else None
