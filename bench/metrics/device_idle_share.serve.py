"""Share of the traced window in which the device ran nothing (device)."""
from bench.roofline import idle_share


def read(rec):
    return idle_share(rec)
