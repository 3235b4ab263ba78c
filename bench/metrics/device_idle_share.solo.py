"""Share of the traced slice in which the device ran nothing (device)."""
from bench.roofline import idle_share


def read(rec):
    return idle_share(rec)
