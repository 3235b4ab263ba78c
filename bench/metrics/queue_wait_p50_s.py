"""Median wait from a request's due time to its admission (scheduler),
from the service's own arrival and admission times."""
import statistics


def read(rec):
    waits = rec.get("queue_waits") or []
    return statistics.median(waits) if waits else None
