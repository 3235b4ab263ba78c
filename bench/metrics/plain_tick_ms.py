"""Median ``serve.tick`` span, in ms, of ticks that admitted nothing
(scheduler)."""
import statistics


def read(rec):
    ticks = [d for d, admits in rec.get("ticks") or [] if admits == 0]
    return 1e3 * statistics.median(ticks) if ticks else None
