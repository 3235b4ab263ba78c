"""Median ``serve.upload`` span, in ms: one admitting tick's copy of the
staged payload and its transfer to the device (admission).  Nothing
where the program records no such span."""
import statistics


def read(rec):
    ups = [b - a for n, a, b in rec.get("host_spans") or []
           if n == "serve.upload"]
    return 1e3 * statistics.median(ups) if ups else None
