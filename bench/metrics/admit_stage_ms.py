"""Median, over the ticks that admitted, of the summed ``serve.stage``
spans inside each ``serve.tick``, in ms: the host copies that stage
admitted requests (admission).  Nothing where the program records no
``serve.stage`` span."""
import statistics


def read(rec):
    spans = rec.get("host_spans") or []
    stages = [(a, b) for n, a, b in spans if n == "serve.stage"]
    sums = []
    for n, t0, t1 in spans:
        if n == "serve.tick":
            inside = [b - a for a, b in stages if t0 <= a and b <= t1]
            if inside:
                sums.append(sum(inside))
    return 1e3 * statistics.median(sums) if sums else None
