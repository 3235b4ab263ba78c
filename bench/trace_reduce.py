"""From a JAX profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the newest ``*.xplane.pb`` under a profile directory
into plain tuples; :func:`reduce` turns them into a summary:

* ``busy_s`` — per device, the length of the union of the intervals in
  which an XLA operation ran, clipped to the window;
* ``modules`` — per XLA module (program) name, how many times it ran
  and its device seconds, summed over devices;
* ``top_ops`` — the operations that took most device time, named
  ``<program>/<operation>``, operations that hold others left out;
* ``idle_gaps`` — the longest stretches in which device 0 ran nothing,
  each named by the innermost host span open at its midpoint.

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``\\ s
(names starting ``bench.``), which the profiler records on its own
clock, plus any spans the caller passes in after shifting them onto
that clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    """An XLA module event's name without its trailing ``(id)``."""
    return _SUFFIX.sub("", name).strip()


def op_name(name: str) -> str:
    """``%fusion.46 = f32[...] fusion(...)`` → ``fusion.46``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _leaf_ops(ops: list) -> list:
    """Operations that hold no other: a ``while`` or ``conditional``
    spans the operations of its body, which carry the time."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= e[2] or nxt[2] > e[2]]


def _owner(modules: list, t: float) -> str:
    """The program whose run holds time ``t``."""
    starts = [m[1] for m in modules]
    j = bisect.bisect_right(starts, t) - 1
    if j >= 0 and modules[j][2] >= t:
        return program_name(modules[j][0])
    return "?"


def load(logdir: str) -> dict:
    """``{"devices": [{"name", "ops", "modules"}], "host": [...]}``, each
    event a ``(name, start_ns, end_ns)`` tuple."""
    import jax

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith("bench.")]
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "host": host}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals, clipped to ``[lo, hi]``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "(no host span)"


def reduce(trace: dict, lo: float, hi: float, extra_host=(),
           top: int = 10) -> dict:
    """Summarise the window ``[lo, hi]`` (nanoseconds on the trace's
    clock).  ``extra_host`` adds ``(name, start_ns, end_ns)`` spans for
    the idle-gap attribution."""
    devs = trace["devices"]
    busy, modules, ops = [], {}, {}
    for dev in devs:
        # Operations give busy time; where a trace has no op line, the
        # module events stand in for them.
        events = dev["ops"] or dev["modules"]
        busy.append(sum(e - s for s, e in union(
            [(s, e) for _, s, e in events], lo, hi)) / 1e9)
        for name, s, e in dev["modules"]:
            if s >= lo and e <= hi:
                rec = modules.setdefault(program_name(name),
                                         {"count": 0, "device_s": 0.0})
                rec["count"] += 1
                rec["device_s"] += (e - s) / 1e9
        mods = sorted(dev["modules"], key=lambda m: m[1])
        for name, s, e in _leaf_ops(dev["ops"]):
            if s >= lo and e <= hi:
                key = f"{_owner(mods, s)}/{op_name(name)}"
                ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
    gaps = []
    if devs:
        events = devs[0]["ops"] or devs[0]["modules"]
        merged = union([(s, e) for _, s, e in events], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        spans = list(trace["host"]) + list(extra_host)
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append([_innermost(spans, (s + e) / 2), (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    n = max(1, len(devs))
    top_ops = sorted(([k, v / n] for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "devices": len(devs),
            "busy_s": busy, "modules": modules, "top_ops": top_ops,
            "idle_gaps": gaps[:top]}
