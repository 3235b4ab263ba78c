"""The program's own spans and named scopes in a profiler trace.

While a ``repro.obs`` tracer is installed, the program records each of
its spans as a profiler annotation of the same name, on the profiler's
clock; and each step of an iteration runs under a ``jax.named_scope``.
This module reads both out of a ``.xplane.pb``:

* :func:`load` — :func:`bench.trace_reduce.load`'s dict, whose ``host``
  also keeps the program's spans (names starting with one of
  :data:`PROGRAM_SPANS`) and whose devices carry ``scopes``, the named
  scope of each operation in ``ops``, in the same order;
* :func:`idle_in` — for each span name, the seconds device 0 ran nothing
  within that name's intervals;
* :func:`scopes` — device seconds per program and named scope;
* :func:`reduce` — :func:`bench.trace_reduce.reduce`, whose idle gaps
  are then named by the program's spans too, plus ``idle_in`` (over the
  trace's own host events) and ``scopes``.

The operation events of a TPU v5e trace carry only their times in
``ProfileEvent.stats``.  Their
scope comes from their program's optimized HLO, which the trace keeps
in its ``/host:metadata`` plane (stat ``Hlo Proto``): an operation's
``metadata.op_name`` is a name stack such as
``jit(family_step)/vmap(objective)/dot_general``, and its scope is the
innermost component, with transformation wrappers such as ``vmap(...)``
taken off, that JAX did not add itself (a ``jit(...)`` boundary,
``while``/``body``, ``cond``/``branch_N_fun``, ``closed_call``); ``""``
where there is none.  (The op's event metadata holds the same path as
``tf_op``, but keyed by the op's text, which repeats across programs.)
The protobuf is read with the few lines of wire format below, which
need no generated code.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from bench import trace_reduce

#: Host events kept beside the benchmark's own ``bench.*`` annotations.
PROGRAM_SPANS = ("client.", "serve.", "solo.", "compile.", "path.")

_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
#: Components of a name stack that JAX adds, not a named scope.
_STRUCTURE = re.compile(
    r"^(p?jit\(.*\)|while|body|cond|branch_\d+_fun|closed_call)$")


# -- protobuf wire format ---------------------------------------------- #
def _varint(buf, i: int):
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message; a length-delimited value
    is a ``memoryview``, a varint an int, fixed widths are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i, val = i + (8 if wire == 1 else 4), None
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _hlo_op_names(hlo_proto) -> dict:
    """``{instruction name: metadata.op_name}`` of one ``HloProto``."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:                                    # hlo_module
            continue
        for f, comp in _fields(module):
            if f != 3:                                # computations
                continue
            for f, instr in _fields(comp):
                if f != 2:                            # instructions
                    continue
                name = op = None
                for g, v in _fields(instr):
                    if g == 1:
                        name = _text(v)
                    elif g == 7:                      # metadata
                        for h, w in _fields(v):
                            if h == 2:                # op_name
                                op = _text(w)
                if name is not None and op:
                    out[name] = op
    return out


def program_op_names(path: str) -> dict:
    """``{program event name: {instruction: op_name}}`` from the
    ``Hlo Proto`` stats of a trace's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:                                    # planes
            continue
        fields = list(_fields(plane))
        if not any(g == 2 and _text(v) == "/host:metadata"
                   for g, v in fields):
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:                                # stat_metadata
                key = name = None
                for h, v in _fields(entry):
                    if h == 1:
                        key = v
                    elif h == 2:
                        name = dict(_fields(v)).get(2)
                stat_names[key] = _text(name or b"")
        for g, entry in fields:
            if g != 4:                                # event_metadata
                continue
            for h, meta in _fields(entry):
                if h != 2:
                    continue
                name, protos = None, []
                for k, v in _fields(meta):
                    if k == 2:
                        name = _text(v)
                    elif k == 5:                      # stats
                        stat = dict(_fields(v))
                        if stat_names.get(stat.get(1)) == "Hlo Proto" \
                                and stat.get(6) is not None:
                            protos.append(stat[6])
                if name and protos:
                    out[name] = _hlo_op_names(protos[0])
    return out


# -- scopes ------------------------------------------------------------ #
def scope_of(op_name: str) -> str:
    """The named scope of an operation's ``op_name`` name stack."""
    for part in reversed(op_name.split("/")[:-1]):
        while (m := _WRAPPED.match(part)) is not None \
                and not part.startswith(("jit(", "pjit(")):
            part = m.group(1)
        if part and not _STRUCTURE.match(part):
            return part
    return ""


def load(logdir: str) -> dict:
    """:func:`bench.trace_reduce.load`'s dict with the program's spans
    in ``host`` and each device's ``scopes``."""
    import jax

    trace = trace_reduce.load(logdir)
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            trace["host"] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for line in plane.lines for e in line.events
                              if e.name.startswith(PROGRAM_SPANS)]
    op_names = program_op_names(path)
    for dev in trace["devices"]:
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        dev["scopes"] = []
        for name, s, _ in dev["ops"]:
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][0] if j >= 0 and mods[j][2] >= s else None
            op = op_names.get(prog, {}).get(trace_reduce.op_name(name), "")
            dev["scopes"].append(scope_of(op))
    return trace


def scopes(trace: dict, lo: float, hi: float) -> dict:
    """``{program: {scope: device seconds}}`` of the operations that ran
    inside ``[lo, hi]``, summed over devices; operations that hold
    others (a loop) are left out, as in ``top_ops``."""
    out: dict = {}
    for dev in trace["devices"]:
        mods = sorted(dev["modules"], key=lambda m: m[1])
        ops = [(n, s, e, sc) for (n, s, e), sc in
               zip(dev["ops"], dev.get("scopes") or [""] * len(dev["ops"]))]
        for _, s, e, scope in trace_reduce._leaf_ops(ops):
            if s >= lo and e <= hi:
                prog = out.setdefault(trace_reduce._owner(mods, s), {})
                prog[scope] = prog.get(scope, 0.0) + (e - s) / 1e9
    return out


def idle_in(trace: dict, lo: float, hi: float, spans) -> dict:
    """``{span name: seconds}`` in which device 0 ran nothing and a span
    of that name was open, inside ``[lo, hi]``."""
    if not trace["devices"]:
        return {}
    events = trace["devices"][0]["ops"] or trace["devices"][0]["modules"]
    busy = trace_reduce.union([(s, e) for _, s, e in events], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    by_name: dict = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((s, e))
    out = {}
    for name, ivs in by_name.items():
        total, j = 0, 0
        for s, e in trace_reduce.union(ivs, lo, hi):
            while j < len(idle) and idle[j][1] <= s:
                j += 1
            k = j
            while k < len(idle) and idle[k][0] < e:
                total += min(e, idle[k][1]) - max(s, idle[k][0])
                k += 1
        out[name] = total / 1e9
    return out


def reduce(trace: dict, lo: float, hi: float, extra_host=(),
           top: int = 10) -> dict:
    """:func:`bench.trace_reduce.reduce` plus ``idle_in`` and
    ``scopes``.  ``idle_in`` reads the trace's own host events only,
    which share the device's clock."""
    out = trace_reduce.reduce(trace, lo, hi, extra_host, top)
    out["idle_in"] = idle_in(trace, lo, hi, trace["host"])
    out["scopes"] = scopes(trace, lo, hi)
    return out
