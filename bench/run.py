#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<traffic>.json``) are found by name from
``BENCHMARK.json``.  The mix names a loop (``bench/loops/<loop>.py``)
that makes the cell's instances from ``--seed``, warms up, and drives the
program through its client for ``--seconds``.  With ``--trace 0`` the
last line of standard output holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace is taken and each per-layer metric is read
by its own reader, ``bench/metrics/<metric>.py``.  Either way the
answers of the window are then compared with the plain reference
(``bench/check.py``), and the numbers compared are printed with their
limits, last on standard error and last in the result line.

The run fails, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from
``bench/peaks.json``.  Further detail of a run goes to
``bench/out/<cell>.<seed>.<trace>.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                    # noqa: E402
import contextlib                                                  # noqa: E402
import gc                                                          # noqa: E402
import importlib                                                   # noqa: E402
import importlib.util                                              # noqa: E402
import json                                                        # noqa: E402
import math                                                        # noqa: E402
import shutil                                                      # noqa: E402
import sys                                                         # noqa: E402
from pathlib import Path                                           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoDevice(SystemExit):
    """The machine lacks what the cell needs; no result is printed."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cell_spec(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"unknown workload {name!r}; one of "
                     f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def read_metric(name: str, rec: dict):
    """Run ``bench/metrics/<name>.py``'s ``read(rec)``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def finite(v, big: float = 1.0e300):
    """JSON has no infinity: a value that never came reads as ``big``."""
    if isinstance(v, float) and not math.isfinite(v):
        return big if v > 0 else -big
    return v


class Run:
    """What a loop gets: the cell's files, the seed and window, and the
    hooks for tracing and for counting compiles."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, peak: dict):
        import jax

        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.tracing = seed, seconds, trace
        self.peak = peak
        self.now = time.perf_counter
        self.t_window = None
        self.profile_dir = OUT / f"trace.{cell['name']}.{seed}"
        self.profile_span = None        # (t_begin, t_end) perf_counter
        self._annotation = None
        self._events = {"cache_misses": 0, "cache_hits": 0, "traces": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    # -- compile counting --------------------------------------------- #
    def _on_event(self, event: str, **_) -> None:
        for key in ("cache_misses", "cache_hits"):
            if event == f"/jax/compilation_cache/{key}":
                self._events[key] += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self._events["traces"] += 1

    def compiles(self) -> dict:
        return dict(self._events)

    # -- window --------------------------------------------------------- #
    def window_start(self) -> float:
        """Marks the end of set-up; returns the window's start time."""
        self.t_window = self.now()
        self._events_at_start = self.compiles()
        return self.t_window

    def compiles_in_window(self) -> dict:
        now = self.compiles()
        return {k: now[k] - self._events_at_start[k] for k in now}

    # -- tracing -------------------------------------------------------- #
    def annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def trace_begin(self) -> None:
        if not self.tracing:
            return
        import jax
        shutil.rmtree(self.profile_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.profile_dir),
                                 profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()
        self.profile_span = (self.now(), None)

    def trace_end(self) -> None:
        if self._annotation is None:
            return
        import jax
        self.profile_span = (self.profile_span[0], self.now())
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.block_until_ready(jax.numpy.zeros(()))
        jax.profiler.stop_trace()

    def reduce_trace(self, host_spans=()) -> dict | None:
        """The traced slice, reduced (see ``bench/trace_reduce.py``).
        ``host_spans``: ``(name, t0, t1)`` on this process's
        ``perf_counter``, shifted onto the trace's clock."""
        if self.profile_span is None:
            return None
        from bench import trace_reduce

        raw = trace_reduce.load(str(self.profile_dir))
        win = [(s, e) for n, s, e in raw["host"] if n == "bench.window"]
        if not win:
            raise RuntimeError("the trace holds no bench.window span")
        lo, hi = win[0]
        t0 = self.profile_span[0]
        extra = [(n, lo + (a - t0) * 1e9, lo + (b - t0) * 1e9)
                 for n, a, b in host_spans]
        summary = trace_reduce.reduce(raw, lo, hi, extra)
        shutil.rmtree(self.profile_dir, ignore_errors=True)
        return summary


def device_check(chips: int, peaks: dict):
    """The devices the cell runs on and their peak entry, or NoDevice."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def load_cell(name: str, rehearsal: dict | None = None):
    """``(bench, cell, config, traffic)`` by the cell's name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = cell_spec(bench, name)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if rehearsal:
        config = {**config, **rehearsal.get("config", {})}
        traffic = {**traffic, **rehearsal.get("traffic", {})}
    return bench, cell, config, traffic


def start_jax(cell: dict, rehearsal: dict | None = None):
    """Compile cache on; ``(devices, peak entry)`` of the cell."""
    import jax
    from repro.launch.runtime import use_compile_cache

    cache_dir = use_compile_cache()
    # Every program goes to the cache, however quickly it compiled, so
    # that a second run in the checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if rehearsal:
        devs, peak = jax.devices()[:cell["chips"]], rehearsal["peak"]
    else:
        peaks = load_json(BENCH / "peaks.json")["devices"]
        devs, peak = device_check(int(cell["chips"]), peaks)
    log(f"# {len(devs)}x {devs[0].device_kind}; compile cache {cache_dir}")
    return devs, peak


def answer_window(cell, config, traffic, seed, seconds, trace, peak):
    """Set up, measure and free the program: ``(run, record, answers,
    pool)``, the program's memory peak read in between."""
    run = Run(cell, config, traffic, seed, seconds, trace, peak)
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")
    state = loop.setup(run)
    record = loop.window(run, state)
    record["setup_s"] = run.t_window - T_START
    answers, pool = record.pop("answers"), state["pool"]
    return run, record, answers, pool, lambda: loop.release(state)


def execute(argv=None, rehearsal: dict | None = None) -> dict:
    """One run; returns the result dict (printed by :func:`main`).

    ``rehearsal`` replaces the chip for a dress rehearsal on the CPU:
    ``{"config": {...}, "traffic": {...}, "peak": {...}}`` overrides,
    and no device check.  Its numbers are not metrics."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload, rehearsal)
    devs, peak = start_jax(cell, rehearsal)
    log(f"# cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    run, record, answers, pool, release = answer_window(
        cell, config, traffic, args.seed, args.seconds, bool(args.trace),
        peak)
    mem_peak = memory_peak(devs)
    release()
    gc.collect()

    from bench import check
    solver = traffic["solver"]
    numbers = check.compare(pool, answers, solver["tol"],
                            solver["max_iters"])
    not_ok = sum(1 for a in answers if a[3] != "ok")
    correct, checks = check.verdict(numbers, traffic["check"],
                                    record["unanswered"], not_ok)

    import jax
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    rec = {**record, "config": config, "traffic": traffic, "peak": peak}
    metrics, breakdown = {}, None
    if args.trace:
        rec["trace"] = tr = run.reduce_trace(record.get("host_spans", ()))
        for m in metrics_of(bench, cell["name"], "per_layer"):
            v = read_metric(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            info["busy_s"] = sum(tr["busy_s"]) / max(1, len(tr["busy_s"]))
            info["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["top_ops"],
                         "idle_gaps": tr["idle_gaps"]}
    else:
        values = {**record["e2e"], "setup_s": record["setup_s"]}
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": finite(values[m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["unanswered"] + not_ok, "metrics": metrics,
              "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": finite(float(v["value"])),
                            "limit": v["limit"]}
                        for k, v in checks.items()}

    OUT.mkdir(parents=True, exist_ok=True)
    detail = {k: v for k, v in rec.items()
              if k not in ("host_spans", "config", "peak")}
    detail.update(numbers=numbers, result=result)
    with open(OUT / f"{cell['name']}.{args.seed}.{args.trace}.json",
              "w") as f:
        json.dump(detail, f, default=str)
    for key in ("attempted", "unanswered", "compiles_in_window",
                "generator_late_p50_s", "generator_late_max_s", "drain_s",
                "iters_by_group", "items", "window_s", "setup_s",
                "latency_p90_s"):
        if key in record:
            log(f"# {key}: {record[key]!r}")
    log(f"# reference iters {numbers['ref_iters']}")
    log(f"# memory_peak_bytes {mem_peak}")
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    try:
        result = execute(argv)
    except NoDevice as e:
        log(f"# {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
