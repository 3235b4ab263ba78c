"""Open loop: requests arrive on a schedule fixed in advance, whatever the
service does with them.

Traffic keys: ``rate_per_s`` (offered rate), ``nnz_mix`` (the planted
support fractions, in equal shares), ``pool_per_group`` (instances per
fraction), ``pool_key`` (the fixed key of the base instances), ``schedule_key``,
``host_data`` (requests carry host arrays, as a tenant's request
arrives), ``backend``, ``serve`` (``ServeConfig`` fields), ``solver``
(``SolverConfig`` fields), ``drain_limit_s``.

The schedule: ``round(rate · seconds)`` arrivals whose gaps are the
quantiles of an exponential distribution at that rate, scaled to span
the window and shuffled, and each request's instance drawn alike, all
from the fixed ``schedule_key``: a trace replayed the same in every
run.  The seed draws the instances' data (the signs of rows and
columns), which changes what each request carries and not the work it
costs.  After ``--seconds`` no request arrives; the service drains what
is due, for at most ``drain_limit_s``.

Each request is timed from its due time to the moment the client holds
its answer.  ``latency_p75_s`` is the nearest-rank 75th percentile over
all requests, an unanswered one counting as infinitely late: the
highest percentile with ten requests beyond it in a window of 41 (the
90th is logged beside it);
``solves_per_s`` is the answers with status ``ok`` over the time from
the window's start to the last answer.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import pool as pools


def arrival_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in ``[0, seconds)``: exponential gap quantiles, shuffled."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def schedule(rate: float, seconds: float, groups: int, per_group: int,
             key: int):
    """``(due offsets, instance of each request)``."""
    rng = np.random.default_rng(key)
    due = arrival_offsets(rate, seconds, rng)
    group = np.arange(len(due)) % groups
    rng.shuffle(group)
    orders = [rng.permutation(per_group) for _ in range(groups)]
    seen = [0] * groups
    inst = []
    for g in group:
        inst.append(int(g) * per_group + int(orders[g][seen[g] % per_group]))
        seen[g] += 1
    return due, inst


def nearest_rank(values, q: float) -> float:
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def _client(t: dict):
    from repro.client import FlexaClient
    from repro.config.base import ServeConfig, SolverConfig

    return FlexaClient(backend=t["backend"],
                       solver=SolverConfig(**t["solver"]),
                       serve=ServeConfig(**t["serve"]))


def setup(run) -> dict:
    from repro.client import SoloSpec

    t = run.traffic
    groups, per = list(t["nnz_mix"]), int(t["pool_per_group"])
    pool = pools.make(run.config, [g for g in groups for _ in range(per)],
                      int(t["pool_key"]), run.seed)
    if t["host_data"]:
        pool = pool.to_host()
    problems = [pool.problem(i) for i in range(len(pool))]
    due, inst = schedule(float(t["rate_per_s"]), run.seconds, len(groups),
                         per, int(t["schedule_key"]))
    # Warm-up: the window's own client serves one request of the
    # lightest group to the end, which builds its slab and compiles and
    # runs every program a tick uses (admission, chunk, eviction).
    client = _client(t)
    with run.annotate("bench.warmup"):
        ticket = client.submit(SoloSpec(problems[0]))
        give_up = run.now() + float(t["drain_limit_s"])
        while client.result(ticket, wait=False) is None \
                and run.now() < give_up:
            client.step()
    return {"pool": pool, "problems": problems, "due": due, "inst": inst,
            "client": client}


def window(run, st: dict) -> dict:
    from repro.client import SoloSpec
    from repro.obs import trace as obs

    t = run.traffic
    client, problems = st["client"], st["problems"]
    due, inst = st["due"], st["inst"]
    n = len(due)
    tracer = obs.Tracer() if run.tracing else None
    prev = obs.set_tracer(tracer)
    tickets, late, done = {}, [], {}
    limit = run.seconds + float(t["drain_limit_s"])
    run.trace_begin()
    t0 = run.window_start()
    i = 0
    try:
        while True:
            now = run.now() - t0
            while i < n and due[i] <= now:
                with run.annotate("bench.submit"):
                    tk = client.submit(SoloSpec(problems[inst[i]]),
                                       arrival=t0 + float(due[i]))
                tickets[tk] = i
                late.append(now - float(due[i]))
                i += 1
                now = run.now() - t0
            if now > limit:
                break
            if client.pending:
                with run.annotate("bench.step"):
                    finished = client.step()
                t_done = run.now() - t0
                for tk in finished:
                    done[tickets[tk]] = (t_done, client.result(tk,
                                                               wait=False))
            elif i < n:
                with run.annotate("bench.wait"):
                    time.sleep(max(0.0, float(due[i]) - now))
            else:
                break
    finally:
        run.trace_end()
        obs.set_tracer(prev)

    lat = [done[j][0] - float(due[j]) if j in done else math.inf
           for j in range(n)]
    ok = [j for j in done if done[j][1].status == "ok"]
    t_last = max((done[j][0] for j in done), default=math.inf)
    waits = [r.queue_wait for r in client.telemetry.requests.values()
             if r.queue_wait is not None and r.arrival >= t0]
    groups = list(t["nnz_mix"])
    per = int(t["pool_per_group"])
    by_group: dict = {}
    for j in done:
        by_group.setdefault(str(groups[inst[j] // per]), []).append(
            done[j][1].iters)
    record = {
        "answers": [(inst[j], np.asarray(done[j][1].x), done[j][1].iters,
                     done[j][1].status, bool(done[j][1].converged))
                    for j in sorted(done)],
        "attempted": n,
        "unanswered": n - len(done),
        "requests": [{"due": float(due[j]), "instance": inst[j],
                      "done": done[j][0] if j in done else None,
                      "iters": done[j][1].iters if j in done else None}
                     for j in range(n)],
        "iters": [done[j][1].iters for j in sorted(done)],
        "iters_by_group": {g: sum(v) / len(v) for g, v in
                           sorted(by_group.items())},
        "queue_waits": waits,
        "generator_late_p50_s": float(np.median(late)) if late else None,
        "generator_late_max_s": max(late) if late else None,
        "drain_s": t_last - run.seconds,
        "compiles_in_window": run.compiles_in_window(),
        "latency_p90_s": nearest_rank(lat, 0.9),
        "e2e": {"latency_p75_s": nearest_rank(lat, 0.75),
                "solves_per_s": len(ok) / t_last if ok else 0.0},
    }
    if tracer is not None:
        admits: dict = {}
        for e in tracer.instants:
            if e.name == "serve.admit":
                admits[e.parent_id] = admits.get(e.parent_id, 0) + 1
        record["ticks"] = [(s.t1 - s.t0, admits.get(s.span_id, 0))
                           for s in tracer.spans if s.name == "serve.tick"]
        record["host_spans"] = [(s.name, s.t0, s.t1) for s in tracer.spans
                                if s.t1 is not None]
    return record


def release(st: dict) -> None:
    st["client"].close()
    st.pop("client")
    st.pop("problems")
