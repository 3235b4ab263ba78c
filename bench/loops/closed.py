"""Closed loop: one client submits its next work item when the last one
returns.

Traffic keys: ``nnz`` (fraction of the planted support), ``pool``
(instances, kept on the device and cycled in an order drawn from the
seed), ``pool_key`` (the fixed key of the base instances), ``solver``
(``SolverConfig`` fields), ``trace_seconds`` (how much of the window a
``--trace 1`` run traces), ``metric`` (the name the end-to-end time per
answer is reported under; ``solve_s`` where it is left out).

The window closes at the first answer after ``--seconds`` that ends a
pass over the pool, so every instance counts equally.  The time per
answer is the window over the answers in it.
"""
from __future__ import annotations

import numpy as np

from bench import pool as pools


def setup(run) -> dict:
    from repro.client import FlexaClient, SoloSpec
    from repro.config.base import SolverConfig

    t = run.traffic
    pool = pools.make(run.config, [t["nnz"]] * int(t["pool"]),
                      int(t["pool_key"]), run.seed)
    problems = [pool.problem(i) for i in range(len(pool))]
    order = np.random.default_rng(run.seed).permutation(len(pool))
    client = FlexaClient(solver=SolverConfig(**t["solver"]))
    with run.annotate("bench.warmup"):
        client.run(SoloSpec(problems[int(order[0])]))
    return {"pool": pool, "problems": problems, "order": order,
            "client": client}


def window(run, st: dict) -> dict:
    from repro.client import SoloSpec

    client, problems, order = st["client"], st["problems"], st["order"]
    passes = len(order)
    answers, done_at = [], []
    run.trace_begin()
    t0 = run.window_start()
    k, t = 0, 0.0
    while True:
        i = int(order[k % passes])
        with run.annotate("bench.solve"):
            r = client.run(SoloSpec(problems[i]))
        t = run.now() - t0
        answers.append((i, np.asarray(r.x), int(r.iters), r.status,
                        bool(r.converged)))
        done_at.append(t)
        k += 1
        if k % passes == 0 and t >= run.traffic["trace_seconds"]:
            run.trace_end()
        if k % passes == 0 and t >= run.seconds:
            break
    run.trace_end()
    iters = [a[2] for a in answers]
    return {
        "answers": answers,
        "attempted": k,
        "unanswered": 0,
        "items": k,
        "window_s": t,
        "iters": iters,
        "iters_by_instance": {int(a[0]): a[2] for a in answers},
        "done_at_s": done_at,
        "compiles_in_window": run.compiles_in_window(),
        "e2e": {run.traffic.get("metric", "solve_s"): t / k},
    }


def release(st: dict) -> None:
    st["client"].close()
    st.pop("client")
    st.pop("problems")
