"""Open loop over sparse designs: ``poisson_open``'s schedule, client and
window, with the instances of ``bench/gen/text_sparse.py``.

Traffic keys as ``poisson_open``'s; ``nnz_mix`` holds the planted
support fractions.  Each request carries its own design, as host arrays
trimmed to its nnz when ``host_data`` is set.  The record adds what the
sparse cell's readers need: each instance's ``nnz``, and the window's
change of the program's admission counters ``nnz_stored`` and
``nnz_capacity`` (nonzeros admitted, and the nnz capacity of the slots
they occupied).
"""
from __future__ import annotations

from bench.gen import text_sparse
from bench.loops import poisson_open as open_loop


def setup(run) -> dict:
    # Fails at once where the program has no sparse layout.
    from repro.problems.sparse import CSCDesign  # noqa: F401
    from repro.client import SoloSpec

    t = run.traffic
    groups, per = list(t["nnz_mix"]), int(t["pool_per_group"])
    pool = text_sparse.make(run.config, [g for g in groups
                                         for _ in range(per)],
                            int(t["pool_key"]), run.seed)
    if t["host_data"]:
        pool = pool.to_host()
    problems = [pool.problem(i) for i in range(len(pool))]
    due, inst = open_loop.schedule(float(t["rate_per_s"]), run.seconds,
                                   len(groups), per, int(t["schedule_key"]))
    # Warm-up as poisson_open's: one request of the lightest group to the
    # end.  Every design of the pool shares one nnz bucket, so this
    # compiles every program a tick uses.
    client = open_loop._client(t)
    with run.annotate("bench.warmup"):
        ticket = client.submit(SoloSpec(problems[0]))
        give_up = run.now() + float(t["drain_limit_s"])
        while client.result(ticket, wait=False) is None \
                and run.now() < give_up:
            client.step()
    return {"pool": pool, "problems": problems, "due": due, "inst": inst,
            "client": client}


def window(run, st: dict) -> dict:
    tele = st["client"].telemetry
    stored, capacity = tele.nnz_stored, tele.nnz_capacity
    record = open_loop.window(run, st)
    record["nnz_stored"] = tele.nnz_stored - stored
    record["nnz_capacity"] = tele.nnz_capacity - capacity
    record["nnz"] = [st["pool"].nnz(i) for i in range(len(st["pool"]))]
    return record


release = open_loop.release
