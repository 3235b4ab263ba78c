"""Whether the answers of the timed path are correct.

Every answer of the window is compared with the plain reference
(:mod:`bench.reference`) on the same instance, objectives evaluated
alike at float32 accuracy and divided by the planted optimal value V*:

``obj_dev`` — the largest ``|V(x) − V(x_ref,k)| / V*`` over the answers,
where ``x_ref,k`` is the reference after as many iterations as the
answer took.  Two runs of Algorithm 1 in float32 that order their sums
differently part slowly (a coordinate near the greedy threshold, a τ
change on a last-bit comparison); the number bounds that parting.

``x_dev`` — the largest ``‖x − x_ref,k‖∞ / ‖x_ref,k‖∞`` over the answers.

``iters_short`` — the largest ``(k_ref − k) / k_ref`` over the answers,
``k_ref`` the iteration at which the reference itself meets the
request's stopping rule.  An answer that stops before the tolerance is
met reads high here, whatever it says of itself; ``obj_dev`` and
``x_dev`` cannot see it, since they follow the answer's own count.

``stop_rule_broken`` — answers that contradict their stopping rule: with
a tolerance (``tol > 0``) an answer has to say it converged; with a
fixed budget (``tol ≤ 0``) it has to have run ``max_iters`` iterations.
An exact count, limit 0.

A request that was never answered, or answered with a status other
than ``ok``, makes the run not correct.
"""
from __future__ import annotations

import math

import numpy as np


def reference(data, c: float, tol: float, max_iters: int,
              precision: str = "highest"):
    """``(x, iters, V(x))`` of the plain reference on ``data = (A, b)``."""
    import jax.numpy as jnp

    from bench import reference as ref

    A, b = data
    x, k, _ = ref.solve(A, b, jnp.float32(c), jnp.float32(tol),
                        jnp.int32(max_iters), precision=precision)
    return np.asarray(x), int(k), float(ref.objective(A, b, c, x))


def compare(pool, answers, tol: float, max_iters: int) -> dict:
    """``answers``: ``[(instance, x, iters, status, converged)]``; one
    instance's data on the device at a time."""
    import jax.numpy as jnp

    from bench import reference as ref

    by_instance: dict = {}
    broken = 0
    for i, x, k, _, converged in answers:
        by_instance.setdefault(i, []).append((x, k))
        broken += (not converged) if tol > 0 else (k != max_iters)
    dev, x_dev, short, ref_iters = [], [], [], {}
    for i, xs in sorted(by_instance.items()):
        data = pool.data(i)
        v_star = pool.v_star[i]
        stopped = reference(data, pool.c, tol, max_iters)
        k_ref = stopped[1]
        ref_iters[int(i)] = k_ref
        at_k = {k_ref: stopped}
        for x, k in xs:
            if k not in at_k:
                at_k[k] = reference(data, pool.c, -1.0, k)
            x_k, _, v_k = at_k[k]
            v = float(ref.objective(*data, pool.c, jnp.asarray(x)))
            if not math.isfinite(v):
                v = math.inf
            dev.append(abs(v - v_k) / v_star)
            x_dev.append(float(np.max(np.abs(x - x_k)))
                         / max(float(np.max(np.abs(x_k))), 1e-30))
            short.append((k_ref - k) / max(k_ref, 1))
        del data
    worst = (lambda v: max(v) if v else math.inf)
    return {"obj_dev": worst(dev), "x_dev": worst(x_dev),
            "iters_short": worst(short), "stop_rule_broken": broken,
            "ref_iters": ref_iters, "answers": len(dev)}


def verdict(numbers: dict, limits: dict, unanswered: int,
            not_ok: int) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for the result line."""
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    checks["stop_rule_broken"] = {"value": numbers["stop_rule_broken"],
                                  "limit": 0}
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    checks["status_not_ok"] = {"value": not_ok, "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok and numbers["answers"] > 0, checks
