"""The iteration carries the design product u = A·x − b (or Z·x) in its
state, so each iteration reads the design twice: one transpose product
for the gradient and one forward product for the objective.

* the pass count, read off the traced program (2 per iteration for every
  family and layout, in the solo step and in the continuous chunk body);
* the carried u equals a fresh product at the state's x after every
  driver (solo, compiled, batched, continuous and mesh slabs, freeze
  masks);
* the iteration against the three-pass iteration it replaced, kept here
  as the oracle: same iterations, stat, objective history and x.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.flexa as flexa
import repro.solvers.batched as B
from repro.config.base import ServeConfig, SolverConfig
from repro.core import selection, stepsize
from repro.core.surrogate import best_response, curvature
from repro.problems.group_lasso import nesterov_group_instance
from repro.problems.lasso import make_lasso, nesterov_instance
from repro.problems.logreg import random_logreg_instance
from repro.problems.sparse import capacity_bucket, is_sparse
from repro.problems.svm import random_svm_instance
from repro.serve import ContinuousSolverEngine, MeshServeEngine, SolveRequest

from test_serve_continuous import to_request as _dense_request
from test_sparse_designs import csc_from_dense


def _sparsified(p, keep=0.3, seed=0):
    """``p``'s design with most entries zeroed, as a sparse design."""
    A = np.asarray(p.data["A"], np.float32)
    A = A * (np.random.default_rng(seed).random(A.shape) < keep)
    return make_lasso(csc_from_dense(A), np.asarray(p.data["b"]),
                      float(p.g_weight), block_size=p.block_size)


PROBLEMS = {
    "lasso": lambda s=0: nesterov_instance(m=20, n=64, nnz_frac=0.15,
                                           c=1.0, seed=s),
    "group_lasso": lambda s=0: nesterov_group_instance(
        m=24, n_blocks=16, block_size=4, nnz_frac=0.25, c=1.0, seed=s),
    "logreg": lambda s=0: random_logreg_instance(m=30, n=48, nnz_frac=0.2,
                                                 c=0.5, seed=s),
    "svm": lambda s=0: random_svm_instance(m=30, n=40, nnz_frac=0.2,
                                           c=0.5, seed=s),
    "lasso_sparse": lambda s=0: _sparsified(PROBLEMS["lasso"](s), seed=s),
    "group_lasso_sparse": lambda s=0: _sparsified(
        PROBLEMS["group_lasso"](s), seed=s),
}


def to_request(p, **kw):
    """Problem -> SolveRequest, the design as the problem holds it
    (dense, or column-compressed)."""
    if is_sparse(p.data.get("A")):
        return SolveRequest(A=p.data["A"], b=np.asarray(p.data["b"]),
                            c=float(p.g_weight), block_size=p.block_size,
                            **kw)
    return _dense_request(p, **kw)


def _design(p):
    return p.data["A"] if "A" in p.data else p.data["Z"]


def _fresh_u(p, x):
    """The design product at ``x``, computed afresh."""
    return np.asarray(p.product(jnp.asarray(x, jnp.float32)))


def _assert_carried(u, fresh, err_msg=""):
    """``u`` equals the fresh product to float32 rounding."""
    scale = 1.0 + float(np.max(np.abs(fresh)))
    np.testing.assert_allclose(np.asarray(u), fresh, rtol=0,
                               atol=1e-5 * scale, err_msg=err_msg)


# ------------------------------------------------------------------ #
# Pass count                                                          #
# ------------------------------------------------------------------ #
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(j, "eqns"):
                yield j
            elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                yield j.jaxpr


def _design_products(jaxpr, p, skip=()):
    """Products that read ``p``'s design in ``jaxpr``, nested programs
    included (but not the bodies of ``skip`` primitives, nor a kernel's
    own body): a ``dot_general`` with an (m, n) or (n, m) operand on a
    dense design; a Pallas tile kernel or an oracle ``scatter-add`` over
    the stored entries on a sparse one."""
    D = _design(p)
    m, n = D.shape
    L = capacity_bucket(D.capacity, m, n) if is_sparse(D) else None
    count = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        shapes = [tuple(getattr(v.aval, "shape", ())) for v in eqn.invars]
        if L is None and name == "dot_general":
            count += any(len(s) >= 2 and sorted(s[-2:]) == sorted((m, n))
                         for s in shapes)
        elif L is not None and name == "pallas_call":
            count += any(len(s) >= 3 and math.prod(s[-3:]) == L
                         for s in shapes)
        elif L is not None and name == "scatter-add":
            count += any(len(s) >= 1 and s[-1] == L for s in shapes)
        if name in skip or name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn):
            count += _design_products(sub, p, skip)
    return count


PASS_CASES = [("lasso", "ref"), ("group_lasso", "ref"), ("logreg", "ref"),
              ("svm", "ref"), ("lasso_sparse", "ref"),
              ("lasso_sparse", "interpret"), ("group_lasso_sparse", "ref"),
              ("group_lasso_sparse", "interpret")]


@pytest.mark.parametrize("driver", ["solo", "chunk"])
@pytest.mark.parametrize("case,kernels", PASS_CASES)
def test_an_iteration_reads_the_design_twice(case, kernels, driver,
                                             monkeypatch):
    """One transpose product (the gradient, from the carried u) and one
    forward product (the objective's, at the new iterate) per iteration;
    a third product, the gradient's own A·x, would count 3."""
    p = PROBLEMS[case]()
    cfg = SolverConfig()
    if driver == "solo":
        state = flexa.init_state(p, jnp.zeros(p.n), cfg)
        step = flexa.make_step(p, cfg)
        monkeypatch.setenv("REPRO_KERNELS", kernels)   # read when traced
        jaxpr = jax.make_jaxpr(step)(state)
        assert _design_products(jaxpr.jaxpr, p) == 2
        return
    spec = B.BatchedProblemSpec.of(p)
    S = 2
    args = (jax.eval_shape(lambda: B.slab_alloc(spec, cfg, S)),
            jnp.ones((S,), bool), jnp.zeros((S,), bool),
            jnp.zeros((S,), jnp.float32), jnp.zeros((S, p.n)),
            jnp.zeros((S,), jnp.int32), jnp.ones((S, p.n)),
            jnp.full((S,), cfg.tol))
    chunk = B.make_chunk_stepper(spec, cfg, 4)
    monkeypatch.setenv("REPRO_KERNELS", kernels)
    jaxpr = jax.make_jaxpr(chunk)(*args)
    # the admission splice (under a cond) computes each row's first u
    # and column norms; the iterations are the loop outside it
    assert _design_products(jaxpr.jaxpr, p, skip=("cond",)) == 2


# ------------------------------------------------------------------ #
# The carried product stays the product at x                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("case", sorted(PROBLEMS))
def test_solo_drivers_carry_the_product_at_x(case):
    p = PROBLEMS[case]()
    cfg = SolverConfig(max_iters=60, tol=-1.0)
    x0 = 0.1 * jnp.ones((p.n,), jnp.float32)
    r = flexa.solve(p, x0=x0, cfg=cfg)
    assert r.iters == 60
    _assert_carried(r.state.u, _fresh_u(p, r.state.x), "solve")
    rc = flexa.solve_compiled(p, x0=x0, cfg=cfg)
    _assert_carried(rc.state.u, _fresh_u(p, rc.state.x), "solve_compiled")
    # the first u of a warm start is the product at x0, not −b
    st = flexa.init_state(p, x0, cfg)
    _assert_carried(st.u, _fresh_u(p, x0), "init_state")


@pytest.mark.parametrize("case", ["lasso", "logreg", "lasso_sparse"])
def test_wave_run_carries_the_product_with_a_freeze_mask(case):
    """The batched run-to-convergence program, with instances that stop
    at different iterations (frozen while the others run) and a freeze
    mask on some coordinates."""
    probs = [PROBLEMS[case](s) for s in range(3)]
    n = probs[0].n
    active = np.ones((3, n), np.float32)
    active[1, ::3] = 0.0
    cfg = SolverConfig(max_iters=400, tol=1e-4)
    r = B._solve_batched(probs, cfg=cfg, active=active)
    assert len(set(np.asarray(r.iters).tolist())) > 1
    for i, p in enumerate(probs):
        _assert_carried(np.asarray(r.state.u)[i],
                        _fresh_u(p, np.asarray(r.state.x)[i]),
                        f"instance {i}")


def test_solo_solve_with_an_active_mask_carries_the_product():
    p = PROBLEMS["lasso"]()
    active = np.ones(p.n, np.float32)
    active[::2] = 0.0
    x0 = 0.05 * jnp.ones((p.n,), jnp.float32)
    r = flexa.solve(p, x0=x0, cfg=SolverConfig(max_iters=50, tol=-1.0),
                    active=active)
    x = np.asarray(r.state.x)
    np.testing.assert_array_equal(x[::2], 0.05 * np.ones(p.n // 2,
                                                         np.float32))
    _assert_carried(r.state.u, _fresh_u(p, x))


def _check_slab(slab, spec, err_msg):
    """Every slot of a slab (live, stopped or empty) carries the product
    of its own data at its own x."""
    for s in range(slab.capacity):
        data = tuple(jax.tree_util.tree_map(lambda a: a[s], d)
                     for d in slab.data)
        p = B.family_problem(data, slab.c[s], spec)
        _assert_carried(np.asarray(slab.state.u)[s],
                        _fresh_u(p, np.asarray(slab.state.x)[s]),
                        f"{err_msg}, slot {s}")


@pytest.mark.parametrize("case", ["lasso", "group_lasso", "svm",
                                  "lasso_sparse"])
def test_continuous_slab_carries_the_product(case):
    """Across admissions (a warm start and a freeze mask among them),
    slots that converge mid-chunk and are frozen, evictions and
    backfills: after every tick each slot's u is the product at its x,
    and a slot that neither runs nor is admitted keeps its u bitwise."""
    probs = [PROBLEMS[case](s) for s in range(5)]
    n = probs[0].n
    kw = {1: {"x0": 0.1 * np.ones(n, np.float32)},
          3: {"active_mask": (np.arange(n) % 4 != 0).astype(np.float32)}}
    reqs = [to_request(p, **kw.get(i, {})) for i, p in enumerate(probs)]
    cfg = SolverConfig(max_iters=500, tol=1e-4)
    eng = ContinuousSolverEngine(
        cfg, ServeConfig(slab_capacity=2, chunk_iters=7))
    ids = [eng.submit(r) for r in reqs]
    (slot_slab,) = eng._slabs.values()
    ticks = 0
    while eng.pending:
        before = np.asarray(slot_slab.slab.state.u)
        idle = ~slot_slab.active.copy()
        eng.step()
        ticks += 1
        after = slot_slab.slab
        _check_slab(after, slot_slab.spec, f"tick {ticks}")
        for s in np.flatnonzero(idle & ~slot_slab.active):
            np.testing.assert_array_equal(np.asarray(after.state.u)[s],
                                          before[s])
    assert {r["req_id"] for r in eng.audit} == set(ids)
    assert len({r["evict_tick"] for r in eng.audit}) > 1


def test_mesh_slab_on_one_device_carries_the_product():
    probs = [PROBLEMS["lasso"](s) for s in range(4)]
    eng = MeshServeEngine(SolverConfig(max_iters=300, tol=1e-4),
                          ServeConfig(slab_capacity=2, chunk_iters=9,
                                      mesh_devices=1))
    for p in probs:
        eng.submit(to_request(p))
    (slot_slab,) = eng._slabs.values()
    while eng.pending:
        eng.step()
        _check_slab(slot_slab.slab, slot_slab.spec, "mesh")


MESH_SRC = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    import repro.solvers.batched as B
    from repro.config.base import ServeConfig, SolverConfig
    from repro.problems.lasso import nesterov_instance
    from repro.serve import MeshServeEngine, SolveRequest

    probs = [nesterov_instance(m=20, n=64, nnz_frac=0.15, c=1.0, seed=s)
             for s in range(6)]
    eng = MeshServeEngine(SolverConfig(max_iters=300, tol=1e-4),
                          ServeConfig(slab_capacity=1, chunk_iters=9,
                                      mesh_devices=2))
    for i, p in enumerate(probs):
        eng.submit(SolveRequest(
            A=np.asarray(p.data["A"]), b=np.asarray(p.data["b"]),
            c=float(p.g_weight),
            x0=None if i % 2 else 0.1 * np.ones(p.n, np.float32)))
    (slot_slab,) = eng._slabs.values()
    worst = 0.0
    while eng.pending:
        eng.step()
        slab = slot_slab.slab
        for s in range(slab.capacity):
            data = tuple(jax.tree_util.tree_map(lambda a: a[s], d)
                         for d in slab.data)
            p = B.family_problem(data, slab.c[s], slot_slab.spec)
            fresh = np.asarray(p.product(slab.state.x[s]))
            err = np.max(np.abs(np.asarray(slab.state.u)[s] - fresh))
            worst = max(worst, float(err / (1.0 + np.abs(fresh).max())))
    print(json.dumps({"devices": len(jax.devices()), "worst": worst,
                      "served": len(eng.audit)}))
""")


def test_mesh_stepper_on_two_virtual_devices_carries_the_product():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    out = subprocess.run([sys.executable, "-c", MESH_SRC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 2 and rec["served"] == 6
    assert rec["worst"] <= 1e-5


# ------------------------------------------------------------------ #
# Against the three-pass iteration                                    #
# ------------------------------------------------------------------ #
def three_pass_iteration(problem, cfg, tau_base, state, active=None):
    """Algorithm 1 as it ran before the product was carried: the
    gradient recomputes A·x, and the objective's product at the new
    iterate is thrown away (``u`` passes through unread)."""
    x = state.x
    tau = tau_base * state.tau_scale
    grad = problem.grad_f(x)
    d = curvature(problem, tau, cfg.surrogate)
    if active is not None:
        active = jnp.asarray(active, jnp.float32)
        active_b = active if problem.block_size == 1 \
            else problem.blockify(active)[:, 0]
    if cfg.inexact_alpha1 > 0 and problem.block_size > 1:
        zhat, cert = best_response(problem, x, grad, d, inner_iters=5,
                                   eps=0.0)
    else:
        zhat = best_response(problem, x, grad, d)
        cert = jnp.asarray(0.0)
    E = problem.block_norms(zhat - x)
    if active is not None:
        E = E * active_b
    M = jnp.max(E)
    if selection.needs_key(cfg.selection) and not cfg.jacobi:
        key, sub = jax.random.split(state.key)
    else:
        key, sub = state.key, state.key
    mask_b = selection.make_mask(E, cfg, sub, state.k, M=M)
    if active is not None:
        mask_b = mask_b * active_b
    mask = mask_b if problem.block_size == 1 \
        else jnp.repeat(mask_b, problem.block_size)
    xnew = x + state.gamma * mask * (zhat - x)
    v_new = problem.v(xnew)
    can_change = state.n_tau_changes < flexa.MAX_TAU_CHANGES
    adapt = bool(cfg.tau_adapt)
    increased = (v_new > state.v_prev) & can_change & adapt
    consec = jnp.where(v_new > state.v_prev, 0, state.consec_dec + 1)
    halve = (consec >= cfg.tau_patience) & can_change & adapt
    tau_scale = jnp.where(increased, state.tau_scale * cfg.tau_grow,
                          state.tau_scale)
    tau_scale = jnp.where(halve, tau_scale * cfg.tau_shrink, tau_scale)
    consec = jnp.where(halve, 0, consec)
    n_changes = state.n_tau_changes + increased.astype(jnp.int32) \
        + halve.astype(jnp.int32)
    step_err = jnp.abs(zhat - x)
    if active is not None:
        step_err = step_err * active
    stat = jnp.max(step_err)
    new_state = state._replace(
        x=xnew, gamma=stepsize.gamma_next(state.gamma, cfg.theta),
        tau_scale=tau_scale, v_prev=v_new, consec_dec=consec,
        n_tau_changes=n_changes, k=state.k + 1, stat=stat, key=key)
    info = {"V": v_new, "stat": stat, "E_max": M,
            "sel_frac": jnp.mean(mask_b), "gamma": state.gamma,
            "tau_scale": tau_scale, "inexact_cert": cert}
    return new_state, info


ORACLE_CASES = [
    ("lasso", {}), ("lasso_sparse", {}), ("group_lasso", {}),
    ("group_lasso_sparse", {}),
    ("group_lasso", {"surrogate": "newton_cg", "inexact_alpha1": 0.5}),
    ("group_lasso_sparse", {"surrogate": "newton_cg",
                            "inexact_alpha1": 0.5}),
    ("logreg", {}), ("svm", {})]


@pytest.mark.parametrize("tau_adapt", [True, False])
@pytest.mark.parametrize("case,opts", ORACLE_CASES,
                         ids=[f"{c}-{'inexact' if o else 'exact'}"
                              for c, o in ORACLE_CASES])
def test_iteration_matches_the_three_pass_iteration(case, opts, tau_adapt,
                                                    monkeypatch):
    """Over a fixed budget the iteration and the three-pass one give the
    same objective and stat histories and the same x, to float32
    rounding: the carried product changes where A·x comes from, and the
    compiler may round two products of one A·x apart, nothing else."""
    p = PROBLEMS[case]()
    cfg = SolverConfig(max_iters=150, tol=-1.0, tau_adapt=tau_adapt,
                       **opts)
    new = flexa.solve(p, cfg=cfg)
    monkeypatch.setattr(flexa, "flexa_iteration", three_pass_iteration)
    old = flexa.solve(p, cfg=cfg)
    assert new.iters == old.iters == 150
    np.testing.assert_allclose(new.history["stat"], old.history["stat"],
                               rtol=0, atol=1e-5 * old.history["stat"][0])
    np.testing.assert_allclose(new.history["V"], old.history["V"],
                               rtol=1e-6)
    x = np.asarray(old.x)
    np.testing.assert_allclose(np.asarray(new.x), x, rtol=0,
                               atol=1e-6 * (1.0 + np.abs(x).max()))


@pytest.mark.parametrize("tau_adapt", [True, False])
@pytest.mark.parametrize("case", ["lasso", "lasso_sparse"])
def test_lasso_runs_to_tolerance_stop_where_they_did(case, tau_adapt,
                                                     monkeypatch):
    """Run to a tolerance, the Lasso stops at the iteration the
    three-pass iteration stopped at, with the same answer."""
    p = PROBLEMS[case]()
    cfg = SolverConfig(max_iters=1000, tol=1e-6, tau_adapt=tau_adapt)
    new = flexa.solve(p, cfg=cfg)
    monkeypatch.setattr(flexa, "flexa_iteration", three_pass_iteration)
    old = flexa.solve(p, cfg=cfg)
    assert new.converged and new.iters == old.iters
    np.testing.assert_allclose(new.history["V"], old.history["V"],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.x), np.asarray(old.x),
                               atol=1e-6)
