"""On-chip smoke test of the FLEXA solver service's main path.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the paths that span four chips

One chip runs these phases in one process, at the paper's sizes (Fig. 1
Lasso instances, generated from ``--seed``):

* **precision** — the relative error of a float32 fig1b ``A @ x`` on
  the chip, at the default precision and as the solvers form it;
* **solo** — fig1b (m=2000, n=10⁴, 10% nnz) and fig1d (m=5000, n=10⁵,
  5% nnz) through ``FlexaClient().run(SoloSpec(...))`` for a fixed
  number of iterations, against the same solve on the host's CPU
  device (one iteration from the same point is held to the bound; see
  :func:`step_devs`); (V−V*)/V* uses the planted optimum;
* **serve** — 16 fig1b requests (distinct seeds and c) through the
  continuous backend, each against its solo solve on the chip;
* **path** — an 8-point compacted regularization path at fig1b size
  against the uncompacted path, with the gather/scatter Pallas kernels
  compiled for the chip;
* **http** — the ``repro.remote`` service on a loopback port of this
  process (one process holds the chip), whose answers must equal the
  in-process continuous ones.

``--chips 4`` runs only what exists across chips: the mesh serving
backend over four chips against continuous serving on one, and the
column-sharded ``pflexa`` solve at fig1d against serial FLEXA.

Any failed phase, error reply or result outside its bound exits
non-zero.  There is no CPU fallback: the run fails unless JAX's devices
are TPUs, and it refuses ``REPRO_KERNELS`` (which swaps the Pallas
kernels for other implementations).  Reference runs use the CPU device
explicitly.  Wall times include set-up (instance generation, transfer
and compilation).  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: One solo iteration under the full rule, chip against host CPU: fp32
#: reduction order differs between the two, nothing else may.
CHIP_VS_CPU_TOL = 1e-5
#: Served answers against solo solves — the repo's serve contract
#: (tol=1e-7, no τ adaptation).
SERVE_TOL = 1e-5
#: Compacted against uncompacted path, per λ-point.
PATH_TOL = 1e-5
#: One full-rule iteration, column-sharded against serial FLEXA.
PFLEXA_TOL = 1e-5

FIG1B = dict(m=2000, n=10_000, nnz_frac=0.10)
FIG1D = dict(m=5000, n=100_000, nnz_frac=0.05)
N_REQUESTS = 16


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def maxdev(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                                - np.asarray(b, np.float64))))


def lasso(size: dict, seed: int, c: float = 1.0):
    from repro.problems.lasso import nesterov_instance
    return nesterov_instance(c=c, seed=seed, **size)


def on_cpu(problem):
    """The same instance with its data on the host CPU device."""
    import jax
    from repro.problems.families import build_problem
    cpu = jax.devices("cpu")[0]
    arrays = tuple(jax.device_put(problem.data[k], cpu) for k in ("A", "b"))
    with jax.default_device(cpu):
        return build_problem(problem.family, arrays, problem.g_weight,
                             n=problem.n, block_size=problem.block_size,
                             g_kind=problem.g_kind)


def fixed_iters(iters: int):
    from repro.config.base import SolverConfig
    return SolverConfig(max_iters=iters, tol=-1.0, tau_adapt=False)


def serve_cfg():
    """The serve contract's configuration: served and solo answers agree
    because both converge to tol=1e-7 (without τ adaptation, fig1b takes
    thousands of iterations; ``max_iters`` is a backstop)."""
    from repro.config.base import SolverConfig
    return SolverConfig(tol=1e-7, max_iters=20_000, tau_adapt=False)


def requests(seed: int):
    return [lasso(FIG1B, seed + 1 + i, c=0.5 + 0.1 * i)
            for i in range(N_REQUESTS)]


# ------------------------------------------------------------------ #
# One chip                                                           #
# ------------------------------------------------------------------ #
def one_step(problem, x0, *, selection: str, method: str = "flexa",
             device=None):
    """One iteration of ``method`` from ``x0`` (fresh γ, τ) under the
    ``selection`` rule, on ``device`` (default: the chip)."""
    import dataclasses
    import jax
    from repro.client import FlexaClient, SoloSpec
    cfg = dataclasses.replace(fixed_iters(1), selection=selection)
    ctx = (jax.default_device(device) if device is not None
           else contextlib.nullcontext())
    with ctx:
        return FlexaClient(solver=cfg).run(
            SoloSpec(problem=problem, x0=x0, method=method))


def step_devs(problem_a, problem_b, x0, *, method_a="flexa",
              method_b="flexa", device_b=None) -> dict:
    """One step from ``x0`` on two sides, under the full (Jacobi) rule
    and under the greedy rule.

    The greedy rule keeps the blocks with Eᵢ ≥ ρ·max E.  Reduction order
    differs between the sides, so a block within float32 noise of that
    threshold can be kept on one side only; the trajectories then part
    (by about γ·ρ·max E per flip).  Under the full rule there is no
    threshold: that deviation is the one held to a bound."""
    out = {}
    for rule in ("full", "greedy"):
        a = one_step(problem_a, x0, selection=rule, method=method_a)
        b = one_step(problem_b, x0, selection=rule, method=method_b,
                     device=device_b)
        out[rule] = maxdev(a.x, b.x)
        out[f"{rule}_sel_frac"] = (a.history["sel_frac"][0],
                                   b.history["sel_frac"][0])
        out[f"{rule}_result"] = a
    return out


def phase_precision(seed: int) -> None:
    """What precision a float32 product of the fig1b design runs at on
    the chip: relative error of ``A @ x`` against float64 on the host, at
    the default precision (plain, and vmapped over two slots as the
    serving slab forms it) and through ``problems.base.mv``, which the
    solvers use."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.problems.base import mv
    A = lasso(FIG1B, seed).data["A"]
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(A.shape[1]),
                    jnp.float32)
    exact = np.asarray(A, np.float64) @ np.asarray(x, np.float64)

    def rel(y) -> float:
        return maxdev(y, exact) / float(np.max(np.abs(exact)))

    default = rel(jax.jit(jnp.matmul)(A, x))
    two = (jnp.stack([A, A]), jnp.stack([x, x]))    # a batch of one folds
    vmapped = rel(jax.jit(jax.vmap(jnp.matmul))(*two)[0])
    highest = rel(jax.jit(mv)(A, x))
    log(f"[precision] fig1b A@x rel_err default={default!r} "
        f"default_vmapped={vmapped!r} solver_mv={highest!r}")
    check(highest <= CHIP_VS_CPU_TOL,
          f"precision: solver products at {highest} > {CHIP_VS_CPU_TOL}")


def phase_solo(name: str, size: dict, iters: int, seed: int) -> None:
    """The solo solve on the chip and on the host CPU (see
    :func:`step_devs` for why one full-rule step carries the bound)."""
    import jax
    import numpy as np
    from repro.client import FlexaClient, SoloSpec
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    p = lasso(size, seed)
    cfg = fixed_iters(iters)
    chip = FlexaClient(solver=cfg).run(SoloSpec(problem=p))
    t_chip = time.perf_counter() - t0
    p_cpu = on_cpu(p)
    with jax.default_device(cpu):
        ref = FlexaClient(solver=cfg).run(SoloSpec(problem=p_cpu))
    d = step_devs(p, p_cpu, np.asarray(chip.x), device_b=cpu)
    rel = (chip.history["V"][-1] - p.v_star) / p.v_star
    rel_cpu = (ref.history["V"][-1] - p.v_star) / p.v_star
    log(f"[solo {name}] m={size['m']} n={size['n']} iters={chip.iters} "
        f"one_step_full_dev_vs_cpu={d['full']!r} "
        f"one_step_greedy_dev_vs_cpu={d['greedy']!r} "
        f"greedy_sel_frac_chip_cpu={d['greedy_sel_frac']!r} "
        f"trajectory_dev_vs_cpu={maxdev(chip.x, ref.x)!r} "
        f"rel_err_chip={rel!r} rel_err_cpu={rel_cpu!r} "
        f"wall_s_setup_inclusive={t_chip!r}")
    check(chip.iters == iters and ref.iters == iters,
          f"{name}: iteration counts {chip.iters}/{ref.iters} != {iters}")
    check(bool(np.isfinite(chip.history["V"]).all()),
          f"{name}: non-finite objective on the chip")
    check(d["full"] <= CHIP_VS_CPU_TOL,
          f"{name}: one step, chip vs CPU, {d['full']} > {CHIP_VS_CPU_TOL}")


def serve_all(backend: str, probs, serve):
    """Submit every request, stream all answers; (answers, telemetry
    snapshot, client)."""
    from repro.client import FlexaClient, SoloSpec
    client = FlexaClient(backend=backend, solver=serve_cfg(), serve=serve)
    tickets = [client.submit(SoloSpec(problem=p)) for p in probs]
    got = dict(client.stream())
    check(sorted(got) == sorted(tickets), f"{backend}: lost tickets")
    answers = [got[t] for t in tickets]
    for r in answers:
        check(r.status == "ok", f"{backend}: request status {r.status}")
    return answers, client.telemetry.snapshot(), client


def phase_serve(probs):
    from repro.client import FlexaClient, SoloSpec
    from repro.config.base import ServeConfig
    t0 = time.perf_counter()
    served, snap, _ = serve_all("continuous", probs,
                                ServeConfig(slab_capacity=8))
    t_serve = time.perf_counter() - t0
    # The solo reference is the compiled solo solver: the same iteration
    # as the default one, without a host round trip per iteration.
    solo = FlexaClient(solver=serve_cfg())
    devs = [maxdev(r.x, solo.run(SoloSpec(problem=p,
                                          method="flexa_compiled")).x)
            for r, p in zip(served, probs)]
    log(f"[serve continuous] requests={len(probs)} slab_capacity=8 "
        f"max_dev_vs_solo={max(devs)!r} "
        f"converged={sum(bool(r.converged) for r in served)} "
        f"iters={[int(r.iters) for r in served]} "
        f"wall_s_setup_inclusive={t_serve!r}")
    log("[serve continuous] telemetry " + json.dumps(snap, default=str))
    check(max(devs) <= SERVE_TOL,
          f"serve: max deviation vs solo {max(devs)} > {SERVE_TOL}")
    return served


def phase_path(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.client import FlexaClient, PathSpec
    from repro.kernels import ops
    from repro.solvers.compaction import make_plan
    p = lasso(FIG1B, seed)
    client = FlexaClient(solver=serve_cfg())
    t0 = time.perf_counter()
    comp = client.run(PathSpec(problem=p, n_points=8, compact=True))
    t_comp = time.perf_counter() - t0
    dense = client.run(PathSpec(problem=p, n_points=8, compact=False))
    dev = maxdev(comp.x, dense.x)
    # The compaction moves rows with the Pallas kernels, compiled for
    # the chip: at the support the path ended on, the programs hold a
    # Mosaic custom call.
    plan = make_plan(np.asarray(comp.x[-1]) != 0, block_size=1)
    A = p.data["A"]
    pack = jax.jit(plan.pack_columns).lower(A).as_text()
    unpack = jax.jit(plan.unpack_vector).lower(
        jnp.zeros(plan.n_compact, jnp.float32)).as_text()
    mode = ops._mode()
    log(f"[path compact] points=8 max_dev_vs_dense={dev!r} "
        f"capacity={plan.capacity} kernel_mode={mode} "
        f"gather_custom_call={'tpu_custom_call' in pack} "
        f"scatter_custom_call={'tpu_custom_call' in unpack} "
        f"row_iters={int(comp.row_iters)} "
        f"wall_s_setup_inclusive={t_comp!r}")
    check(mode == "pallas", f"path: kernel mode {mode!r}, not pallas")
    check("tpu_custom_call" in pack and "tpu_custom_call" in unpack,
          "path: gather/scatter did not lower to a Pallas kernel")
    check(dev <= PATH_TOL, f"path: compact vs dense {dev} > {PATH_TOL}")


def phase_http(probs, served) -> None:
    """The HTTP service in this process answers solo and batch requests;
    each answer equals the in-process continuous one."""
    from repro.client import BatchSpec, ClientConfig, FlexaClient, SoloSpec
    from repro.remote.server import InProcessServer
    cfg = serve_cfg()
    argv = ["--tol", repr(cfg.tol), "--max-iters", str(cfg.max_iters),
            "--no-tau-adapt", "--slab-capacity", "8",
            "--max-in-flight", "64"]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        server = InProcessServer(argv)
    try:
        client = FlexaClient(config=ClientConfig(
            backend="remote", remote_url=server.url,
            remote_tenant="chip-smoke", solver=cfg))
        t0 = time.perf_counter()
        tickets = [client.submit(SoloSpec(problem=probs[i])) for i in (0, 1)]
        tickets.append(client.submit(BatchSpec(problems=probs[2:4])))
        *solo, batch = [client.result(t) for t in tickets]
        t_http = time.perf_counter() - t0
        devs = [maxdev(solo[0].x, served[0].x),
                maxdev(solo[1].x, served[1].x),
                maxdev(batch.x, [served[2].x, served[3].x])]
        log(f"[http] solo=2 batch=2 max_dev_vs_continuous={max(devs)!r} "
            f"wall_s_setup_inclusive={t_http!r}")
        check(all(r.status == "ok" for r in solo)
              and all(s == "ok" for s in (batch.status or ["ok"])),
              "http: non-ok status in a reply")
        check(max(devs) == 0.0,
              f"http: answers differ from continuous by {max(devs)}")
    finally:
        with contextlib.redirect_stdout(out):
            server.begin_drain()
            code = server.join()
    check(code == 0, f"http: server exit code {code}")


def one_chip(seed: int) -> None:
    phase_precision(seed)
    phase_solo("fig1b", FIG1B, 200, seed)
    phase_solo("fig1d", FIG1D, 20, seed)
    probs = requests(seed)
    served = phase_serve(probs)
    phase_path(seed)
    phase_http(probs, served)


# ------------------------------------------------------------------ #
# Four chips                                                         #
# ------------------------------------------------------------------ #
def phase_mesh(seed: int) -> None:
    from repro.config.base import ServeConfig
    probs = requests(seed)
    t0 = time.perf_counter()
    mesh, snap, client = serve_all(
        "mesh", probs, ServeConfig(slab_capacity=4, mesh_devices=4))
    t_mesh = time.perf_counter() - t0
    single, _, _ = serve_all("continuous", probs,
                             ServeConfig(slab_capacity=8))
    dev = max(maxdev(a.x, b.x) for a, b in zip(mesh, single))
    per_dev = [d["live_iters"] for d in snap["mesh"]["per_device"]]
    slab = next(iter(client._backend._eng._slabs.values())).slab
    shards = slab.data[0].addressable_shards
    placed = sorted(str(s.device) for s in shards)
    log(f"[mesh] devices=4 requests={len(probs)} "
        f"max_dev_vs_continuous1={dev!r} live_iters_per_device={per_dev} "
        f"slab_shards={placed} shard_rows={[s.data.shape[0] for s in shards]} "
        f"wall_s_setup_inclusive={t_mesh!r}")
    log("[mesh] telemetry " + json.dumps(snap["mesh"], default=str))
    check(dev <= SERVE_TOL, f"mesh: vs continuous@1 {dev} > {SERVE_TOL}")
    check(len(per_dev) == 4 and min(per_dev) > 0,
          f"mesh: a device did no work: {per_dev}")
    check(len(set(placed)) == 4 and all(s.data.shape[0] == 4
                                        for s in shards),
          f"mesh: slab not spread over four devices: {placed}")


def phase_pflexa(seed: int) -> None:
    """Column-sharded against serial FLEXA at fig1d (see
    :func:`step_devs` for why one full-rule step carries the bound)."""
    import numpy as np
    from repro.client import FlexaClient, SoloSpec
    p = lasso(FIG1D, seed)
    client = FlexaClient(solver=fixed_iters(20))
    t0 = time.perf_counter()
    par = client.run(SoloSpec(problem=p, method="pflexa"))
    t_par = time.perf_counter() - t0
    ser = client.run(SoloSpec(problem=p))
    d = step_devs(p, p, np.asarray(par.x), method_a="pflexa")
    state_devs = len(d["full_result"].raw.state.x.sharding.device_set)
    rel = (par.history["V"][-1] - p.v_star) / p.v_star
    log(f"[pflexa fig1d] shards={par.raw.meta['n_shards']} "
        f"x_devices={state_devs} iters={par.iters} "
        f"one_step_full_dev_vs_serial={d['full']!r} "
        f"one_step_greedy_dev_vs_serial={d['greedy']!r} "
        f"greedy_sel_frac={d['greedy_sel_frac']!r} "
        f"trajectory_dev_vs_serial={maxdev(par.x, ser.x)!r} "
        f"rel_err={rel!r} wall_s_setup_inclusive={t_par!r}")
    check(par.raw.meta["n_shards"] == 4 and state_devs == 4,
          "pflexa: not sharded over four devices")
    check(d["full"] <= PFLEXA_TOL,
          f"pflexa: one step vs serial {d['full']} > {PFLEXA_TOL}")


def four_chips(seed: int) -> None:
    phase_mesh(seed)
    phase_pflexa(seed)


# ------------------------------------------------------------------ #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNELS"):
        print("chip_smoke: REPRO_KERNELS is set; the smoke runs the "
              "kernels the chip dispatches, unset it", file=sys.stderr)
        return 2
    from repro.launch.runtime import device_info, use_compile_cache
    use_compile_cache()
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {info['platform']!r})",
              file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {info['count']} "
              "device(s)", file=sys.stderr)
        return 2
    log(f"# device: {json.dumps(info)}")

    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"# all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
