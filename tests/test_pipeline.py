"""Pipeline-parallel + ZeRO-3 strategy correctness (8-device subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.configs.registry import get_reduced
    from repro.config.base import ShapeConfig
    from repro.distributed.sharding import Dist
    from repro.distributed.pipeline import pipeline_loss_fn
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T, io as IO

    mesh = make_mesh((4, 2), ("data", "model"))
    dist = Dist(mesh=mesh, dp_axes=("data",))
    cfg = get_reduced("yi-6b").replace(num_layers=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = IO.random_batch(cfg, ShapeConfig("t", "train", 32, 8))

    ref_loss, _ = T.loss_fn(cfg, params, batch)
    pp_loss, _ = jax.jit(lambda p, b: pipeline_loss_fn(
        cfg, p, b, dist, n_micro=4))(params, batch)

    # L=5 exercises the zero-layer padding path (5 % 4 != 0)
    cfg5 = get_reduced("yi-6b").replace(num_layers=5)
    params5 = T.init_params(cfg5, jax.random.PRNGKey(1))
    ref5, _ = T.loss_fn(cfg5, params5, batch)
    pp5, _ = jax.jit(lambda p, b: pipeline_loss_fn(
        cfg5, p, b, dist, n_micro=4))(params5, batch)

    g_ref = jax.grad(lambda p: T.loss_fn(cfg, p, batch)[0])(params)
    g_pp = jax.jit(jax.grad(lambda p: pipeline_loss_fn(
        cfg, p, batch, dist, n_micro=4)[0]))(params)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), g_ref, g_pp)

    # ZeRO-3 layout: dp over both axes — loss must equal the reference
    dz = Dist(mesh=mesh, dp_axes=("data", "model"))
    z_loss, _ = jax.jit(lambda p, b: T.loss_fn(
        cfg, p, b, mesh=mesh, dp_axes=dz.dp_axes))(params, batch)

    print(json.dumps({
        "ref": float(ref_loss), "pp": float(pp_loss),
        "ref5": float(ref5), "pp5": float(pp5),
        "max_grad_err": max(jax.tree_util.tree_leaves(errs)),
        "zero3": float(z_loss),
    }))
""")


@pytest.mark.slow
def test_pipeline_and_zero3_match_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SRC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["pp"] - rec["ref"]) < 5e-3          # bf16 schedule noise
    assert abs(rec["pp5"] - rec["ref5"]) < 5e-3        # padded-depth path
    assert rec["max_grad_err"] < 5e-2                  # bf16 grads
    assert abs(rec["zero3"] - rec["ref"]) < 5e-3
