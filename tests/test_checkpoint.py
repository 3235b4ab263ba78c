"""Checkpointing: atomicity, retention, async, elastic restore."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer


def tree():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,)), "s": jnp.asarray(3)}}


def test_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    t = tree()
    ck.save(7, t)
    restored, step = ck.restore(t)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_pointer_and_retention(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, tree())
    assert ck.latest_step() == 4
    kept = sorted(p.name for p in tmp_path.glob("step_????????"))
    assert kept == ["step_00000003", "step_00000004"]


def test_async_save(tmp_path):
    ck = Checkpointer(tmp_path, keep=3)
    ck.save_async(5, tree())
    ck.wait()
    assert ck.latest_step() == 5
    restored, _ = ck.restore(tree())
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree()["w"]))


def test_partial_write_is_invisible(tmp_path):
    """A crash mid-write (simulated .tmp dir) must not corrupt restore."""
    ck = Checkpointer(tmp_path, keep=3)
    ck.save(1, tree())
    # simulate a torn write
    (tmp_path / "step_00000002.tmp").mkdir()
    (tmp_path / "step_00000002.tmp" / "leaf_00000.npy").write_bytes(b"junk")
    assert ck.latest_step() == 1
    restored, step = ck.restore(tree())
    assert step == 1


def test_stale_pointer_falls_back(tmp_path):
    ck = Checkpointer(tmp_path, keep=3)
    ck.save(1, tree())
    ck.save(2, tree())
    (tmp_path / "LATEST").write_text("step_00000099")  # corrupt pointer
    assert ck.latest_step() == 2


ELASTIC_SRC = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.checkpoint import Checkpointer
    from repro.launch.mesh import make_mesh

    ckdir = sys.argv[1]
    mesh = make_mesh((8,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None)),
          "nested": {"b": NamedSharding(mesh, P()),
                     "s": NamedSharding(mesh, P())}}
    like = {"w": jnp.zeros((16, 4)), "nested": {"b": jnp.zeros((5,)),
            "s": jnp.asarray(0)}}
    ck = Checkpointer(ckdir)
    restored, step = ck.restore(like, shardings=sh)
    print(json.dumps({
        "step": step,
        "sum": float(jnp.sum(restored["w"])),
        "nshards": len(restored["w"].sharding.device_set),
    }))
""")


def test_elastic_restore_onto_different_topology(tmp_path):
    """Write on 1 device, restore 8-way sharded in a subprocess."""
    t = {"w": jnp.arange(64.0).reshape(16, 4),
         "nested": {"b": jnp.ones((5,)), "s": jnp.asarray(3)}}
    Checkpointer(tmp_path).save(11, t)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", ELASTIC_SRC, str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["step"] == 11
    assert rec["sum"] == float(np.arange(64.0).sum())
    assert rec["nshards"] == 8
