"""Entry-point process set-up: where the compile cache lives."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch import runtime

ROOT = Path(__file__).resolve().parent.parent


def test_importing_the_library_leaves_the_cache_alone():
    import repro.client  # noqa: F401
    import repro.remote.server  # noqa: F401
    assert jax.config.jax_compilation_cache_dir == \
        os.environ.get("JAX_COMPILATION_CACHE_DIR")


def test_default_cache_dir_is_fixed_and_gitignored():
    assert runtime.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def _cache_files(path: Path) -> set:
    return set(os.listdir(path)) if path.is_dir() else set()


def test_env_cache_dir_is_the_only_one_written(tmp_path):
    env_dir = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(env_dir),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    before = _cache_files(runtime.DEFAULT_CACHE_DIR)
    src = textwrap.dedent("""
        from repro.launch.runtime import use_compile_cache
        print(use_compile_cache())
        import jax, jax.numpy as jnp
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", src], env=env, text=True,
                         capture_output=True, timeout=120, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == str(env_dir)
    assert _cache_files(env_dir)
    assert _cache_files(runtime.DEFAULT_CACHE_DIR) == before
