"""Process set-up shared by the entry points (``chip_smoke.py``,
``python -m repro.remote.server``, ``benchmarks/*.py``).

Nothing here runs when the library is imported: an entry point calls
:func:`use_compile_cache` before its first compile, and prints
:func:`device_banner` next to anything it times, so a CPU timing is never
read as a chip timing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Where compiled programs are kept when ``JAX_COMPILATION_CACHE_DIR`` is
#: not set: a fixed directory of the checkout (gitignored).  The path is
#: part of the cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and no other directory is set here.  Otherwise the cache
    lives in :data:`DEFAULT_CACHE_DIR`.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """The device the process computes on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_banner() -> str:
    d = device_info()
    return f"# device: platform={d['platform']} kind={d['kind']} " \
           f"count={d['count']}"
