"""Mesh construction: the one place the repo builds a ``jax.sharding.Mesh``.

Functions, not module-level constants, so importing this module never
touches jax device state — a dry run must set XLA_FLAGS before the first
jax device query.

Every mesh has ``Auto`` axes.  ``jax.make_mesh`` defaults to ``Explicit``
axes, under which a product over a sharded contraction dimension
(``A @ x`` with A column-sharded) and ``with_sharding_constraint`` are
refused; the repo's sharded code relies on the compiler's partitioner.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, names, devices=None):
    """A mesh of ``shape`` over the first ``prod(shape)`` of ``devices``
    (default: every device the process was given), all axes ``Auto``."""
    shape = tuple(int(s) for s in shape)
    devices = list(jax.devices() if devices is None else devices)
    n = math.prod(shape)
    if n > len(devices):
        raise ValueError(f"a {shape} mesh needs {n} devices; "
                         f"{len(devices)} given")
    return jax.make_mesh(shape, tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices[:n])


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 chips per pod ("data" × "model"); 2 pods in multi-pod mode."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU tests, small examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))
