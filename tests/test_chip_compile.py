"""The main path's kernels and chunk program compile for a TPU v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tiling, scalar stores to vector memory, more
memory than the chip has).  These tests compile for a v5e that is
described, not attached: the TPU compiler is installed with jax, so they
run on a CPU-only machine.  Nothing runs, so results are checked
elsewhere (interpret-mode tests here, ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.base import SolverConfig
from repro.kernels import flexa_prox, ssd_scan
from repro.solvers.batched import (BatchedProblemSpec, make_chunk_stepper,
                                   slab_alloc, slab_data_template)

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_gather_rows_compiles_at_fig1b_compaction_width(one_chip):
    # Packing the design of a fig1b path (m=2000 → 2048 lanes) into a
    # 1024-block capacity bucket.
    c = _compile(lambda s, i: flexa_prox.gather_rows(s, i), one_chip,
                 _s((10_000, 2048)), _s((1024,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_scatter_rows_compiles_at_fig1b_compaction_width(one_chip):
    c = _compile(lambda v, i, b: flexa_prox.scatter_rows(v, i, b), one_chip,
                 _s((1024, 2048)), _s((10_000,), jnp.int32),
                 _s((10_000, 2048)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dense_d", [False, True])
def test_best_response_compiles_on_a_multi_tile_grid(one_chip, dense_d):
    shape = (1000, 1000)                # 4 × 2 tiles, ragged at both edges
    if dense_d:
        fn = lambda x, g, d: flexa_prox.best_response(x, g, d, 0.5)
        shapes = (_s(shape),) * 3
    else:
        fn = lambda x, g: flexa_prox.best_response(x, g, 1.7, 0.5)
        shapes = (_s(shape),) * 2
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes).as_text()


def test_ssd_scan_compiles_at_mamba2_head_widths(one_chip):
    # mamba2-1.3b: 64 heads of width 64, state 128, chunk 256.
    Bt, H, S, P, N = 1, 64, 1024, 64, 128
    c = _compile(lambda x, dt, A, B, C: ssd_scan.ssd_scan(
        x, dt, A, B, C, chunk=256), one_chip,
        _s((Bt, H, S, P)), _s((Bt, H, S)), _s((H,)), _s((Bt, S, N)),
        _s((Bt, S, N)))
    assert "tpu_custom_call" in c.as_text()


def test_continuous_chunk_program_fits_one_chip_at_fig1b(one_chip):
    spec = BatchedProblemSpec(m=2000, n=10_000)
    cfg = SolverConfig(tol=1e-7, max_iters=20_000, tau_adapt=False)
    S = 8
    slab = jax.eval_shape(lambda: slab_alloc(spec, cfg, S))
    payload = (_s((S,)), _s((S, spec.n)), _s((S,), jnp.int32),
               _s((S, spec.n)), _s((S,)))
    args = (slab, _s((S,), jnp.bool_), _s((S,), jnp.bool_)) + payload
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    chunk = make_chunk_stepper(spec, cfg, 16)
    mem = chunk.lower(*placed).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used
    # The arguments hold one data slab (admitted rows are written into
    # it before the tick), not a second staged copy of it.
    data = _slab_data_bytes(spec, S)
    assert data < mem.argument_size_in_bytes < 2 * data


def _slab_data_bytes(spec, S: int) -> int:
    """Bytes of an S-slot slab's family data."""
    return S * sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(
                       slab_data_template(spec)))


def test_sparse_chunk_program_fits_one_chip_at_rcv1(one_chip, monkeypatch):
    """The served sparse cell's chunk program (rcv1-shaped designs in a
    2²¹-entry slab, S = 32) compiles for the chip, with the products'
    Pallas kernel, and fits it; its arguments hold the sparse slab (12
    bytes a stored entry), a fifth of one dense design of that shape."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")   # as on the chip
    spec = BatchedProblemSpec(m=20_242, n=47_236, layout="csc",
                              nnz_cap=2 ** 21)
    cfg = SolverConfig(tol=2e-3, max_iters=2000)
    S = 32
    slab = jax.eval_shape(lambda: slab_alloc(spec, cfg, S))
    payload = (_s((S,)), _s((S, spec.n)), _s((S,), jnp.int32),
               _s((S, spec.n)), _s((S,)))
    args = (slab, _s((S,), jnp.bool_), _s((S,), jnp.bool_)) + payload
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = make_chunk_stepper(spec, cfg, 16).lower(*placed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used
    data = _slab_data_bytes(spec, S)
    assert data == S * (12 * 2 ** 21 + 8 * 2 ** 21 // 1024 + 4 * spec.m)
    assert data < mem.argument_size_in_bytes < 2 * data
    assert data < 4 * spec.m * spec.n
