"""Fused FLEXA best-response / update Pallas TPU kernels.

The FLEXA hot spot is elementwise and *memory-bound*: per parameter tensor we
need  z = soft(x − g/d, c/d),  Eᵢ² = Σ(z−x)²,  and later  x ← x + γ·m·(z−x).
Unfused jnp materializes w, z, (z−x), (z−x)² … each a full HBM round trip.
The kernels here do:

* ``best_response``: one read of (x, g) → write z + per-tile Eᵢ² partials
  (one pass, fp32 accumulation in VMEM);
* ``apply_update``:  one read of (x, g) → write x_new, *recomputing* z in
  registers instead of re-reading it — for a memory-bound op, recomputing
  (2 reads + 1 write) strictly beats materializing (2r+1w then 2r+1w).

Tiles are (block_r × block_c) VMEM blocks with block_c a multiple of 128
(lane width) and block_r a multiple of 8 (sublane) — MXU is not involved,
the VPU streams at HBM bandwidth.  Tensors are padded/reshaped to 2-D by
``ops.py`` (zero padding is algebraically inert: soft(0−0)=0 contributes
nothing to z or Eᵢ²).

``batched_best_response`` / ``batched_apply_update`` accept a leading batch
dimension (B, R, C) with *per-instance* scalars c / d / γ·mask — the kernel
grid gains a batch axis and each instance reads its own (1, 1, 1) scalar
block, so one kernel launch can cover a whole request bucket of the batched
multi-instance engine.  Per-instance e2 partials reduce to a (B,)
error-bound vector.  Dispatch lives in ``ops.flexa_*_batched``; note the
batched *solver* (``repro.solvers.batched``) currently runs its prox chain
as plain vmapped jnp (XLA-fused; on CPU that is also what these ops
dispatch to) — these kernels are the TPU implementation of that hot path,
validated against the same oracle, not yet wired into the solver loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (256, 512)  # 256×512 fp32 ≈ 0.5 MB/operand — comfortably VMEM


def _tile_valid(shape, rows: int, cols: int):
    """Mask of the in-bounds entries of the current (br, bc) tile: edge
    tiles of a grid that does not divide (R, C) read padding, which must
    not reach a reduction."""
    br, bc = shape
    r = pl.program_id(0) * br + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = pl.program_id(1) * bc + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (r < rows) & (c < cols)


def _partial_tile(value):
    """An (8, 128) output tile holding ``value`` at [0, 0] and zeros
    elsewhere, so summing every tile adds each partial exactly once."""
    r = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    return jnp.where((r == 0) & (c == 0), value, 0.0)


def _br_kernel(x_ref, g_ref, d_ref, c_ref, z_ref, e2_ref, *, scalar_d: bool,
               rows: int, cols: int):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    d = d_ref[0, 0] if scalar_d else d_ref[...].astype(jnp.float32)
    c = c_ref[0, 0]
    w = x - g / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    z_ref[...] = z
    sq = jnp.where(_tile_valid(x.shape, rows, cols), (z - x) ** 2, 0.0)
    e2_ref[...] = _partial_tile(jnp.sum(sq, keepdims=True))


def best_response(x, g, d, c, *, block=DEFAULT_BLOCK, interpret: bool = False):
    """x, g: (R, C) 2-D views. d: scalar () or (R, C). c: scalar ().

    Returns (z fp32 (R,C), e2 fp32 scalar).  Scalars ride in SMEM; each
    tile's Eᵢ² partial lands in its own (8, 128) output tile.
    """
    R, C = x.shape
    br, bc = min(block[0], R), min(block[1], C)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    scalar_d = jnp.ndim(d) == 0
    d_arr = jnp.asarray(d, jnp.float32).reshape(1, 1) if scalar_d else d
    c_arr = jnp.asarray(c, jnp.float32).reshape(1, 1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    d_spec = smem if scalar_d else pl.BlockSpec((br, bc), lambda i, j: (i, j))
    z, e2p = pl.pallas_call(
        partial(_br_kernel, scalar_d=scalar_d, rows=R, cols=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            d_spec,
            smem,
        ],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((8, 128), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.float32),
            jax.ShapeDtypeStruct((grid[0] * 8, grid[1] * 128), jnp.float32),
        ],
        interpret=interpret,
    )(x, g, d_arr, c_arr)
    return z, jnp.sum(e2p)


def _apply_kernel(x_ref, g_ref, d_ref, c_ref, gm_ref, o_ref, *,
                  scalar_d: bool):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    d = d_ref[0, 0] if scalar_d else d_ref[...].astype(jnp.float32)
    c = c_ref[0, 0]
    gamma_mask = gm_ref[0, 0]            # γ·maskᵢ premultiplied by caller
    w = x - g / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    o_ref[...] = (x + gamma_mask * (z - x)).astype(o_ref.dtype)


def apply_update(x, g, d, c, gamma_mask, *, block=DEFAULT_BLOCK,
                 interpret: bool = False):
    """Fused  x + γ·m·(x̂(x) − x)  with in-register best-response recompute."""
    R, C = x.shape
    br, bc = min(block[0], R), min(block[1], C)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    scalar_d = jnp.ndim(d) == 0
    d_arr = jnp.asarray(d, jnp.float32).reshape(1, 1) if scalar_d else d
    c_arr = jnp.asarray(c, jnp.float32).reshape(1, 1)
    gm_arr = jnp.asarray(gamma_mask, jnp.float32).reshape(1, 1)

    d_spec = (pl.BlockSpec((1, 1), lambda i, j: (0, 0)) if scalar_d
              else pl.BlockSpec((br, bc), lambda i, j: (i, j)))
    return pl.pallas_call(
        partial(_apply_kernel, scalar_d=scalar_d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            d_spec,
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(x, g, d_arr, c_arr, gm_arr)


# ===================================================================== #
# Leading-batch-dimension variants (the multi-instance engine's bucket) #
# ===================================================================== #
def _expand_instance_scalar(v, B: int, name: str):
    """() or (B,) → (B, 1, 1) fp32 for per-instance (1,1,1) scalar blocks."""
    v = jnp.asarray(v, jnp.float32)
    if v.ndim == 0:
        v = jnp.broadcast_to(v, (B,))
    if v.shape != (B,):
        raise ValueError(f"{name} must be a scalar or (B,), got {v.shape}")
    return v.reshape(B, 1, 1)


def _norm_batched_d(d, x):
    """d may be (), (B,), or (B, R, C); returns (d_arr, d_spec, scalar_d)."""
    B = x.shape[0]
    scalar_d = jnp.ndim(d) <= 1
    if scalar_d:
        d_arr = _expand_instance_scalar(d, B, "d")
        d_spec = pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, 0, 0))
    else:
        if d.shape != x.shape:
            raise ValueError(f"dense d must match x {x.shape}, got {d.shape}")
        d_arr = d
        d_spec = None  # filled by caller with the tile spec
    return d_arr, d_spec, scalar_d


def _br_kernel_batched(x_ref, g_ref, d_ref, c_ref, z_ref, e2_ref, *,
                       scalar_d: bool):
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    d = d_ref[0, 0, 0] if scalar_d else d_ref[0].astype(jnp.float32)
    c = c_ref[0, 0, 0]
    w = x - g / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    z_ref[0] = z
    e2_ref[0, 0, 0] = jnp.sum((z - x) ** 2)


def batched_best_response(x, g, d, c, *, block=DEFAULT_BLOCK,
                          interpret: bool = False):
    """x, g: (B, R, C).  d: (), (B,) or (B, R, C).  c: () or (B,).

    Returns (z fp32 (B, R, C), e2 fp32 (B,)) — per-instance error bounds.
    """
    B, R, C = x.shape
    br, bc = min(block[0], R), min(block[1], C)
    grid = (B, pl.cdiv(R, br), pl.cdiv(C, bc))
    d_arr, d_spec, scalar_d = _norm_batched_d(d, x)
    if d_spec is None:
        d_spec = pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j))
    c_arr = _expand_instance_scalar(c, B, "c")

    z, e2p = pl.pallas_call(
        partial(_br_kernel_batched, scalar_d=scalar_d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
            pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
            d_spec,
            pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
            pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, R, C), jnp.float32),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        interpret=interpret,
    )(x, g, d_arr, c_arr)
    return z, jnp.sum(e2p, axis=(1, 2))


def _apply_kernel_batched(x_ref, g_ref, d_ref, c_ref, gm_ref, o_ref, *,
                          scalar_d: bool):
    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    d = d_ref[0, 0, 0] if scalar_d else d_ref[0].astype(jnp.float32)
    c = c_ref[0, 0, 0]
    gamma_mask = gm_ref[0, 0, 0]         # per-instance γ·mask scalar
    w = x - g / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0)
    o_ref[0] = (x + gamma_mask * (z - x)).astype(o_ref.dtype)


def batched_apply_update(x, g, d, c, gamma_mask, *, block=DEFAULT_BLOCK,
                         interpret: bool = False):
    """Fused batched  x + γᵢ·mᵢ·(x̂(x) − x)  over a (B, R, C) bucket.

    ``gamma_mask`` is () or (B,): each instance carries its own damping
    (independent γ/τ trajectories in the multi-instance engine).
    """
    B, R, C = x.shape
    br, bc = min(block[0], R), min(block[1], C)
    grid = (B, pl.cdiv(R, br), pl.cdiv(C, bc))
    d_arr, d_spec, scalar_d = _norm_batched_d(d, x)
    if d_spec is None:
        d_spec = pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j))
    c_arr = _expand_instance_scalar(c, B, "c")
    gm_arr = _expand_instance_scalar(gamma_mask, B, "gamma_mask")

    return pl.pallas_call(
        partial(_apply_kernel_batched, scalar_d=scalar_d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
            pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
            d_spec,
            pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, i, j: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, br, bc), lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, R, C), x.dtype),
        interpret=interpret,
    )(x, g, d_arr, c_arr, gm_arr)


# ===================================================================== #
# Compacted active-set gather/scatter (capacity-bucketed screening)     #
# ===================================================================== #
# These kernels move whole *block rows* between the full layout (N rows)
# and the compact layout (K = capacity rows).  The row index array rides
# in scalar-prefetch memory (`PrefetchScalarGridSpec`): BlockSpec index
# maps read it to pick each tile's source.  A TPU tile is 8 rows deep, so
# one row cannot be a block of its own: each grid step writes an (8, bc)
# output tile, and fetches, for each of its 8 rows, the aligned 8-row
# source tile holding that row (8 block specs over the same array), then
# copies the one row it needs.  Index −1 marks unused capacity (gather)
# or an inactive destination (scatter); −1 clamps to row 0 for the DMA
# and the kernel body masks the value, so padded work is read-only and
# algebraically inert.  Index vectors are padded with −1 to a multiple of
# 8; ragged row and column edges are clipped by the grid.  ``ops.py``
# zero-pads C to a lane multiple before dispatch (zero columns are inert
# for gather, scatter and the fused prox alike).
COMPACT_BLOCK_C = 512
_ROWS = 8                       # sublanes per TPU tile


def _pad_index(idx, mult: int = _ROWS):
    idx = jnp.asarray(idx, jnp.int32)
    pad = (-idx.shape[0]) % mult
    if pad:
        idx = jnp.concatenate([idx, jnp.full((pad,), -1, jnp.int32)])
    return idx


def _row_specs(bc: int):
    """The 8 block specs that fetch, for output row r of grid step g, the
    aligned source tile holding row ``idx[8g + r]``."""
    def spec(r):
        return pl.BlockSpec(
            (_ROWS, bc),
            lambda g, j, idx_ref: (
                jnp.maximum(idx_ref[g * _ROWS + r], 0) // _ROWS, j))
    return [spec(r) for r in range(_ROWS)]


def _picked_row(idx_ref, src_refs, r):
    """(index, row) for output row r of this grid step: the source row
    ``idx[8g + r]`` read out of its aligned tile."""
    i = idx_ref[pl.program_id(0) * _ROWS + r]
    return i, src_refs[r][pl.ds(jnp.maximum(i, 0) % _ROWS, 1), :]


def _gather_kernel(idx_ref, *refs):
    src_refs, out_ref = refs[:_ROWS], refs[_ROWS]
    for r in range(_ROWS):
        i, row = _picked_row(idx_ref, src_refs, r)
        out_ref[pl.ds(r, 1), :] = jnp.where(i >= 0, row.astype(jnp.float32),
                                            0.0)


def gather_rows(src, idx, *, block_c: int = COMPACT_BLOCK_C,
                interpret: bool = False):
    """src: (N, C) fp rows; idx: (K,) int32, −1 ⇒ zero row.

    Returns (K, C) fp32: ``out[k] = src[idx[k]]`` (or zeros).
    """
    N, C = src.shape
    K = idx.shape[0]
    idx = _pad_index(idx)
    bc = min(block_c, C)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0] // _ROWS, pl.cdiv(C, bc)),
        in_specs=_row_specs(bc),
        out_specs=pl.BlockSpec((_ROWS, bc), lambda g, j, idx_ref: (g, j)),
    )
    out = pl.pallas_call(
        _gather_kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], C), jnp.float32),
        interpret=interpret,
    )(idx, *([src] * _ROWS))
    return out[:K]


def _scatter_kernel(inv_ref, *refs):
    vals_refs, base_ref, out_ref = refs[:_ROWS], refs[_ROWS], refs[_ROWS + 1]
    for r in range(_ROWS):
        i, row = _picked_row(inv_ref, vals_refs, r)
        out_ref[pl.ds(r, 1), :] = jnp.where(
            i >= 0, row.astype(out_ref.dtype), base_ref[pl.ds(r, 1), :])


def scatter_rows(vals, inv, base, *, block_c: int = COMPACT_BLOCK_C,
                 interpret: bool = False):
    """vals: (K, C); inv: (N,) int32 (−1 ⇒ keep base); base: (N, C).

    Returns (N, C): ``out[i] = vals[inv[i]]`` where inv[i] ≥ 0 else
    ``base[i]``.  The scatter is expressed as a gather of the inverse
    permutation, so every output row is written exactly once.
    """
    N, C = base.shape
    inv = _pad_index(inv)
    bc = min(block_c, C)
    tile = pl.BlockSpec((_ROWS, bc), lambda g, j, inv_ref: (g, j))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(inv.shape[0] // _ROWS, pl.cdiv(C, bc)),
        in_specs=_row_specs(bc) + [tile],
        out_specs=tile,
    )
    return pl.pallas_call(
        _scatter_kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((N, C), base.dtype),
        interpret=interpret,
    )(inv, *([vals] * _ROWS), base)


def _compact_br_kernel(idx_ref, x_ref, g_ref, d_ref, c_ref, z_ref, e2_ref,
                       *, scalar_d: bool):
    i = pl.program_id(0)
    valid = (idx_ref[i] >= 0).astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) * valid
    g = g_ref[...].astype(jnp.float32) * valid
    d = d_ref[0, 0] if scalar_d else d_ref[...].astype(jnp.float32)
    c = c_ref[0, 0]
    w = x - g / d
    t = c / d
    z = jnp.sign(w) * jnp.maximum(jnp.abs(w) - t, 0.0) * valid
    z_ref[...] = z
    e2_ref[0, 0] = jnp.sum((z - x) ** 2)


def compact_best_response(x, g, d, c, idx, *,
                          block_c: int = COMPACT_BLOCK_C,
                          interpret: bool = False):
    """The compacted ``flexa_prox`` variant: gather + best response fused.

    x, g, (dense) d: (N, C) full-layout block rows; idx: (K,) int32 with
    −1 padding; scalar d () and c ().  One pass gathers the K active
    rows and soft-thresholds them — screened rows are never read, so
    device work scales with the capacity bucket, not the full width.

    Returns (z (K, C) fp32, e2 () fp32) — e2 sums only gathered rows
    (padding contributes exactly 0).
    """
    N, C = x.shape
    K = idx.shape[0]
    bc = min(block_c, C)
    grid = (K, pl.cdiv(C, bc))
    scalar_d = jnp.ndim(d) == 0
    d_arr = jnp.asarray(d, jnp.float32).reshape(1, 1) if scalar_d else d
    c_arr = jnp.asarray(c, jnp.float32).reshape(1, 1)
    gather_spec = pl.BlockSpec(
        (1, bc), lambda i, j, idx_ref: (jnp.maximum(idx_ref[i], 0), j))
    d_spec = (pl.BlockSpec((1, 1), lambda i, j, idx_ref: (0, 0))
              if scalar_d else gather_spec)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[gather_spec, gather_spec, d_spec,
                  pl.BlockSpec((1, 1), lambda i, j, idx_ref: (0, 0))],
        out_specs=[
            pl.BlockSpec((1, bc), lambda i, j, idx_ref: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j, idx_ref: (i, j)),
        ],
    )
    z, e2p = pl.pallas_call(
        partial(_compact_br_kernel, scalar_d=scalar_d), grid_spec=gs,
        out_shape=[
            jax.ShapeDtypeStruct((K, C), jnp.float32),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), x, g, d_arr, c_arr)
    return z, jnp.sum(e2p)
