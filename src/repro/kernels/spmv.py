"""Sparse-design product Pallas TPU kernel: one pass over a blocked
sparse design's tiles.

The design (``repro.problems.sparse.BlockedDesign``) stores its entries
in tiles of :data:`TILE` = 1024 entries, one (8, 128) vreg per array;
every entry of a tile lies in one (:data:`BLOCK` × :data:`BLOCK`) block
of A, so its row and its column are each one of :data:`BLOCK` = 2048
positions of a window.  For each tile the kernel

* gathers ``win[g]`` for the entries' gather indices ``g`` from the
  tile's (16, 128) window of the input vector: for each of the window's
  16 rows, a lane gather (``take_along_axis``) of that row, kept where
  the entry's index falls in it — on the vector unit, no MXU;
* multiplies by the entries' values, and
* scatter-adds the products into the tile's (16, 128) output window at
  the scatter indices ``s``, as a one-hot product on the MXU: for each
  sublane of 128 entries, ``P[h, l] += Σₖ [s_hi(k) = h]·wₖ · [s_lo(k) =
  l]``.  The one-hot factor is exact in bfloat16 and the float32 factor
  is split into three bfloat16 parts, so each product is exact and the
  sum accumulates in float32 — what ``Precision.HIGHEST`` computes for
  these operands, in half its MXU passes.

The windows are cut from and added back into the vectors outside the
kernel (``ops.blocked_product``), by one-hot products at
``Precision.HIGHEST``.  ``ops.py`` dispatches; ``ref.blocked_product_ref``
is the oracle.

**The gate.**  Each design brings a count of the tiles to compute: its
tiles that hold entries (a prefix: the tiles past its last block pair
hold none), or none at all for a design whose answer is thrown away (a
stopped or empty slot of a serving slab).  Every other tile is neither
multiplied nor read: its output window is written as zeros, and its grid
step asks for the block the pipeline already holds, so no DMA starts.
A live tile's arithmetic is the same whatever the count, so a gated
call gives the ungated call's outputs on its counted tiles bit for bit.
Under ``vmap`` the batched rule (``jax.custom_batching.custom_vmap``)
runs one kernel over the whole stack, grid (designs × tile steps), with
the counts prefetched as scalars; ``pallas_call``'s own batching would
loop over the designs one kernel call at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Rows (and columns) of A per block: a tile's window of a vector.
BLOCK = 2048
#: Stored entries per tile: one (8, 128) vreg.
TILE = 1024
#: Tiles per grid step.
TILES_PER_STEP = 8

_W = BLOCK // 128       # window rows of 128 lanes


def _split3(a):
    """``a`` (float32) as three bfloat16 parts whose float32 sum is
    ``a``."""
    a1 = a.astype(jnp.bfloat16)
    r = a - a1.astype(jnp.float32)
    a2 = r.astype(jnp.bfloat16)
    a3 = (r - a2.astype(jnp.float32)).astype(jnp.bfloat16)
    return a1, a2, a3


def _tiles_kernel(cnt_ref, src_ref, lo_ref, hi_ref, val_ref, gi_ref,
                  si_ref, win_ref, out_ref):
    del src_ref, lo_ref, hi_ref         # read by the index maps alone
    hrow = lax.broadcasted_iota(jnp.int32, (_W, 128), 0)
    lrow = lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    step = val_ref.shape[0]
    # tiles of this step that are computed: those before the design's count
    n = cnt_ref[pl.program_id(0)] - pl.program_id(1) * step

    def tile(t, carry):
        @pl.when(t < n)
        def _():
            gi = gi_ref[t] & (BLOCK - 1)
            si = si_ref[t] & (BLOCK - 1)
            win = win_ref[t]
            g_hi, g_lo = gi >> 7, gi & 127
            g = jnp.zeros((8, 128), jnp.float32)
            for h in range(_W):
                row = jnp.broadcast_to(win[h:h + 1, :], (8, 128))
                picked = jnp.take_along_axis(row, g_lo, axis=1,
                                             mode="promise_in_bounds")
                g = jnp.where(g_hi == h, picked, g)
            w = val_ref[t] * g
            s_hi, s_lo = si >> 7, si & 127
            acc = jnp.zeros((_W, 128), jnp.float32)
            for s in range(8):
                a = jnp.where(hrow == s_hi[s:s + 1, :], w[s:s + 1, :], 0.0)
                parts = jnp.concatenate(_split3(a), axis=0)    # (3W, 128)
                onehot = (lrow == s_lo[s:s + 1, :]).astype(jnp.bfloat16)
                p = lax.dot_general(parts, onehot,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                acc = acc + p[:_W] + p[_W:2 * _W] + p[2 * _W:]
            out_ref[t] = acc

        @pl.when(t >= n)
        def _():
            out_ref[t] = jnp.zeros((_W, 128), jnp.float32)

        return carry

    lax.fori_loop(0, step, tile, 0)


def _held_blocks(count, step: int):
    """Per design s of a stack, the input block its grid steps ask for:
    ``(src[s], clamp(i, lo[s], hi[s]))`` at tile step i.  A counted
    design reads its own steps up to its last counted one and then holds
    it; a design with nothing counted holds the block of the last counted
    design before it (the first block where there is none), so a gated
    step never starts a DMA."""
    S = count.shape[0]
    last = jnp.maximum(count - 1, 0) // step
    on = count > 0
    src = lax.cummax(jnp.where(on, jnp.arange(S, dtype=jnp.int32), -1))
    held = jnp.where(src >= 0, last[jnp.maximum(src, 0)], 0)
    return (jnp.maximum(src, 0), jnp.where(on, 0, held),
            jnp.where(on, last, held))


def _stack_products(values, gidx, sidx, windows, count, interpret: bool):
    """The kernel over a stack: (S, T, 8, 128) entries, (S, T, 16, 128)
    windows, (S,) int32 counts."""
    S, nt = values.shape[:2]
    step = min(TILES_PER_STEP, nt)

    def held(s, i, cnt, src, lo, hi):
        del cnt
        return src[s], jnp.minimum(jnp.maximum(i, lo[s]), hi[s]), 0, 0

    ent = pl.BlockSpec((None, step, 8, 128), held)
    win = pl.BlockSpec((None, step, _W, 128), held)
    out = pl.BlockSpec((None, step, _W, 128), lambda s, i, *_: (s, i, 0, 0))
    return pl.pallas_call(
        _tiles_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(S, nt // step),
            in_specs=[ent, ent, ent, win], out_specs=out),
        out_shape=jax.ShapeDtypeStruct((S, nt, _W, 128), jnp.float32),
        interpret=interpret,
    )(count, *_held_blocks(count, step), values, gidx, sidx, windows)


@functools.cache
def _gated_products(interpret: bool):
    """The products as a ``custom_vmap`` function of arrays with any
    number of leading stack dimensions (those of ``count``): a vmapped
    call adds one and calls itself again, so nested ``vmap``s still end
    in one kernel over the flattened stack."""

    @jax.custom_batching.custom_vmap
    def products(values, gidx, sidx, windows, count):
        lead = count.shape
        S = math.prod(lead)
        flat = [a.reshape((S,) + a.shape[len(lead):])
                for a in (values, gidx, sidx, windows)]
        out = _stack_products(*flat, count.reshape(S), interpret)
        return out.reshape(lead + out.shape[1:])

    @products.def_vmap
    def _(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return products(*args), True

    return products


def tile_products(values, gidx, sidx, windows, count, *,
                  interpret: bool = False):
    """Per-tile partial outputs ``out[t, s] += values[t, k]·windows[t,
    gidx[t, k]]`` over each tile's entries k (indices taken modulo
    :data:`BLOCK`), for the tiles t < ``count``; the rest are zeros and
    are not computed (the gate, module docstring).  ``values``,
    ``gidx``, ``sidx``: (T, 8, 128); ``windows``: (T, 16, 128);
    ``count``: int32 scalar; returns (T, 16, 128) float32.  T is a power
    of two (a capacity bucket over :data:`TILE`).  Under ``vmap`` one
    kernel runs over the whole stack."""
    return _gated_products(bool(interpret))(
        values, gidx, sidx, windows, jnp.asarray(count, jnp.int32))
