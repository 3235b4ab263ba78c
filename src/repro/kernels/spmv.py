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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

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


def _tiles_kernel(val_ref, gi_ref, si_ref, win_ref, out_ref):
    hrow = lax.broadcasted_iota(jnp.int32, (_W, 128), 0)
    lrow = lax.broadcasted_iota(jnp.int32, (128, 128), 0)

    def tile(t, carry):
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
            parts = jnp.concatenate(_split3(a), axis=0)        # (3W, 128)
            onehot = (lrow == s_lo[s:s + 1, :]).astype(jnp.bfloat16)
            p = lax.dot_general(parts, onehot, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc = acc + p[:_W] + p[_W:2 * _W] + p[2 * _W:]
        out_ref[t] = acc
        return carry

    lax.fori_loop(0, val_ref.shape[0], tile, 0)


def tile_products(values, gidx, sidx, windows, *, interpret: bool = False):
    """Per-tile partial outputs ``out[t, s] += values[t, k]·windows[t,
    gidx[t, k]]`` over each tile's entries k (indices taken modulo
    :data:`BLOCK`).  ``values``, ``gidx``, ``sidx``: (T, 8, 128);
    ``windows``: (T, 16, 128); returns (T, 16, 128) float32.  T is a
    power of two (a capacity bucket over :data:`TILE`)."""
    nt = values.shape[0]
    step = min(TILES_PER_STEP, nt)
    ent = pl.BlockSpec((step, 8, 128), lambda i: (i, 0, 0))
    win = pl.BlockSpec((step, _W, 128), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _tiles_kernel,
        grid=(nt // step,),
        in_specs=[ent, ent, ent, win],
        out_specs=win,
        out_shape=jax.ShapeDtypeStruct((nt, _W, 128), jnp.float32),
        interpret=interpret,
    )(values, gidx, sidx, windows)
