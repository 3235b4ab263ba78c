"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The SSD ("state-space duality") insight is that the selective-state
recurrence factors into *matmuls* over chunks plus a tiny inter-chunk
recurrence — exactly the shape the TPU MXU wants (the hardware adaptation:
the GPU kernel's warp-level scan becomes chunk-local dense algebra here).

Grid = (batch, heads, n_chunks) with the chunk dimension innermost and
sequential ("arbitrary"); the (N × P) state lives in VMEM scratch and is
carried across chunk steps, reset at chunk 0 of each (b, h) program.

The kernel takes a head-major layout — x (Bt, H, S, P), dt (Bt, H, nc, L)
— so that a block's two minor dimensions are (chunk, P): the sequence
chunk is the 8-deep tiled dimension, as the TPU's (8, 128) tiling needs.
``ops.ssd_scan`` transposes to and from the model's (Bt, S, H, P).

Per chunk of length L (all in fp32 in VMEM):
    s       = cumsum(dt·A)                       (L,)
    G       = C·Bᵀ                               (L, L)   MXU
    W       = G ⊙ tril(exp(sᵢ−sⱼ)) ⊙ dtⱼ         (L, L)
    y_intra = W·X                                (L, P)   MXU
    y_inter = exp(s) ⊙ (C·h_prev)                (L, P)   MXU
    h       = exp(s_L)·h_prev + (exp(s_L−s)⊙dt⊙B)ᵀ·X     MXU

The cumulative sum is a masked (L, L) reduction: the TPU lowering has no
scan primitive, and both a row and a column copy of s are needed.

The jnp oracle is ``ref.ssd_scan_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_out_ref,
                h_ref, *, L: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)                  # (L, P)
    dt_row = dt_ref[pl.ds(ic, 1), :].astype(jnp.float32)  # (1, L)
    A = a_ref[pl.program_id(1)]                         # scalar (this head)
    Bm = b_ref[...].astype(jnp.float32)                 # (L, N)
    Cm = c_ref[...].astype(jnp.float32)                 # (L, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # dt as a column: the diagonal of its row broadcast.
    dt_col = jnp.sum(jnp.where(ii == jj, jnp.broadcast_to(dt_row, (L, L)),
                               0.0), axis=1, keepdims=True)     # (L, 1)
    la_row = dt_row * A
    la_col = dt_col * A
    s_col = jnp.sum(jnp.where(jj <= ii, jnp.broadcast_to(la_row, (L, L)),
                              0.0), axis=1, keepdims=True)      # (L, 1)
    s_row = jnp.sum(jnp.where(ii <= jj, jnp.broadcast_to(la_col, (L, L)),
                              0.0), axis=0, keepdims=True)      # (1, L)
    s_last = jnp.sum(la_row, axis=1, keepdims=True)             # (1, 1)

    # Intra-chunk quadratic term.
    G = _dot(Cm, Bm, ((1,), (1,)))                             # (L, L)
    M = jnp.where(jj <= ii, jnp.exp(s_col - s_row), 0.0)
    W = G * M * dt_row
    y = _dot(W, x, ((1,), (0,)))                                # (L, P)

    # Inter-chunk contribution from the carried state.
    h_prev = h_ref[...]                                         # (N, P)
    y += jnp.exp(s_col) * _dot(Cm, h_prev, ((1,), (0,)))

    # State update.
    wB = (jnp.exp(s_last - s_col) * dt_col) * Bm                # (L, N)
    h_new = jnp.exp(s_last) * h_prev + _dot(wB, x, ((0,), (0,)))  # (N, P)
    h_ref[...] = h_new

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        h_out_ref[...] = h_new.astype(h_out_ref.dtype)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False):
    """Pallas SSD scan over the head-major layout:

    x: (Bt, H, S, P); dt: (Bt, H, S); A: (H,); B, C: (Bt, S, N).
    Returns (y: (Bt, H, S, P), h_final: (Bt, H, N, P) fp32).
    """
    Bt, H, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (Bt, H, nc)
    dt = dt.reshape(Bt, H, nc, chunk)

    kernel = functools.partial(_ssd_kernel, L=chunk)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, nc, chunk), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((Bt, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    return y, h
