"""Kernel dispatch layer: Pallas on TPU, jnp reference elsewhere.

Every op has one public entry point with a single semantic contract (the
``ref.py`` oracle).  Backend selection:

* TPU backend            → compiled Pallas kernel;
* ``REPRO_KERNELS=interpret`` env or ``force="interpret"`` → Pallas in
  interpret mode (used by the correctness sweeps — executes the kernel body
  on CPU);
* otherwise (CPU/GPU)    → the jnp reference (fast-enough, XLA-fused).

The 2-D reshaping/padding for the FLEXA elementwise kernels lives here so
kernels stay shape-simple.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flexa_prox as _fp
from repro.kernels import ref
from repro.kernels import spmv as _spmv
from repro.kernels import ssd_scan as _ssd


def _mode(force=None) -> str:
    if force is not None:
        return force
    env = os.environ.get("REPRO_KERNELS", "")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _to_2d(t: jnp.ndarray, cols: int = 512):
    """Flatten + zero-pad a tensor to (rows, cols) for elementwise kernels."""
    flat = t.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % cols
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, cols), n


# ------------------------------------------------------------------ #
def flexa_best_response(x, g, d, c, *, force=None):
    """z = soft(x − g/d, c/d), e2 = Σ(z−x)².  Any-shape tensors."""
    mode = _mode(force)
    if mode == "ref":
        return ref.flexa_best_response_ref(x, g, d, c)
    interp = mode == "interpret"
    scalar_d = jnp.ndim(d) == 0
    x2, n = _to_2d(x)
    g2, _ = _to_2d(g)
    d2 = d if scalar_d else _to_2d(jnp.broadcast_to(d, x.shape))[0]
    # Padded entries: x=g=0 ⇒ z=0, e2 contribution 0.  (d pad must be ≥ 0:
    # broadcast pads with zeros ⇒ guard with +1 on pad rows via maximum.)
    if not scalar_d:
        d2 = jnp.maximum(d2, 1e-30)
    z2, e2 = _fp.best_response(x2, g2, d2, c, interpret=interp)
    z = z2.reshape(-1)[:n].reshape(x.shape)
    return z, e2


def flexa_apply(x, g, d, c, gamma_mask, *, force=None):
    """x ← x + γ·m·(x̂ − x) fused; returns updated tensor with x.dtype."""
    mode = _mode(force)
    if mode == "ref":
        return ref.flexa_apply_ref(x, g, d, c, gamma_mask)
    interp = mode == "interpret"
    scalar_d = jnp.ndim(d) == 0
    x2, n = _to_2d(x)
    g2, _ = _to_2d(g)
    d2 = d if scalar_d else jnp.maximum(
        _to_2d(jnp.broadcast_to(d, x.shape))[0], 1e-30)
    o2 = _fp.apply_update(x2, g2, d2, c, gamma_mask, interpret=interp)
    return o2.reshape(-1)[:n].reshape(x.shape)


def _to_3d(t: jnp.ndarray, cols: int = 512):
    """Flatten + zero-pad each instance of (B, ...) to (B, rows, cols)."""
    B = t.shape[0]
    flat = t.reshape(B, -1)
    n = flat.shape[1]
    pad = (-n) % cols
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((B, pad), flat.dtype)], axis=1)
    return flat.reshape(B, -1, cols), n


def flexa_best_response_batched(x, g, d, c, *, force=None):
    """Per-instance z = soft(x − g/d, c/d) and e2 over a (B, ...) bucket.

    ``c`` / ``gamma_mask`` / scalar ``d`` may be per-instance (B,) vectors —
    each request in a serving bucket carries its own regularization weight
    and γ/τ state.  Returns (z with x's shape, e2 (B,)).
    """
    mode = _mode(force)
    if mode == "ref":
        return ref.flexa_best_response_batched_ref(x, g, d, c)
    interp = mode == "interpret"
    B = x.shape[0]
    dense_d = jnp.ndim(d) > 1
    x3, n = _to_3d(x)
    g3, _ = _to_3d(g)
    if dense_d:
        d3 = jnp.maximum(_to_3d(jnp.broadcast_to(d, x.shape))[0], 1e-30)
    else:
        d3 = d
    z3, e2 = _fp.batched_best_response(x3, g3, d3, c, interpret=interp)
    z = z3.reshape(B, -1)[:, :n].reshape(x.shape)
    return z, e2


def flexa_apply_batched(x, g, d, c, gamma_mask, *, force=None):
    """Fused batched update x ← x + γᵢ·mᵢ·(x̂ − x) over a (B, ...) bucket."""
    mode = _mode(force)
    if mode == "ref":
        return ref.flexa_apply_batched_ref(x, g, d, c, gamma_mask)
    interp = mode == "interpret"
    B = x.shape[0]
    dense_d = jnp.ndim(d) > 1
    x3, n = _to_3d(x)
    g3, _ = _to_3d(g)
    if dense_d:
        d3 = jnp.maximum(_to_3d(jnp.broadcast_to(d, x.shape))[0], 1e-30)
    else:
        d3 = d
    o3 = _fp.batched_apply_update(x3, g3, d3, c, gamma_mask,
                                  interpret=interp)
    return o3.reshape(B, -1)[:, :n].reshape(x.shape)


# ------------------------------------------------------------------ #
# Compacted active-set gather/scatter (capacity-bucketed screening)   #
# ------------------------------------------------------------------ #
def _pad_cols(t: jnp.ndarray, mult: int = 128):
    """Zero-pad the trailing dim to a lane multiple for the row kernels.

    Zero columns are inert for gather, scatter and the fused prox (they
    ride along and are sliced off after), so ragged layouts — e.g. a
    block row of bs·m values — dispatch through the same aligned tiles.
    """
    C = t.shape[-1]
    pad = (-C) % mult
    if pad:
        t = jnp.concatenate(
            [t, jnp.zeros(t.shape[:-1] + (pad,), t.dtype)], axis=-1)
    return t, C


def gather_blocks(src, idx, *, force=None):
    """Row gather: out[k] = src[idx[k]] (−1 ⇒ zero row).  src (N, C)."""
    mode = _mode(force)
    idx = jnp.asarray(idx, jnp.int32)
    if mode == "ref":
        return ref.gather_rows_ref(src, idx)
    src2, C = _pad_cols(jnp.asarray(src))
    out = _fp.gather_rows(src2, idx, interpret=(mode == "interpret"))
    return out[:, :C]


def scatter_blocks(vals, inv, base, *, force=None):
    """Inverse-permutation scatter: out[i] = vals[inv[i]] or base[i]."""
    mode = _mode(force)
    inv = jnp.asarray(inv, jnp.int32)
    if mode == "ref":
        return ref.scatter_rows_ref(vals, inv, base)
    vals2, _ = _pad_cols(jnp.asarray(vals))
    base2, C = _pad_cols(jnp.asarray(base))
    out = _fp.scatter_rows(vals2, inv, base2,
                           interpret=(mode == "interpret"))
    return out[:, :C]


def compact_best_response(x, g, d, c, idx, *, force=None):
    """Fused gather + soft-threshold over the active rows (see ref)."""
    mode = _mode(force)
    idx = jnp.asarray(idx, jnp.int32)
    if mode == "ref":
        return ref.compact_best_response_ref(x, g, d, c, idx)
    interp = mode == "interpret"
    x2, C = _pad_cols(jnp.asarray(x))
    g2, _ = _pad_cols(jnp.asarray(g))
    if jnp.ndim(d) == 0:
        d2 = d
    else:
        # Zero pad columns would divide 0/0 — clamp like the dense path.
        d2 = jnp.maximum(_pad_cols(jnp.broadcast_to(d, x.shape))[0], 1e-30)
    z2, e2 = _fp.compact_best_response(x2, g2, d2, c, idx,
                                       interpret=interp)
    return z2[:, :C], e2


def blocked_product(values, gidx, sidx, tile_g, tile_s, table, n_out, *,
                    live=None, force=None):
    """One product of a blocked sparse design (``problems.sparse.
    BlockedDesign``): out[sidx[k]] += values[k]·table[gidx[k]], (n_out,).

    ``values``, ``gidx``, ``sidx``: (L,) stored entries, L a multiple of
    ``spmv.TILE``; ``tile_g`` / ``tile_s``: (L / TILE,) int32, the block
    of ``table`` / of the output that tile t's entries index, or −1 for
    the tiles past the design's last block pair, which hold no entries.
    On the kernel path each tile's window of ``table`` is cut by a one-hot
    product, the kernel forms each tile's partial output window, and a
    second one-hot product adds the windows into the output; both at
    ``Precision.HIGHEST``, so they move float32 values exactly.

    The kernel computes only the tiles that hold entries, and none where
    ``live`` (a bool scalar, one per design under ``vmap``) is False:
    such a product is then zeros, for a caller that throws it away.
    ``live=None`` (every unbatched call) computes every tile that holds
    entries.  The answer of a computed product never depends on the gate.
    The XLA path (``ref``) computes every entry whatever ``live`` says.
    """
    mode = _mode(force)
    if mode == "ref":
        return ref.blocked_product_ref(values, gidx, sidx, table, n_out)
    B, nt = _spmv.BLOCK, values.shape[0] // _spmv.TILE
    n_g = -(-table.shape[0] // B)
    n_s = -(-n_out // B)
    hi = jax.lax.Precision.HIGHEST
    tab = jnp.pad(table.astype(jnp.float32),
                  (0, n_g * B - table.shape[0])).reshape(n_g, B)
    win = jnp.dot(jax.nn.one_hot(tile_g, n_g, dtype=jnp.float32), tab,
                  precision=hi)
    count = jnp.sum(tile_g >= 0, dtype=jnp.int32)   # the tiles with entries
    if live is not None:
        count = jnp.where(live, count, 0)
    part = _spmv.tile_products(
        values.reshape(nt, 8, 128), gidx.reshape(nt, 8, 128),
        sidx.reshape(nt, 8, 128), win.reshape(nt, B // 128, 128), count,
        interpret=mode == "interpret")
    out = jnp.dot(jax.nn.one_hot(tile_s, n_s, dtype=jnp.float32).T,
                  part.reshape(nt, B), precision=hi)
    return out.reshape(-1)[:n_out]


def flash_attention(q, k, v, *, causal=True, scale=None, force=None,
                    block_q: int = 256, block_k: int = 512):
    mode = _mode(force)
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return _fa.flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=(mode == "interpret"))


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, force=None):
    # Pad S to a chunk multiple.  dt=0 padding is algebraically inert:
    # decay exp(0·A)=1 keeps the state, update dt·(B⊗x)=0 adds nothing.
    S = x.shape[1]
    pad = (-S) % chunk
    if pad:
        padw = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                 [(0, 0)] * (t.ndim - 2))
        x, dt, B, C = padw(x), padw(dt), padw(B), padw(C)
    mode = _mode(force)
    if mode == "ref":
        y, h = ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk)
    else:
        # Head-major for the kernel: the sequence chunk becomes the
        # tiled (second-minor) dimension of every block.
        y, h = _ssd.ssd_scan(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1),
                             A, B, C, chunk=chunk,
                             interpret=(mode == "interpret"))
        y = y.transpose(0, 2, 1, 3)
    return (y[:, :S] if pad else y), h


def ssd_decode(x_t, dt_t, A, B_t, C_t, h):
    """Single-token SSD step — always the jnp path (it is a few GEMVs)."""
    return ref.ssd_decode_ref(x_t, dt_t, A, B_t, C_t, h)
