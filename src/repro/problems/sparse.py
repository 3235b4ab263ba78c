"""Sparse designs: the layouts a :class:`Problem`'s design may take
beside the dense (m, n) array.

A tenant sends a :class:`CSCDesign`, one design A of shape (m, n) by its
stored entries, column by column:

* ``values``  (L,) float32 — the entries;
* ``rows``    (L,) int32 — the row of each entry;
* ``col_ptr`` (n + 1,) int32 — column j's entries are
  ``values[col_ptr[j]:col_ptr[j + 1]]``, so ``col_ptr[n]`` is nnz.

Entries past ``col_ptr[n]`` are padding, appended once at the end and
never per column: that is how designs of unequal nnz share one slab of a
fixed capacity (:func:`capacity_bucket`).

On the device a design is stored as a :class:`BlockedDesign`
(:func:`block_layout`, on the device once per admission): the same
entries, each with its row and column, grouped by the (:data:`BLOCK` ×
:data:`BLOCK`) block of A it lies in and padded with zero entries to
whole tiles of :data:`TILE` entries, so that
every tile's rows and columns each fall in one window of BLOCK
positions.  The padding is per block pair, at most TILE − 1 entries for
each, never per column: per-column padding (ELL) would pad every column
to the longest one, and a text design's column counts are as skewed as
word frequencies.  The three products the quadratic families need — A·x,
Aᵀ·r and the column norms ‖aⱼ‖² — are one pass over the tiles each
(``repro.kernels.ops.blocked_product``: a Pallas kernel on TPU, gathers
and ``segment_sum`` elsewhere).  A·x runs under the named scope
``spmv``, Aᵀ·r and the column norms under ``spmv_t``.  The tiles past a
design's last block pair carry the block index −1 and are not computed,
and :func:`gate_designs` marks a stack's designs whose products a caller
throws away (stopped or empty slots), which are not computed either.
:func:`design_matvec`, :func:`design_rmatvec` and :func:`design_col_sq`
pick the product by layout, so the problem families keep one definition
of their math for both layouts.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.spmv import BLOCK, TILE
from repro.problems.base import mv

#: The smallest nnz capacity a slab is sized to: one tile.
MIN_CAPACITY = TILE


def _blocks(size: int) -> int:
    return -(-int(size) // BLOCK)


def tile_padding_bound(m: int, n: int) -> int:
    """The most zero entries :func:`block_layout` adds to a design of
    shape (m, n): TILE − 1 for each of its block pairs."""
    return _blocks(m) * _blocks(n) * (TILE - 1)


def capacity_bucket(nnz: int, m: int, n: int) -> int:
    """The nnz capacity of the slab a design of ``nnz`` stored entries
    and shape (m, n) lands in: the next power of two that holds its
    entries and its tile padding (at least :data:`MIN_CAPACITY`), so
    that designs whose nnz differ by a few percent share one slab and
    one compiled program."""
    need = int(nnz) + tile_padding_bound(m, n)
    return max(MIN_CAPACITY, 1 << max(0, need - 1).bit_length())


@jax.tree_util.register_pytree_node_class
class CSCDesign:
    """One column-compressed design, as a tenant sends it (see the
    module docstring).  A pytree: its arrays are the leaves, ``(m, n)``
    the static part."""

    def __init__(self, values, rows, col_ptr, shape):
        self.values = values
        self.rows = rows
        self.col_ptr = col_ptr
        self.shape = (int(shape[0]), int(shape[1]))

    def tree_flatten(self):
        return (self.values, self.rows, self.col_ptr), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        return cls(*children, shape)

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    @property
    def capacity(self) -> int:
        """L: stored entries, padding included (a static shape)."""
        return int(self.values.shape[-1])

    @property
    def nnz(self) -> int:
        """True entries, ``col_ptr[n]`` (reads the device for a
        device-resident design)."""
        return int(np.asarray(self.col_ptr)[..., -1])

    def __repr__(self) -> str:
        return f"CSCDesign(shape={self.shape}, capacity={self.capacity})"

    def check(self) -> None:
        """Raise ``ValueError`` unless the arrays describe a design of
        this shape: ``rows`` as long as ``values``, ``col_ptr`` of n + 1
        non-decreasing offsets from 0 to at most L, and every stored
        entry's row in [0, m).  Host-side; reads the arrays."""
        L = self.capacity
        if np.shape(self.rows) != (L,) or np.shape(self.col_ptr) != (
                self.n + 1,):
            raise ValueError(
                f"a sparse design needs rows of its values' length ({L},) "
                f"and col_ptr of shape ({self.n + 1},), got "
                f"{np.shape(self.rows)} and {np.shape(self.col_ptr)}")
        ptr = np.asarray(self.col_ptr)
        if ptr[0] != 0 or np.any(np.diff(ptr) < 0) or ptr[-1] > L:
            raise ValueError(
                f"col_ptr must rise from 0 to at most {L} stored entries "
                f"without falling (got col_ptr[0] = {ptr[0]}, "
                f"col_ptr[n] = {ptr[-1]})")
        rows = np.asarray(self.rows)[:ptr[-1]]
        if rows.size and (rows.min() < 0 or rows.max() >= self.m):
            raise ValueError(
                f"every stored entry's row must lie in [0, {self.m}), got "
                f"rows from {rows.min()} to {rows.max()}")

    def padded(self, capacity: int) -> "CSCDesign":
        """Host arrays padded to ``capacity`` stored entries (float32
        values, int32 indices), as a slab of that capacity takes them
        (a device-resident design is copied to the host)."""
        L = self.capacity
        if L > capacity:
            raise ValueError(f"a design of {L} stored entries does not "
                             f"fit a capacity of {capacity}")
        values = np.zeros((capacity,), np.float32)
        rows = np.zeros((capacity,), np.int32)
        values[:L] = np.asarray(self.values, np.float32)
        rows[:L] = np.asarray(self.rows, np.int32)
        return CSCDesign(values, rows, np.asarray(self.col_ptr, np.int32),
                         self.shape)

    def blocked(self) -> "BlockedDesign":
        """This design on the device in the stored layout, padded to its
        capacity bucket."""
        d = self.padded(capacity_bucket(self.capacity, self.m, self.n))
        return _block_layout_jit(d.values, d.rows, d.col_ptr, self.shape)


@jax.tree_util.register_pytree_node_class
class BlockedDesign:
    """One design in the stored layout (module docstring): ``values``,
    ``rows``, ``cols`` (L,) by tile, and ``tile_rb``, ``tile_cb``
    (L / TILE,) int32, the row block and column block of each tile (−1
    past the last block pair).  ``live`` is ``None``, or a bool scalar
    (one per design of a stack) that gates the products: where it is
    False they are zeros and are not computed (:func:`gate_designs`).
    A pytree, so a stack of designs vmaps like a stack of dense
    matrices."""

    def __init__(self, values, rows, cols, tile_rb, tile_cb, shape,
                 live=None):
        self.values = values
        self.rows = rows
        self.cols = cols
        self.tile_rb = tile_rb
        self.tile_cb = tile_cb
        self.shape = (int(shape[0]), int(shape[1]))
        self.live = live

    def tree_flatten(self):
        return ((self.values, self.rows, self.cols, self.tile_rb,
                 self.tile_cb, self.live), self.shape)

    @classmethod
    def tree_unflatten(cls, shape, children):
        *arrays, live = children
        return cls(*arrays, shape, live=live)

    @property
    def m(self) -> int:
        return self.shape[0]

    @property
    def n(self) -> int:
        return self.shape[1]

    def __repr__(self) -> str:
        return (f"BlockedDesign(shape={self.shape}, "
                f"capacity={self.values.shape[-1]})")

    def matvec(self, x) -> jnp.ndarray:
        """A·x, (m,)."""
        with jax.named_scope("spmv"):
            return ops.blocked_product(self.values, self.cols, self.rows,
                                       self.tile_cb, self.tile_rb, x, self.m,
                                       live=self.live)

    def rmatvec(self, r) -> jnp.ndarray:
        """Aᵀ·r, (n,)."""
        with jax.named_scope("spmv_t"):
            return ops.blocked_product(self.values, self.rows, self.cols,
                                       self.tile_rb, self.tile_cb, r, self.n,
                                       live=self.live)

    def col_sq(self) -> jnp.ndarray:
        """‖aⱼ‖² per column, (n,)."""
        with jax.named_scope("spmv_t"):
            return ops.blocked_product(
                self.values * self.values, self.rows, self.cols,
                self.tile_rb, self.tile_cb, jnp.ones((self.m,), jnp.float32),
                self.n, live=self.live)


def _cols_of(col_ptr, L: int, n: int):
    """The column of each of L stored entries (traceable): the number of
    columns that end at or before it; padding lands in column n − 1."""
    marks = jnp.zeros((L + 1,), jnp.int32).at[col_ptr[1:]].add(1)
    return jnp.minimum(jnp.cumsum(marks[:L]), n - 1).astype(jnp.int32)


def block_layout(values, rows, col_ptr, shape) -> BlockedDesign:
    """A padded column-compressed design in the stored layout, with as
    many stored entries (traceable; L a multiple of TILE that holds the
    design's entries and its :func:`tile_padding_bound`).

    A counting sort, with no sort primitive (whose TPU compile takes
    tens of seconds): the entries are already grouped by column block,
    so each (column block, row block) pair's entries are those of its
    row block within its column block's run.  Pair p's tiles start at
    ``base[p]``; entry k goes to ``base[p] +`` its rank in p, which is a
    running count of its row block's entries less the count before its
    column block's run: one cumulative sum over (L, row blocks) int32,
    then one scatter of each array.  The top-up of each pair to whole
    tiles, and the tiles past the last pair, hold zero entries; the
    latter carry the block index −1 (row and column), so the products
    skip them.
    """
    m, n = shape
    L = values.shape[-1]
    nrb, ncb = _blocks(m), _blocks(n)
    i32 = jnp.int32
    col_ptr = jnp.asarray(col_ptr, i32)
    rows = jnp.asarray(rows, i32)
    cols = _cols_of(col_ptr, L, n)
    valid = jnp.arange(L) < col_ptr[n]
    rb = jnp.where(valid, rows // BLOCK, nrb)          # padding: no block
    cb = cols // BLOCK
    inc = jax.nn.one_hot(rb, nrb, dtype=i32)            # (L, nrb)
    # entries per (column block, row block), as a one-hot product
    # (exact below 2**24)
    cnt = jnp.dot(jax.nn.one_hot(cb, ncb, dtype=jnp.bfloat16).T,
                  inc.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32).astype(i32)
    tiles = -(-cnt // TILE)
    base = (jnp.cumsum(tiles.reshape(-1)) - tiles.reshape(-1)).reshape(
        ncb, nrb) * TILE
    shift = base - (jnp.cumsum(cnt, axis=0) - cnt)
    run_start = col_ptr[jnp.arange(ncb) * BLOCK]
    marks = jnp.zeros((L + 1, nrb), i32).at[run_start].add(
        jnp.diff(shift, axis=0, prepend=0))[:L]
    pos = jnp.cumsum(inc + marks, axis=0) - inc
    dest = jnp.where(valid, jnp.sum(pos * inc, axis=1), L)

    def place(a):
        return jnp.zeros_like(a).at[dest].set(a, mode="drop",
                                              unique_indices=True)

    first = jnp.arange(L // TILE, dtype=i32)[:, None] * TILE
    start, size = base.reshape(1, -1), tiles.reshape(1, -1) * TILE
    inside = (first >= start) & (first < start + size)
    pair = jnp.argmax(inside, axis=1)
    held = jnp.any(inside, axis=1)
    return BlockedDesign(
        place(jnp.asarray(values, jnp.float32)), place(rows), place(cols),
        jnp.where(held, pair % nrb, -1).astype(i32),
        jnp.where(held, pair // nrb, -1).astype(i32), shape)


_block_layout_jit = jax.jit(block_layout, static_argnums=(3,))


def is_sparse(A) -> bool:
    return isinstance(A, (CSCDesign, BlockedDesign))


def gate_designs(data: tuple, live, *, force_open: bool = False) -> tuple:
    """A stack's family data with each sparse design's products gated to
    the instances where ``live()``, a (B,) bool mask, holds; the others'
    products are zeros and are not computed, for a caller that throws
    them away.  Dense arrays pass through, and ``live`` is called only
    when ``data`` holds a sparse design, so a dense stack traces exactly
    as without the gate.  ``force_open`` (for tests) returns ``data``
    unchanged."""
    if force_open or not any(isinstance(d, BlockedDesign) for d in data):
        return data
    mask = live()
    return tuple(
        BlockedDesign(d.values, d.rows, d.cols, d.tile_rb, d.tile_cb,
                      d.shape, live=mask)
        if isinstance(d, BlockedDesign) else d for d in data)


def design_matvec(A, x):
    """A·x, A dense or stored (the dense product at float32 accuracy)."""
    return A.matvec(x) if is_sparse(A) else mv(A, x)


def design_rmatvec(A, r):
    """Aᵀ·r, A dense or stored."""
    return A.rmatvec(r) if is_sparse(A) else mv(A.T, r)


def design_col_sq(A):
    """‖aⱼ‖² per column, A dense or stored."""
    return A.col_sq() if is_sparse(A) else jnp.sum(A * A, axis=0)


def design_layout(A) -> tuple[str, int]:
    """``(layout, nnz capacity)`` of a design, read from its shapes
    alone: ``("dense", 0)`` or ``("csc", capacity_bucket(...))``."""
    if isinstance(A, CSCDesign):
        return "csc", capacity_bucket(A.capacity, A.m, A.n)
    return "dense", 0


def stack_designs(designs, capacity: int) -> BlockedDesign:
    """Stack column-compressed designs of one shape along a new leading
    axis, in the stored layout of ``capacity`` entries each."""
    padded = [d.padded(capacity) for d in designs]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *padded)
    return jax.vmap(partial(block_layout, shape=designs[0].shape))(
        stacked.values, stacked.rows, stacked.col_ptr)
