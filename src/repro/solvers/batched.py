"""Batched multi-instance FLEXA: B independent solves, ONE compiled program.

The serving scenario the ROADMAP asks for is "many concurrent solve
requests".  Looping ``solve()`` over instances pays per-instance dispatch
and compilation and leaves the accelerator idle between small matvecs.
This module instead *vmaps Algorithm 1 itself* over a stack of instances:

* every instance shares one static shape signature
  (:class:`BatchedProblemSpec`: m, n, block size, G kind **and problem
  family**) — the data arrays and the regularization weight ``c`` vary per
  instance.  The family (lasso / group_lasso / logreg / svm — see
  ``repro.problems.families``) selects which F closures get rebuilt from
  the vmapped data slices inside the vmap;
* the per-instance iteration is literally
  :func:`repro.core.flexa.flexa_iteration`, so batched iterates match B
  sequential ``solve`` calls to float32 accuracy (asserted for every
  family by ``tests/test_solvers_api.py``);
* the driver is a single ``lax.while_loop``: converged instances are
  frozen (their state stops updating, their ``k`` stops counting) while
  stragglers keep iterating, and the program exits when every instance is
  done — one compilation, zero per-step host round trips;
* compiled programs are cached on ``(spec, cfg)`` via a bounded,
  instrumented LRU (``repro.solvers.cache.CompileCache``, capacity from
  ``REPRO_COMPILE_CACHE_SIZE``) — one compile cache entry per (family,
  shape, config) signature.  The inline backend's batch solves and the
  lockstep path driver (``repro.path``) run this program.

Besides the run-to-convergence program, this module exposes the
*resumable* slab core the serving engines (``repro.serve.continuous``
and its mesh-sharded form ``repro.serve.mesh``) schedule over:
:func:`slab_alloc` packs a fixed-capacity stack of instance buffers,
:func:`make_row_writer` writes one admitted request's data rows alone
into the donated slab, and :func:`make_chunk_stepper` compiles "advance
every live slot by K iterations", on one device or with the slot axis
sharded over a mesh, with the same freeze-on-convergence merge the
run-to-convergence program uses (:func:`_frozen_step`) — so a slot's
trajectory is bit-identical whichever driver runs it.

γ, τ, the PRNG key of the randomized selection rules, and the selection
mask are per-instance state, so each instance follows the identical
trajectory it would take in a solo run with ``key = fold_in(PRNGKey(seed),
instance_index)`` — batching changes the schedule of nothing but the
hardware.

Reproducibility note: batched and solo matvecs may reduce in different
orders (≈1e-6 relative fp32 noise).  The §4 τ-controller branches on exact
objective comparisons (``V > V_prev``), so that noise can occasionally flip
a discrete τ double/halve and visibly split trajectories on ill-conditioned
instances.  With ``tau_adapt=False`` the iteration is a smooth contraction
and batched solutions track solo ones to ~1e-6 absolute; with the default
adaptive τ both still converge to the same optimum, just not always along
bit-identical paths.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.config.base import SolverConfig
from repro.core import flexa as _flexa
from repro.core.flexa import FlexaState, flexa_iteration
from repro.problems.base import Problem
from repro.obs.health import (HealthConfig, STATUS_RUNNING,
                              STATUS_STOPPED, STATUS_DIVERGED,
                              STATUS_STALLED)
from repro.problems.families import build_problem, get_family, infer_family
from repro.problems.sparse import (TILE, BlockedDesign, CSCDesign,
                                   block_layout, design_layout, gate_designs,
                                   is_sparse, stack_designs)
from repro.solvers.cache import CompileCache
from repro.solvers.result import SolverResult


@dataclass(frozen=True)
class BatchedProblemSpec:
    """The static signature every instance in one batch must share.

    Shapes must match for vmap/stacking; ``family`` selects the F closures
    and the G structure selects the prox (soft-threshold vs group
    shrinkage) baked into the compiled program.  ``layout`` is how the
    design is stored: ``"dense"`` (m, n), or ``"csc"``: sent
    column-compressed (:class:`~repro.problems.sparse.CSCDesign`) and
    stored blocked (:class:`~repro.problems.sparse.BlockedDesign`), in
    ``nnz_cap`` stored entries, a power-of-two bucket, so designs of
    nearly equal nnz share one slab.  Hashable on purpose: it is the
    compile-cache key.
    """
    m: int
    n: int
    block_size: int = 1
    g_kind: str = "l1"
    family: str = "lasso"
    layout: str = "dense"
    nnz_cap: int = 0

    @classmethod
    def for_design(cls, design, *, n: int, block_size: int, g_kind: str,
                   family: str) -> "BatchedProblemSpec":
        """The signature of a design (dense array or sparse), read from
        its shapes alone: nothing is copied."""
        layout, nnz_cap = design_layout(design)
        if layout != "dense" and family not in SPARSE_FAMILIES:
            raise ValueError(
                f"family {family!r} takes a dense design; sparse designs "
                f"serve the families {SPARSE_FAMILIES}")
        return cls(m=int(design.shape[0]), n=int(n),
                   block_size=int(block_size), g_kind=str(g_kind),
                   family=family, layout=layout, nnz_cap=nnz_cap)

    @classmethod
    def of(cls, problem: Problem) -> "BatchedProblemSpec":
        family = infer_family(problem)
        fam = get_family(family)
        missing = [k for k in fam.data_keys if k not in problem.data]
        if missing:
            raise ValueError(
                f"batched FLEXA on family {family!r} needs problem data "
                f"{fam.data_keys} (got {problem.name!r} missing {missing})")
        return cls.for_design(problem.data[fam.data_keys[0]],
                              n=problem.n, block_size=problem.block_size,
                              g_kind=problem.g_kind, family=family)


#: Families whose design may be sparse: the quadratic ones, whose F
#: reads its design through the layout's products alone.
SPARSE_FAMILIES = ("lasso", "group_lasso")


def family_problem(arrays, c, spec: BatchedProblemSpec,
                   col_sq=None) -> Problem:
    """Rebuild the per-instance :class:`Problem` from raw arrays.

    Traceable (``repro.problems.families.build_problem``): the F closures
    are the very same builders the solo constructors install, so batched
    and solo solves share one definition of the math.  ``col_sq`` may be
    precomputed outside the solve loop to avoid redoing the ‖column‖²
    reduction every iteration.
    """
    return build_problem(spec.family, arrays, c, n=spec.n,
                         block_size=spec.block_size, g_kind=spec.g_kind,
                         col_sq=col_sq)


def quadratic_problem(A, b, c, spec: BatchedProblemSpec,
                      col_sq=None) -> Problem:
    """Back-compat alias for the quadratic families (pre-registry API)."""
    return family_problem((A, b), c, spec, col_sq=col_sq)


def _tau_base(half_curv, cfg: SolverConfig, n: int) -> jnp.ndarray:
    """Traceable twin of ``flexa._base_tau``: the §4 default from
    ``diag_curv/2`` (``ProblemFamily.half_curv``), via the shared
    :func:`~repro.core.flexa.tau0_from_colsq`."""
    if cfg.tau0 > 0:
        return jnp.full((n,), cfg.tau0, jnp.float32)
    t0 = _flexa.tau0_from_colsq(half_curv, n)
    return jnp.broadcast_to(t0.astype(jnp.float32), (n,))


def _instance_step(spec: BatchedProblemSpec, cfg: SolverConfig,
                   arrays, c, col_sq, tau_base, active,
                   state: FlexaState):
    """One per-instance iteration; ``active`` is the (n,) freeze mask
    (all-ones ⇒ bit-identical to the unmasked iteration — the multiplies
    are by exact fp32 1.0s)."""
    problem = family_problem(arrays, c, spec, col_sq=col_sq)
    return flexa_iteration(problem, cfg, tau_base, state, active=active)


def _instance_init(spec: BatchedProblemSpec, cfg: SolverConfig,
                   arrays, c, x0, idx) -> FlexaState:
    problem = family_problem(arrays, c, spec)
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), idx)
    return _flexa.init_state(problem, x0, cfg, key=key)


def _bmask(mask, ndim: int):
    """Broadcast a (S,) bool mask against an (S, ...) array."""
    return mask.reshape((-1,) + (1,) * (ndim - 1))


def _freeze_done(done, new_state: FlexaState, old_state: FlexaState):
    """Keep the old state on instances already finished (their k stops;
    their x and the design product ``u`` at it stay together)."""
    return jax.tree_util.tree_map(
        lambda new, old: jnp.where(_bmask(done, new.ndim), old, new),
        new_state, old_state)


def _frozen_step(vstep, cfg: SolverConfig, data, c, col_sq, tau_base,
                 active, state: FlexaState, stop, tol):
    """One freeze-merge iteration over a stack of instances: every
    instance steps, those already stopped keep their old state, and
    ``stop`` gains the instances that now reach ``tol`` (a scalar, or
    one tolerance per instance) or ``cfg.max_iters``.  Returns
    ``(state, stop, info)``.  Every driver of the stack (the
    run-to-convergence loop, the chunk, the recorded-history loop)
    iterates through this one step.

    On sparse designs the products of the instances already stopped are
    not computed (:func:`~repro.problems.sparse.gate_designs`): their
    step is thrown away, so the answers do not change, and their entries
    of ``info`` are those of a step taken with zero products."""
    new_state, info = vstep(gate_designs(data, lambda: ~stop), c, col_sq,
                            tau_base, active, state)
    merged = _freeze_done(stop, new_state, state)
    stop = stop | (merged.stat <= tol) | (merged.k >= cfg.max_iters)
    return merged, stop, info


def _stack_norms(spec: BatchedProblemSpec, cfg: SolverConfig, data):
    """``(col_sq, tau_base)``, each (B, n), of a stack of instances'
    family data: the column norms and the §4 base τ built from them."""
    fam = get_family(spec.family)
    col_sq = jax.vmap(fam.col_sq)(*data)
    tau_base = jax.vmap(
        lambda csq: _tau_base(fam.half_curv(csq), cfg, spec.n))(col_sq)
    return col_sq, tau_base


def _build_batched_solver(spec: BatchedProblemSpec, cfg: SolverConfig):
    """Compile ``run(data, c, x0) -> (final FlexaState, converged)``.

    ``data`` is the tuple of stacked family arrays (leading dim B — e.g.
    ``(A: (B, m, n), b: (B, m))`` for the quadratic families, ``(Z: (B, m,
    n),)`` for logreg/svm), ``c``: (B,), ``x0``: (B, n).  ``active`` is an
    optional (B, n) per-instance freeze mask (``None`` ⇒ all coordinates
    live — the pre-screening behaviour, bit for bit).  The cache key is
    (spec, cfg); jit recompiles per distinct B.
    """
    vstep = jax.vmap(partial(_instance_step, spec, cfg))
    vinit = jax.vmap(partial(_instance_init, spec, cfg))

    @jax.jit
    def run(data, c, x0, active=None):
        col_sq, tau_base = _stack_norms(spec, cfg, data)  # once per solve
        B = x0.shape[0]
        if active is None:
            active = jnp.ones((B, spec.n), jnp.float32)
        state = vinit(data, c, x0, jnp.arange(B))
        done = jnp.zeros((B,), bool)

        def cond(carry):
            _, done = carry
            return jnp.any(~done)

        def body(carry):
            state, done = carry
            state, done, _ = _frozen_step(vstep, cfg, data, c, col_sq,
                                          tau_base, active, state, done,
                                          cfg.tol)
            return state, done

        final, _ = jax.lax.while_loop(cond, body, (state, done))
        return final, final.stat <= cfg.tol

    return run


#: Bounded LRU over (spec, cfg): ``make_batched_solver(spec, cfg)``.
#: Counters surface via ``repro.serve.metrics``.
make_batched_solver = CompileCache("batched_solver", _build_batched_solver)


# ===================================================================== #
# Resumable slab core (continuous batching)                             #
# ===================================================================== #
class SlabState(NamedTuple):
    """Device buffers of one fixed-capacity slot slab (leading dim S).

    This is the "packed" form the continuous runtime schedules over: the
    per-slot family data, regularization weights, precomputed column
    norms / base-τ vectors, and the stacked :class:`FlexaState`.  It is a
    pytree, so one jitted program can consume and (with donation) reuse
    the whole bundle in place.
    """
    data: tuple                 # family arrays, each (S, ...)
    c: jnp.ndarray              # (S,)
    col_sq: jnp.ndarray         # (S, n)
    tau_base: jnp.ndarray       # (S, n)
    state: FlexaState           # stacked, leading dim S
    active: jnp.ndarray = None  # (S, n) per-slot freeze mask (1 = live)
    tol: jnp.ndarray = None     # (S,) per-slot stopping tolerance

    @property
    def capacity(self) -> int:
        return int(self.c.shape[0])


def slab_data_template(spec: BatchedProblemSpec) -> tuple:
    """Per-instance family data as a slab stores it, in ``data_keys``
    order, as ``jax.ShapeDtypeStruct`` leaves: the leading key is the
    design — a dense (m, n) array, or a
    :class:`~repro.problems.sparse.BlockedDesign` of ``nnz_cap`` stored
    entries — and ``b`` is the (m,) observation vector."""
    f32, i32 = jnp.float32, jnp.int32
    out = []
    for j, key in enumerate(get_family(spec.family).data_keys):
        if j == 0 and spec.layout == "dense":
            out.append(jax.ShapeDtypeStruct((spec.m, spec.n), f32))
        elif j == 0 and spec.layout == "csc":
            L, T = spec.nnz_cap, spec.nnz_cap // TILE
            out.append(BlockedDesign(
                jax.ShapeDtypeStruct((L,), f32),
                jax.ShapeDtypeStruct((L,), i32),
                jax.ShapeDtypeStruct((L,), i32),
                jax.ShapeDtypeStruct((T,), i32),
                jax.ShapeDtypeStruct((T,), i32), (spec.m, spec.n)))
        elif key == "b":
            out.append(jax.ShapeDtypeStruct((spec.m,), f32))
        else:
            raise NotImplementedError(
                f"no slab layout for data key {key!r} of family "
                f"{spec.family!r} (layout {spec.layout!r})")
    return tuple(out)


def shipped_row_bytes(spec: BatchedProblemSpec) -> int:
    """Bytes one admitted request's data rows take on their way to the
    slab: the dense arrays as the slab stores them, or a sparse design
    column-compressed (values and rows padded to ``nnz_cap``, n + 1
    column pointers), which the row writer lays out on the device."""
    if spec.layout == "dense":
        return sum(math.prod(t.shape) * t.dtype.itemsize
                   for t in slab_data_template(spec))
    return 8 * spec.nnz_cap + 4 * (spec.n + 1) + 4 * spec.m


def _set_rows(data: tuple, slot, rows: tuple) -> tuple:
    """``data`` with slot ``slot`` of every leaf set from ``rows``, a
    column-compressed design laid out blocked as the slab stores it
    (traceable)."""
    stored = (block_layout(r.values, r.rows, r.col_ptr, r.shape)
              if isinstance(r, CSCDesign) else r for r in rows)
    return tuple(
        jax.tree_util.tree_map(lambda d, r: d.at[slot].set(r.astype(d.dtype)),
                               d, r)
        for d, r in zip(data, stored))


def slab_alloc(spec: BatchedProblemSpec, cfg: SolverConfig,
               capacity: int) -> SlabState:
    """Pack a zeroed slab of ``capacity`` slots.

    Empty slots hold benign placeholders (unit column norms / τ, zero
    data) so the chunk stepper can run them through the vmapped iteration
    and throw the result away without manufacturing NaNs; their ``stat``
    starts at +inf, so they can never read as converged.  Every slot's
    stopping tolerance starts at ``cfg.tol``; admission may override it
    per request (the multi-tenant mixed-tolerance path).
    """
    S = int(capacity)
    data = jax.tree_util.tree_map(
        lambda t: jnp.zeros((S,) + t.shape, t.dtype),
        slab_data_template(spec))
    c = jnp.ones((S,), jnp.float32)
    col_sq = jnp.ones((S, spec.n), jnp.float32)
    tau_base = jnp.ones((S, spec.n), jnp.float32)
    state = jax.vmap(partial(_instance_init, spec, cfg))(
        data, c, jnp.zeros((S, spec.n), jnp.float32), jnp.arange(S))
    return SlabState(data=data, c=c, col_sq=col_sq, tau_base=tau_base,
                     state=state,
                     active=jnp.ones((S, spec.n), jnp.float32),
                     tol=jnp.full((S,), cfg.tol, jnp.float32))


def _build_row_writer(spec: BatchedProblemSpec):
    """Compile ``write_rows(slab, slot, *rows) -> slab``: one admitted
    request's family data rows (``(A, b)`` for the quadratic families,
    ``(Z,)`` for logreg/svm) written in place into slot ``slot`` of
    ``slab.data``.

    Admission's data path: the continuous slab ships each admitted
    request's rows alone, and the chunk program's splice then reads them
    from the slab.  ``slot`` is a traced int32 scalar and the slab is
    donated, so one program per signature serves every slot and every
    admission count, and the write is a ``dynamic_update_slice`` into
    the resident buffer.  Every other slot's data and every non-data
    buffer pass through unchanged.  A sparse design arrives padded to
    the slab's nnz capacity (``SolveRequest.data_arrays``), so every design of
    one bucket shares the program; the column of each entry is filled
    in here, once per admission.
    """
    @partial(jax.jit, donate_argnums=(0,))
    def write_rows(slab: SlabState, slot, *rows):
        return slab._replace(data=_set_rows(slab.data, slot, rows))

    return write_rows


make_row_writer = CompileCache("row_writer", _build_row_writer)


def _chunk_core(spec: BatchedProblemSpec, cfg: SolverConfig,
                chunk_iters: int, health: HealthConfig | None = None):
    """The (un-jitted) fused tick body of :func:`make_chunk_stepper`:

        core(slab, stop, admit, new_c, new_x0, new_ids, new_active,
             new_tol) -> (slab, stop)

    or, with the numerical-health watchdog enabled (``health`` a
    :class:`repro.obs.health.HealthConfig`):

        core(slab, stop, admit, ..., new_tol, prev_stat, stall)
            -> (slab, status, prev_stat, stall)

    where ``status`` is the (S,) int32 verdict vector (STATUS_RUNNING /
    STOPPED / DIVERGED / STALLED) that replaces the boolean stop mask in
    the one-per-tick readback, and ``(prev_stat, stall)`` is the
    device-resident per-slot health carry (last chunk-end stat + count
    of consecutive non-decreasing chunks), reset on admitted rows.  The
    health pass runs *after* the iteration loop and only reads its
    outputs — the iteration math is byte-identical either way, which is
    the watchdog's bitwise-while-healthy guarantee.

    Phase 1 — **admission splice**: slots flagged in ``admit`` (an (S,)
    bool mask) already hold their new family data rows in ``slab.data``
    (written before the tick by :func:`make_row_writer`, one row per
    admitted request); the splice overwrites the rest of each admitted
    row in place from the per-slot vectors: regularization weight, a
    column-norm / base-τ row computed from the slab's data, and a fresh
    :class:`FlexaState` initialized exactly as a solo solve would
    (``_instance_init`` with the *request id* folded into the PRNG
    stream, so a request's trajectory never depends on its slot or
    neighbours).  Non-admitted rows are ignored (masked select), so the
    host can leave stale bytes in the per-slot vectors.

    Phase 2 — **K iterations** of :func:`_frozen_step` on every
    unstopped slot: a slot flips its own ``stop`` bit the moment it
    converges (``stat ≤ tol``) or exhausts ``max_iters`` and is frozen
    from the next inner iteration on, so its final state is the state
    at first convergence — the same answer :func:`make_batched_solver`'s
    while_loop produces, independent of the chunk size K.

    Every operation here is per-slot (vmapped iteration, masked row
    selects) — no cross-slot reductions or collectives — which is what
    lets :func:`make_chunk_stepper` wrap the identical body in a
    ``shard_map`` over the slot axis with no communication.
    """
    fam = get_family(spec.family)
    vstep = jax.vmap(partial(_instance_step, spec, cfg))
    vinit = jax.vmap(partial(_instance_init, spec, cfg))
    vtau = jax.vmap(lambda csq: _tau_base(fam.half_curv(csq), cfg, spec.n))

    def splice(slab: SlabState, admit, new_c, new_x0, new_ids,
               new_active, new_tol) -> SlabState:
        # Masked in-place splice of admitted rows, whose data the row
        # writer has already put in the slab.  The fresh per-row
        # quantities are computed for every row and selected by the
        # mask — cheaper than dynamic gathers at slab widths, and every
        # slab row is finite (data or zero placeholders) so no NaNs can
        # leak through the select.  Its operations carry the ``splice``
        # scope.  On sparse slabs the rows not admitted skip their
        # products (their results are selected away).
        with jax.named_scope("splice"):
            data = gate_designs(slab.data, lambda: admit)
            csq_new = jax.vmap(fam.col_sq)(*data)
            init = vinit(data, new_c, new_x0, new_ids)
            state = jax.tree_util.tree_map(
                lambda s, v: jnp.where(_bmask(admit, s.ndim),
                                       v.astype(s.dtype), s),
                slab.state, init)
            return slab._replace(
                c=jnp.where(admit, new_c, slab.c),
                col_sq=jnp.where(admit[:, None], csq_new, slab.col_sq),
                tau_base=jnp.where(admit[:, None], vtau(csq_new),
                                   slab.tau_base),
                state=state,
                active=jnp.where(admit[:, None], new_active, slab.active),
                tol=jnp.where(admit, new_tol, slab.tol))

    def core(slab: SlabState, stop, admit, new_c, new_x0, new_ids,
             new_active, new_tol):
        # Phase 1 under a cond: the steady-state tick between evictions
        # admits nothing, and the splice's fresh-state/column-norm work
        # (~one iteration's worth of matvecs) should not be paid then.
        # Under shard_map the cond predicate is per-shard, so a device
        # admitting nothing this tick skips its splice independently.
        slab = jax.lax.cond(
            jnp.any(admit),
            lambda s: splice(s, admit, new_c, new_x0, new_ids,
                             new_active, new_tol),
            lambda s: s,
            slab)
        stop = stop & ~admit

        # Phase 2: K frozen-merge iterations.  The stop check reads the
        # slab's per-slot tolerance vector, so one slab can mix tenant
        # tolerances; with every slot at cfg.tol the comparisons are
        # value-identical to the scalar program.
        def body(_, carry):
            state, stop = carry
            state, stop, _ = _frozen_step(
                vstep, cfg, slab.data, slab.c, slab.col_sq, slab.tau_base,
                slab.active, state, stop, slab.tol)
            return state, stop
        state, stop = jax.lax.fori_loop(0, chunk_iters, body,
                                        (slab.state, stop))
        return slab._replace(state=state), stop

    if health is None:
        return core

    H = int(health.stall_window)

    def core_health(slab: SlabState, stop, admit, new_c, new_x0,
                    new_ids, new_active, new_tol, prev_stat, stall):
        # Slots that iterate this chunk: not stopped at entry, or being
        # (re)admitted right now.  Empty slots arrive with stop=True and
        # hold +inf/NaN placeholders, so every verdict below is masked
        # to `ran` rows.
        ran = ~stop | admit
        prev_stat = jnp.where(admit, jnp.inf, prev_stat)
        stall = jnp.where(admit, 0, stall)

        slab, stop_out = core(slab, stop, admit, new_c, new_x0,
                              new_ids, new_active, new_tol)

        stat = slab.state.stat
        finite = (jnp.all(jnp.isfinite(slab.state.x), axis=-1)
                  & jnp.isfinite(slab.state.v_prev)
                  & jnp.isfinite(stat))
        diverged = ran & ~finite
        # Stall counter: +1 each chunk the stat fails to strictly
        # decrease, reset on decrease or normal stop.  The first chunk
        # after admission compares against +inf, so any finite stat
        # counts as a decrease — quarantine therefore lands at chunk
        # H+1 at the earliest.
        decreased = stat < prev_stat
        stall = jnp.where(stop_out | decreased, 0, stall + 1) \
            .astype(stall.dtype)
        stalled = ran & ~stop_out & ~diverged & (stall >= H)

        status = jnp.where(stop_out, STATUS_STOPPED, STATUS_RUNNING)
        status = jnp.where(stalled, STATUS_STALLED, status)
        status = jnp.where(diverged, STATUS_DIVERGED, status) \
            .astype(jnp.int32)
        return slab, status, stat, stall

    return core_health


def _shard_slots(core, spec: BatchedProblemSpec, n_devices: int,
                 n_carry: int):
    """``core`` under ``shard_map`` over a 1-D ``("serve",)`` mesh of
    ``n_devices``, every argument and result partitioned on its leading
    (slot) dimension: each device advances its own contiguous block of
    ``S / n_devices`` slots.  The core is collective-free, so the
    sharded program needs no communication.  ``n_carry`` trailing
    arguments (the health carry) shard like the rest, and the core
    returns ``1 + n_carry`` per-slot vectors after the slab."""
    from jax.sharding import PartitionSpec

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((int(n_devices),), ("serve",))
    row = PartitionSpec("serve")       # shard dim 0, replicate the rest
    slab_specs = SlabState(
        data=jax.tree_util.tree_map(lambda _: row,
                                    slab_data_template(spec)),
        c=row, col_sq=row, tau_base=row,
        state=FlexaState(*([row] * len(FlexaState._fields))),
        active=row, tol=row)
    # stop, admit, then c, x0, ids, active, tol, then the carry
    in_specs = (slab_specs,) + (row,) * (7 + n_carry)
    out_specs = (slab_specs,) + (row,) * (1 + n_carry)
    return jax.shard_map(core, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _build_chunk_stepper(spec: BatchedProblemSpec, cfg: SolverConfig,
                         chunk_iters: int, n_devices: int = 1,
                         health: HealthConfig | None = None):
    """Compile one fused scheduler tick (see :func:`_chunk_core` for the
    phase-by-phase contract):

        chunk(slab, stop, admit, new_c, new_x0, new_ids, new_active,
              new_tol, *health_carry) -> (slab, stop)

    Fusing admission into the step matters operationally: a scheduler
    tick is ONE chunk program and one (S,) mask readback, however many
    requests were admitted — separate per-slot splice calls would pay
    dispatch per admission and dominate the serving makespan at small
    instance sizes.  Only the admitted data rows travel apart
    (:func:`make_row_writer`), so the chunk's arguments hold one data
    slab and (S,)/(S, n) per-slot vectors.  The slab, the stop mask and
    the health carry are donated (in-place advance).

    ``health_carry`` is empty while ``health`` is ``None``.  With
    ``health`` set it is the device-resident per-slot carry
    ``(prev_stat, stall)``, and the readback widens to an int32 status
    vector (still exactly one transfer per tick):

        chunk(slab, stop, admit, ..., new_tol, prev_stat, stall)
            -> (slab, status, prev_stat, stall)

    With ``n_devices > 1`` the slot axis is sharded over that many
    devices (:func:`_shard_slots`, the mesh engine's program); the slab
    capacity S must then be divisible by ``n_devices``.  At one device
    the program is the plain jitted core.
    """
    core = _chunk_core(spec, cfg, chunk_iters, health)
    n_carry = 0 if health is None else 2
    if n_devices > 1:
        core = _shard_slots(core, spec, n_devices, n_carry)

    @partial(jax.jit, donate_argnums=(0, 1) + tuple(range(8, 8 + n_carry)))
    def chunk(slab: SlabState, stop, admit, new_c, new_x0, new_ids,
              new_active, new_tol, *health_carry):
        return core(slab, stop, admit, new_c, new_x0, new_ids,
                    new_active, new_tol, *health_carry)

    return chunk


make_chunk_stepper = CompileCache("chunk_stepper", _build_chunk_stepper)


def read_slots(state: FlexaState, slots) -> list[FlexaState]:
    """Unpack single-instance states out of a stacked :class:`FlexaState`
    (host-side; one small transfer per requested slot)."""
    rows = jax.device_get(
        jax.tree_util.tree_map(lambda a: a[jnp.asarray(slots)], state))
    return [jax.tree_util.tree_map(lambda a: a[i], rows)
            for i in range(len(slots))]


def slab_migrate(slab: SlabState, slots, spec: BatchedProblemSpec,
                 cfg: SolverConfig, capacity: int) -> SlabState:
    """Repack the given live slots into a fresh slab of ``capacity``.

    The drain-tail compaction move: ``slots[i]``'s entire row — family
    data, weights, precomputed norms and the mid-flight
    :class:`FlexaState` — lands bitwise in slot ``i`` of the new slab, so
    a migrated request resumes exactly where it stopped (its PRNG stream
    is keyed by request id, never by slot, so the trajectory is
    slot-independent by construction).  Remaining slots are
    :func:`slab_alloc` placeholders.  Works in both directions: shrink to
    a narrower capacity bucket at the drain tail, or grow back when new
    arrivals need room.
    """
    capacity = int(capacity)
    k = len(slots)
    if k > capacity:
        raise ValueError(
            f"cannot migrate {k} live slots into capacity {capacity}")
    fresh = slab_alloc(spec, cfg, capacity)
    if k == 0:
        return fresh
    sel = jnp.asarray(np.asarray(slots, np.int64).astype(np.int32))

    def move(dst, src):
        return dst.at[:k].set(jnp.take(src, sel, axis=0).astype(dst.dtype))

    return SlabState(
        data=jax.tree_util.tree_map(move, fresh.data, slab.data),
        c=move(fresh.c, slab.c),
        col_sq=move(fresh.col_sq, slab.col_sq),
        tau_base=move(fresh.tau_base, slab.tau_base),
        state=jax.tree_util.tree_map(move, fresh.state, slab.state),
        active=move(fresh.active, slab.active),
        tol=move(fresh.tol, slab.tol),
    )


def _stack_instances(problems: Sequence[Problem]):
    spec = BatchedProblemSpec.of(problems[0])
    for p in problems[1:]:
        other = BatchedProblemSpec.of(p)
        if other != spec:
            raise ValueError(
                f"all instances in a batch must share one shape signature; "
                f"got {spec} and {other}")
    fam = get_family(spec.family)
    data = stack_data([tuple(p.data[k] for k in fam.data_keys)
                       for p in problems], spec)
    c = jnp.asarray([float(p.g_weight) for p in problems], jnp.float32)
    return spec, data, c


def stack_data(per_instance: Sequence[tuple],
               spec: BatchedProblemSpec) -> tuple:
    """Stack per-instance family data tuples along a new leading axis:
    float32 arrays, or sparse designs padded to ``spec.nnz_cap`` with
    their columns filled in."""
    return tuple(
        stack_designs(col, spec.nnz_cap) if is_sparse(col[0])
        else jnp.stack([jnp.asarray(a, jnp.float32) for a in col])
        for col in zip(*per_instance))


def _solve_batched(problems: Sequence[Problem], x0=None,
                   cfg: SolverConfig | None = None,
                   record_history: bool = False,
                   active=None) -> SolverResult:
    """Solve B independent instances in one compiled FLEXA program.

    The instances may come from any registered problem family (lasso,
    group_lasso, logreg, svm) as long as they share one
    :class:`BatchedProblemSpec`.  Returns a :class:`SolverResult` whose
    ``x`` is (B, n) and whose ``iters`` / ``converged`` are per-instance
    ``(B,)`` arrays.  Each row of ``x`` matches the solo
    ``solve(problems[i])`` solution (same cfg) to float32 accuracy.

    ``record_history=True`` switches to a Python-loop driver recording the
    batched trajectory (``history["V"]`` etc. are lists of (B,) arrays) —
    the benchmark path; the default compiled driver records nothing and
    never syncs with the host until convergence — the serving path.

    ``active`` is an optional (B, n) per-instance freeze mask: coordinates
    with mask 0 are excluded from selection, updates and the termination
    measure (the regularization-path engine's screening hook — see
    ``repro.path``).
    """
    cfg = cfg or SolverConfig()
    spec, data, c = _stack_instances(problems)
    B = len(problems)
    if x0 is None:
        x0 = jnp.zeros((B, spec.n), jnp.float32)
    else:
        x0 = jnp.asarray(x0, jnp.float32)
        if x0.shape != (B, spec.n):
            raise ValueError(f"x0 must be (B, n) = {(B, spec.n)}")
    if active is not None:
        active = jnp.asarray(active, jnp.float32)
        if active.shape != (B, spec.n):
            raise ValueError(f"active must be (B, n) = {(B, spec.n)}")

    t0 = time.perf_counter()
    if not record_history:
        run = make_batched_solver(spec, cfg)
        final, converged = run(data, c, x0, active)
        return SolverResult(
            x=final.x, iters=np.asarray(final.k),
            converged=np.asarray(converged), state=final,
            method="flexa_batched",
            meta={"batch": B, "family": spec.family,
                  "wall_s": time.perf_counter() - t0})

    # History path: the same freeze-merge step, stepped from the host so
    # trajectories can be recorded (used by benchmarks).
    step = jax.jit(partial(_frozen_step,
                           jax.vmap(partial(_instance_step, spec, cfg)), cfg))
    col_sq, tau_base = _stack_norms(spec, cfg, data)
    if active is None:
        active = jnp.ones((B, spec.n), jnp.float32)
    state = jax.vmap(partial(_instance_init, spec, cfg))(
        data, c, x0, jnp.arange(B))
    done = jnp.zeros((B,), bool)
    hist: dict[str, list] = {k: [] for k in
                             ("V", "stat", "E_max", "sel_frac", "gamma",
                              "tau_scale", "time")}
    while not np.asarray(done).all():
        state, done, info = step(data, c, col_sq, tau_base, active, state,
                                 done, cfg.tol)
        for key in ("V", "stat", "E_max", "sel_frac", "gamma", "tau_scale"):
            hist[key].append(np.asarray(info[key]))
        hist["time"].append(time.perf_counter() - t0)
    return SolverResult(
        x=state.x, iters=np.asarray(state.k),
        converged=np.asarray(state.stat) <= cfg.tol, state=state,
        history=hist, method="flexa_batched",
        meta={"batch": B, "family": spec.family,
              "wall_s": time.perf_counter() - t0})
