"""Serving engines: two request-batched drivers behind one design idea —
pack concurrent requests into *fixed shape buckets* so each bucket pays XLA
compilation once and every later request rides the compiled program.

1. :class:`ServeEngine` — LM text generation: batched prefill + decode with
   a static-shape KV cache:

   * requests are padded/packed into a fixed (batch, max_len) grid — static
     shapes keep one compiled executable per (batch, len) bucket;
   * prefill builds the cache at ``max_len`` capacity; decode then appends
     one token per step for the whole batch in lock-step (continuous
     batching is a scheduler-level extension: slots free as sequences hit
     EOS);
   * greedy or temperature sampling (seeded, deterministic).

   This is the substrate the decode_32k / long_500k dry-run cells lower
   (``serve_step`` = one engine decode step).

2. :class:`SolverServeEngine` — the paper-side workload: many concurrent
   solve requests from *any* registered problem family (lasso, group
   lasso, sparse logistic regression, ℓ1-ℓ2 SVM — see
   ``repro.problems.families``).  Requests are grouped by shape signature
   (family included), padded up to power-of-two batch buckets, and
   dispatched to the batched multi-instance FLEXA program
   (:func:`repro.solvers.solve_batched`'s compiled core).  One compilation
   per (signature, bucket) is amortized over every subsequent request —
   the "heavy concurrent traffic" scenario from the ROADMAP — and a
   heterogeneous wave (a logreg mix riding along with Lasso traffic) just
   occupies several cache entries.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from repro.config.base import (ModelConfig, ServeConfig, ShapeConfig,
                               SolverConfig)
from repro.deprecation import warn_legacy
from repro.models import io as IO
from repro.obs import trace as obs_trace
from repro.models import transformer as T
from repro.problems.families import get_family
from repro.problems.sparse import is_sparse
from repro.serve.metrics import ServeTelemetry
from repro.solvers.batched import (BatchedProblemSpec, make_batched_solver,
                                   stack_data)


@dataclass
class GenerationResult:
    tokens: np.ndarray        # (batch, generated)
    prefill_logits: np.ndarray


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 256,
                 mesh=None, dp_axes=("data",)):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.dp_axes = dp_axes

        def prefill_fn(params, batch):
            return T.prefill(cfg, params, batch, mesh=mesh, dp_axes=dp_axes)

        def decode_fn(params, token, cache, pos):
            return T.decode_step(cfg, params, token, cache, pos,
                                 mesh=mesh, dp_axes=dp_axes)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

    def _grow_cache(self, cache, batch: int):
        """Re-home the prefill cache into max_len-capacity buffers."""
        shape = ShapeConfig("serve", "decode", self.max_len, batch)
        full = IO.zero_cache(self.cfg, shape)

        def fit(dst, src):
            sl = tuple(slice(0, s) for s in src.shape)
            return dst.at[sl].set(src.astype(dst.dtype))
        return jax.tree_util.tree_map(fit, full, cache)

    def generate(self, prompts: np.ndarray, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 extra_inputs: dict | None = None) -> GenerationResult:
        """prompts: (batch, prompt_len) int32."""
        B, Lp = prompts.shape
        assert Lp + max_new_tokens <= self.max_len
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.use_mrope:
            pos = jnp.broadcast_to(jnp.arange(Lp, dtype=jnp.int32),
                                   (B, Lp))
            batch["positions"] = jnp.broadcast_to(pos[:, None, :],
                                                  (B, 3, Lp))
        if self.cfg.is_encoder_decoder:
            if extra_inputs is None or "enc_embeds" not in extra_inputs:
                raise ValueError("encdec serving needs enc_embeds")
            batch["enc_embeds"] = jnp.asarray(extra_inputs["enc_embeds"])

        logits, cache = self._prefill(self.params, batch)
        cache = self._grow_cache(cache, B)

        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, temperature, key)
        out.append(np.asarray(tok))
        pos = Lp
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            lg, cache = self._decode(self.params, tok, cache,
                                     jnp.asarray(pos, jnp.int32))
            tok = self._sample(lg, temperature, sub)
            out.append(np.asarray(tok))
            pos += 1
        return GenerationResult(
            tokens=np.concatenate(out, axis=1),
            prefill_logits=np.asarray(logits))

    @staticmethod
    def _sample(logits, temperature: float, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        g = jax.random.gumbel(key, logits.shape)
        return jnp.argmax(logits / temperature + g,
                          axis=-1)[:, None].astype(jnp.int32)


# ===================================================================== #
# Batched solver serving (the paper-side workload)                      #
# ===================================================================== #
@dataclass
class SolveRequest:
    """One composite-minimization request:  min F(x) + c·g(x).

    ``family`` picks F (``repro.problems.families``): the quadratic
    families ("lasso"/"group_lasso") read ``A`` as the design matrix and
    need ``b``; "logreg"/"svm" read ``A`` as the label-signed feature
    matrix Z = diag(a)·Y and take no ``b``.  The quadratic families'
    ``A`` may be a :class:`~repro.problems.sparse.CSCDesign` (host
    arrays, as a tenant sends it): such a request is served in a slab of
    sparse designs of its nnz bucket (``BatchedProblemSpec.nnz_cap``).

    ``priority``/``deadline`` are scheduling hints consumed by the
    continuous runtime's admission queue (``repro.serve.continuous``);
    the wave engine serves in submission order and ignores them.

    Warm starts: ``x0`` is spliced into the slab/bucket on admission
    (zeros if unset).  ``warm_from`` is continuous-engine sugar — "use
    the solution of that finished request as my x0"; admission is
    deferred until the referenced request completes (it must be an
    earlier, same-signature request of the same engine).  ``active_mask``
    is a per-coordinate {0,1} freeze mask (safe-screening support —
    ``repro.path``): zero coordinates are excluded from selection,
    updates and the termination measure.
    """
    A: np.ndarray               # (m, n) design / signed-feature matrix,
    #                             or a CSCDesign (quadratic families)
    b: np.ndarray | None = None  # (m,) observations (quadratic families)
    c: float = 1.0              # regularization weight
    block_size: int = 1         # 1 ⇒ ℓ1; >1 ⇒ group-ℓ2 blocks
    family: str = ""            # "" ⇒ lasso/group_lasso by block_size
    x0: np.ndarray | None = None  # optional warm start
    priority: int = 0           # higher = admitted first ("priority" policy)
    deadline: float | None = None  # absolute time ("deadline" policy)
    warm_from: int | None = None   # req_id whose solution becomes x0
    active_mask: np.ndarray | None = None  # (n,) freeze mask (1 = live)
    #: Per-request stopping tolerance (None ⇒ the engine's
    #: ``SolverConfig.tol``).  Consumed by the continuous/mesh slabs,
    #: whose stop check reads a per-slot tolerance vector — one engine
    #: can mix tenant tolerances (the multi-tenant serving scenario, and
    #: what lets ``CVSpec(tol_coarse=)`` ride a shared engine).  The
    #: wave engine compiles one tolerance per program and rejects it.
    tol: float | None = None

    @property
    def spec(self) -> BatchedProblemSpec:
        family = self.family or (
            "lasso" if self.block_size == 1 else "group_lasso")
        return BatchedProblemSpec.for_design(
            self.A, n=int(self.A.shape[1]), block_size=self.block_size,
            g_kind="l1" if self.block_size == 1 else "group_l2",
            family=family)

    def data_arrays(self, spec: BatchedProblemSpec) -> tuple:
        """The family data tuple this request contributes to the stack.

        ``A`` always supplies the leading (m, n) design array whatever the
        family calls it; ``b`` supplies the observation vector.  Families
        with additional per-instance arrays need a richer request type —
        fail loudly rather than guessing.

        The arrays come back as float32 where they already are: host
        arrays stay on the host (no copy when already float32), device
        arrays on their device.  The caller places them, so the
        continuous slab can ship a row straight to the device that owns
        its slot.  A sparse design comes back padded to the slab's nnz
        capacity.
        """
        def f32(a):
            if is_sparse(a):
                return a.padded(spec.nnz_cap)
            if isinstance(a, jax.Array):
                return a.astype(jnp.float32)
            return np.asarray(a, np.float32)

        keys = get_family(spec.family).data_keys
        out = []
        for j, k in enumerate(keys):
            if j == 0:
                out.append(f32(self.A))
            elif k == "b":
                out.append(f32(self.b))
            else:
                raise NotImplementedError(
                    f"SolveRequest has no field for data key {k!r} of "
                    f"family {spec.family!r}")
        return tuple(out)


@dataclass
class SolveResponse:
    """Per-request solver verdict (unbatched back out of the bucket)."""
    x: np.ndarray
    iters: int
    converged: bool
    stat: float                 # final ‖x̂(x)−x‖∞
    bucket: int                 # batch bucket / slab capacity served in
    #: Health verdict: "ok" for a normal completion (converged or
    #: max-iters), "diverged"/"stalled" when the numerical-health
    #: watchdog (``ServeConfig.watchdog``) quarantined the solve,
    #: "timeout" when the continuous engine evicted a past-deadline
    #: request (``ContinuousSolverEngine.expire_overdue``).
    status: str = "ok"


def validate_request(i: "int | None", r: SolveRequest,
                     spec: BatchedProblemSpec) -> None:
    """Shape/family checks shared by the wave and continuous engines —
    raise before any device work so rejection is atomic.  ``i`` is the
    request's position within a wave (``None`` for single-request
    submission paths, where an index would mislead)."""
    where = "request" if i is None else f"request {i}"
    if is_sparse(r.A):
        try:
            r.A.check()
        except ValueError as e:
            raise ValueError(f"{where}: {e}") from None
    needs_b = "b" in get_family(spec.family).data_keys
    if needs_b and np.shape(r.b) != (spec.m,):
        raise ValueError(
            f"{where}: family {spec.family!r} needs b of shape "
            f"({spec.m},), got {np.shape(r.b)}")
    if not needs_b and r.b is not None:
        raise ValueError(
            f"{where}: family {spec.family!r} takes no b")
    if r.x0 is not None and np.shape(r.x0) != (spec.n,):
        raise ValueError(
            f"{where}: x0 must have shape ({spec.n},), got "
            f"{np.shape(r.x0)}")
    if r.active_mask is not None and np.shape(r.active_mask) != (spec.n,):
        raise ValueError(
            f"{where}: active_mask must have shape ({spec.n},), got "
            f"{np.shape(r.active_mask)}")
    if r.warm_from is not None and r.x0 is not None:
        raise ValueError(
            f"{where}: warm_from and x0 are mutually exclusive")
    if r.tol is not None and not (float(r.tol) >= 0):
        raise ValueError(
            f"{where}: tol must be a non-negative float, got {r.tol!r}")


class SolverServeEngine:
    """Serve many concurrent FLEXA solves from shared compiled programs.

    The hot path of "millions of small solves" is not FLOPs but *dispatch*:
    per-request jit tracing, compilation and Python-loop stepping dwarf the
    actual linear algebra at small m×n.  The engine removes all three:

    * requests are grouped by :class:`BatchedProblemSpec` (same family, m,
      n, block structure — the static signature a compiled program is
      specialized to) and stacked;
    * each group is chopped into power-of-two *buckets* (≤ ``max_batch``);
      short remainders are padded by repeating the first request — padding
      rows are dropped before responding.  Under deterministic selection
      rules they converge in lock-step with the request they clone; under
      the randomized rules each batch slot draws its own PRNG stream, so a
      padding clone may take a different trajectory and keep the bucket
      iterating a little longer (bounded by ``cfg.max_iters`` — wasted
      device work only, never a wrong answer);
    * each (spec, bucket) pair hits :func:`make_batched_solver` — a
      bounded-LRU-cached (``repro.solvers.cache``), jitted
      vmap+while_loop program — so compilation happens once per shape
      signature, then every subsequent batch of requests with that
      signature reuses the executable;
    * the whole bucket converges inside ONE device program (stragglers keep
      iterating while finished instances are frozen), so there is no
      per-iteration host sync either.

    ``engine.stats`` reports requests/batches served, padding overhead,
    distinct compiled signatures, and (no longer silent) the padding-waste
    and bucket-occupancy aggregates; ``engine.telemetry`` keeps the full
    per-wave and per-request records (``repro.serve.metrics``) — the
    baseline columns of ``results/bench/BENCH_serve.json``.  The
    amortization measurement in ``results/bench/BENCH_solvers.json``
    (``batched`` section) is produced by ``benchmarks/fig1.run_batched``
    over the same compiled-program cache.
    """

    def __init__(self, cfg: SolverConfig | None = None,
                 serve: ServeConfig | None = None, *,
                 max_batch: int | None = None,
                 telemetry: ServeTelemetry | None = None):
        """``serve`` carries the wave knob (``ServeConfig.max_batch``) —
        the same config object the continuous engine takes, so callers
        configure both runtimes from one place.  The plain ``max_batch=``
        kwarg remains as a back-compat override (it wins when both are
        given).  Prefer the front door: ``repro.client.FlexaClient``
        with ``backend="wave"``."""
        warn_legacy(
            "repro.serve.SolverServeEngine",
            'FlexaClient(backend="wave").run(...)')
        self.cfg = cfg or SolverConfig()
        self.serve = serve or ServeConfig()
        self.max_batch = int(self.serve.max_batch if max_batch is None
                             else max_batch)
        self.telemetry = telemetry or ServeTelemetry()
        self.stats = {"requests": 0, "batches": 0, "padded": 0,
                      "signatures": 0, "occupancy": 0.0,
                      "padding_waste": 0.0}
        self._seen: set = set()
        #: Request ids of the most recent wave, aligned with the
        #: `requests` list passed to :meth:`submit` (read by the client
        #: WaveBackend to feed ``FlexaClient.diagnostics()``).
        self.last_request_ids: list[int] = []
        # Running totals for the stats aggregates (cheaper than a full
        # telemetry snapshot per wave, which sorts every latency seen).
        self._row_iters = 0
        self._pad_row_iters = 0
        self._occupancy_sum = 0.0

    # ------------------------------------------------------------- #
    def _bucket(self, count: int) -> int:
        """Smallest power-of-two ≥ count; ``max_batch`` itself is the top
        bucket (the cap holds even when it is not a power of two)."""
        b = 1
        while b < count and b < self.max_batch:
            b *= 2
        return min(b, self.max_batch)

    def submit(self, requests: list[SolveRequest],
               arrivals: list[float] | None = None
               ) -> list[SolveResponse]:
        """Solve a wave of requests; responses align with request order.

        The whole wave is validated before any bucket runs, so a malformed
        request rejects the wave atomically (no partial stats/responses).
        ``arrivals`` optionally backdates each request's telemetry arrival
        timestamp (a request that waited for the server to go idle before
        it could be submitted arrived *earlier* — latency must include
        that wait, or saturated-regime percentiles understate reality).
        """
        by_spec: dict[BatchedProblemSpec, list[int]] = {}
        for i, r in enumerate(requests):
            spec = r.spec
            validate_request(i, r, spec)
            if r.warm_from is not None:
                raise ValueError(
                    f"request {i}: warm_from is a continuous-engine "
                    "feature (the wave engine keeps no per-id results "
                    "to warm from); pass x0 explicitly")
            if r.tol is not None:
                raise ValueError(
                    f"request {i}: per-request tol is a continuous-"
                    "engine feature (the wave program compiles one "
                    "tolerance); configure SolverConfig.tol instead")
            by_spec.setdefault(spec, []).append(i)
        if arrivals is not None and len(arrivals) != len(requests):
            raise ValueError("arrivals must align with requests")

        tele = self.telemetry
        req_ids = [tele.next_request_id() for _ in requests]
        # Expose this wave's request ids (aligned with `requests`) so
        # callers — the client's WaveBackend — can map tickets to the
        # telemetry request traces that diagnostics() renders.
        self.last_request_ids = list(req_ids)
        for i, r in enumerate(requests):
            tele.record_arrival(req_ids[i], r.spec.family, "wave",
                                t=None if arrivals is None
                                else arrivals[i])

        out: list[SolveResponse | None] = [None] * len(requests)
        for spec, idxs in by_spec.items():
            run = make_batched_solver(spec, self.cfg)
            pos = 0
            while pos < len(idxs):
                chunk = idxs[pos:pos + self.max_batch]
                pos += self.max_batch
                B = self._bucket(len(chunk))
                pad = B - len(chunk)
                rows = [requests[i] for i in chunk] \
                    + [requests[chunk[0]]] * pad
                per_req = [r.data_arrays(spec) for r in rows]
                data = stack_data(per_req, spec)
                c = jnp.asarray([float(r.c) for r in rows], jnp.float32)
                x0 = jnp.stack([
                    jnp.zeros((spec.n,), jnp.float32) if r.x0 is None
                    else jnp.asarray(r.x0, jnp.float32) for r in rows])
                if any(r.active_mask is not None for r in rows):
                    active = jnp.stack([
                        jnp.ones((spec.n,), jnp.float32)
                        if r.active_mask is None
                        else jnp.asarray(r.active_mask, jnp.float32)
                        for r in rows])
                else:
                    active = None

                for i in chunk:
                    tele.record_admit(req_ids[i])
                t0 = time.perf_counter()
                with obs_trace.span("serve.wave", cat="wave", bucket=B,
                                    n_real=len(chunk), padded=pad,
                                    family=spec.family):
                    final, converged = run(data, c, x0, active)
                    xs = np.asarray(final.x)     # device sync: wave is done
                wall = time.perf_counter() - t0
                ks = np.asarray(final.k)
                stats_ = np.asarray(final.stat)
                conv = np.asarray(converged)
                for j, i in enumerate(chunk):
                    out[i] = SolveResponse(
                        x=xs[j], iters=int(ks[j]), converged=bool(conv[j]),
                        stat=float(stats_[j]), bucket=B)
                    tele.record_completion(req_ids[i], iters=int(ks[j]),
                                           converged=bool(conv[j]))
                tele.record_wave(bucket=B, n_real=len(chunk),
                                 iters=ks[:len(chunk)], wall_s=wall,
                                 device_iters_max=int(ks.max()),
                                 flops=(B * int(ks.max())
                                        * spec.m * spec.n))

                self.stats["requests"] += len(chunk)
                self.stats["batches"] += 1
                self.stats["padded"] += pad
                self._seen.add((spec, B))
                self._row_iters += B * int(ks.max())
                self._pad_row_iters += pad * int(ks.max())
                self._occupancy_sum += len(chunk) / B
        self.stats["signatures"] = len(self._seen)
        if self.stats["batches"]:
            self.stats["occupancy"] = \
                self._occupancy_sum / self.stats["batches"]
        if self._row_iters:
            self.stats["padding_waste"] = \
                self._pad_row_iters / self._row_iters
        return out  # type: ignore[return-value]
