"""The client session: one object fronting every way of running the
solver stack.

    from repro.client import FlexaClient, SoloSpec, BatchSpec

    client = FlexaClient()                        # inline backend
    r = client.run(SoloSpec(problem))             # submit + wait

    client = FlexaClient(backend="continuous",
                         solver=SolverConfig(tol=1e-7, tau_adapt=False),
                         serve=ServeConfig(slab_capacity=8))
    tickets = [client.submit(SoloSpec(p)) for p in problems]
    for ticket, result in client.stream():        # completion order
        ...

``submit`` validates + normalizes the spec and hands it to the
configured backend (eager for ``inline``, admitted for
``continuous``/``mesh``); ``run`` is submit-then-wait; ``step``
advances asynchronous backends one scheduler round; ``stream`` yields
``(ticket, result)`` pairs in completion order until the session is
drained.  Results are identical across backends (the equivalence matrix
in ``tests/test_client.py``), so backend choice is purely an
execution-policy decision.
"""
from __future__ import annotations

import itertools
from typing import Iterator

from repro.client.backends import Backend, make_backend
from repro.client.errors import ClientError
from repro.client.specs import TicketDiagnostics, WorkItem, normalize
from repro.config.base import ClientConfig, ServeConfig, SolverConfig
from repro.obs import trace as obs
from repro.serve.metrics import MeshTelemetry, ServeTelemetry


class FlexaClient:
    """One front door: typed specs in, backend-independent results out.

    Configuration composes: pass a full :class:`ClientConfig`, or any of
    the ``backend=`` / ``solver=`` / ``serve=`` overrides (overrides
    win over the config object's fields).  A shared
    :class:`ServeTelemetry` may be injected for cross-engine
    apples-to-apples latency accounting (the load benchmark does).
    """

    def __init__(self, config: ClientConfig | None = None, *,
                 backend: str | None = None,
                 solver: SolverConfig | None = None,
                 serve: ServeConfig | None = None,
                 telemetry: ServeTelemetry | None = None):
        cfg = config or ClientConfig()
        if backend is not None:
            cfg = cfg.replace(backend=backend)
        if solver is not None:
            cfg = cfg.replace(solver=solver)
        if serve is not None:
            cfg = cfg.replace(serve=serve)
        self.config = cfg
        # The mesh backend records chunk counters per device, which
        # takes the MeshTelemetry subclass (sized lazily by the engine
        # once it knows its mesh).
        if telemetry is None:
            telemetry = (MeshTelemetry() if cfg.backend == "mesh"
                         else ServeTelemetry())
        self.telemetry = telemetry
        self._backend: Backend = make_backend(cfg, self.telemetry)
        self._tickets = itertools.count()
        self._items: dict[int, WorkItem] = {}
        self._completed: list[int] = []     # completion order
        self._streamed = 0                  # stream() read cursor

    # ------------------------------------------------------------- #
    @property
    def backend(self) -> str:
        return self._backend.name

    @property
    def pending(self) -> int:
        """Accepted-but-unfinished tickets."""
        return self._backend.pending

    def submit(self, spec, *, arrival: float | None = None) -> int:
        """Validate, normalize and hand one workload to the backend.

        Returns the ticket used by :meth:`result` / :meth:`stream`.
        ``arrival`` optionally backdates the telemetry arrival timestamp
        (serving backends; a request that waited client-side arrived
        earlier than it was submitted).
        """
        item = normalize(spec, next(self._tickets))
        self._backend.validate(item)
        # Register only after the backend accepted the work: an eager
        # (inline) execution error must not leak a half-registered
        # ticket — rejection stays atomic.
        with obs.span("client.submit", cat="client", ticket=item.ticket,
                      kind=item.kind, backend=self._backend.name):
            done = self._backend.submit(item, arrival=arrival)
        self._items[item.ticket] = item
        self._completed.extend(done)
        return item.ticket

    def step(self) -> list[int]:
        """Advance the backend one scheduler round; returns the tickets
        completed by it (inline work completes at submit instead)."""
        with obs.span("client.step", cat="client",
                      backend=self._backend.name,
                      pending=self._backend.pending):
            done = self._backend.step()
        self._completed.extend(done)
        return done

    def result(self, ticket: int, *, wait: bool = True):
        """The completed result of ``ticket`` (``None`` if still in
        flight and ``wait=False``; steps the backend to completion
        otherwise)."""
        if ticket not in self._items:
            raise KeyError(f"unknown ticket {ticket!r}")
        r = self._backend.result(ticket)
        while r is None and wait:
            if not self._backend.pending:
                raise ClientError(
                    f"ticket {ticket} never completed and the backend "
                    "has no pending work — this is a bug")
            self.step()
            r = self._backend.result(ticket)
        return r

    def run(self, spec):
        """Submit one spec and wait for its result (the one-shot path)."""
        return self.result(self.submit(spec))

    def stream(self) -> Iterator[tuple]:
        """Yield ``(ticket, result)`` in completion order, stepping the
        backend as needed, until every submitted workload has been
        yielded.  Interleaving further ``submit`` calls is allowed —
        newly submitted work joins the stream."""
        while True:
            while self._streamed < len(self._completed):
                t = self._completed[self._streamed]
                self._streamed += 1
                yield t, self._backend.result(t)
            if not self._backend.pending:
                return
            self.step()

    def drain(self) -> dict[int, object]:
        """Step until idle; returns {ticket: result} for everything
        completed so far in this session."""
        while self._backend.pending:
            self.step()
        return {t: self._backend.result(t) for t in self._completed}

    # ------------------------------------------------------------- #
    def stats(self) -> dict:
        """Backend counters + the session telemetry snapshot."""
        return {**self._backend.stats(),
                "telemetry": self.telemetry.snapshot()}

    def diagnostics(self, ticket: int) -> TicketDiagnostics:
        """Per-request lifecycle view of one ticket: every engine
        request it spawned, as :meth:`RequestTrace.as_dict` dicts (with
        residual-trajectory ``samples`` when
        ``telemetry.sample_progress`` is on) — the dashboard's
        convergence-sparkline feed.  All backends (serve, inline)
        keep the ticket → request-id mapping.
        """
        if ticket not in self._items:
            raise KeyError(f"unknown ticket {ticket!r}")
        item = self._items[ticket]
        traces = []
        for rid in self._backend.request_ids(ticket):
            t = self.telemetry.requests.get(rid)
            if t is not None:
                traces.append(t.as_dict())
        return TicketDiagnostics(
            ticket=ticket, kind=item.kind, backend=self._backend.name,
            done=self._backend.result(ticket) is not None,
            requests=traces)

    def close(self) -> None:
        """Release backend resources (engines keep no device locks —
        this mainly makes the session's end explicit)."""
        self._backend.close()

    def __enter__(self) -> "FlexaClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
