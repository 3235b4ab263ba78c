"""Share of the window's slot-iterations that advanced no request
(scheduler): empty slots, and slots held after their request converged
inside a chunk.

Each ``serve.chunk`` span is one chunk dispatch, which steps every slot
of the slab ``chunk_iters`` times; the requests of the window advanced
the iterations their answers report.  So the share is
1 − Σ iters / (chunks · slab_capacity · chunk_iters), the program's own
(padding + freeze) / row iterations over the window.  Nothing without
chunk spans."""


def read(rec):
    chunks = sum(1 for n, _, _ in rec.get("host_spans") or []
                 if n == "serve.chunk")
    serve = rec["traffic"]["serve"]
    rows = chunks * int(serve["slab_capacity"]) * int(serve["chunk_iters"])
    if rows == 0:
        return None
    return 100.0 * (1.0 - sum(rec.get("iters") or []) / rows)
