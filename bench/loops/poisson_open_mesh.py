"""Open loop over the mesh backend: ``poisson_open``'s schedule, client
and window, with a warm-up that reaches every device.

The mesh slab writes an admitted request's rows on the device that owns
its slot, so each device runs its own compiled row writer.
``poisson_open``'s warm-up admits one request, which compiles the
writer of device 0 alone; the other devices' would then compile inside
the window.  Here the warm-up also submits one request per device at
once (least-loaded routing sends one to each) and serves them to the
end.  Traffic keys as ``poisson_open``'s; ``serve.mesh_devices`` gives
the device count (0: every visible device).
"""
from __future__ import annotations

from bench.loops import poisson_open as open_loop


def setup(run) -> dict:
    import jax
    from repro.client import SoloSpec

    st = open_loop.setup(run)
    t, client = run.traffic, st["client"]
    devices = int(t["serve"].get("mesh_devices") or len(jax.devices()))
    with run.annotate("bench.warmup"):
        tickets = [client.submit(SoloSpec(st["problems"][0]))
                   for _ in range(devices)]
        give_up = run.now() + float(t["drain_limit_s"])
        while any(client.result(tk, wait=False) is None for tk in tickets) \
                and run.now() < give_up:
            client.step()
    return st


window = open_loop.window
release = open_loop.release
