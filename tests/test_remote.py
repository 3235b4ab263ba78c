"""``repro.remote`` — wire protocol round-trips + live-server contracts.

Two layers:

1. **Protocol** (no server): the ndarray/problem/spec/result codecs
   round-trip bitwise, schema mismatches fail loudly, and telemetry
   snapshots survive a JSON round-trip under their frozen schema.
2. **Service** (subprocess on a loopback port): the remote backend's
   results match inline within the stack's 1e-5 envelope, quota
   rejections surface as the typed ``QuotaExceeded`` and stay observable
   in ``/stats``, past-deadline requests come back ``status="timeout"``
   through the normal eviction path, and SIGTERM drains gracefully
   (admitted work completes, telemetry is flushed, ``DRAINED`` printed).

The live tests share one module-scoped server running the calibrated
equivalence config (``tol=1e-7, tau_adapt off`` — the configuration the
backend matrix in test_client.py is calibrated against).
"""
import json
import os
import signal
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.client import (ClientConfig, FlexaClient, BatchSpec, CVSpec,
                          PathSpec, SoloSpec, UnsupportedWorkloadError,
                          normalize)
from repro.client.errors import ClientError
from repro.config.base import SolverConfig
from repro.problems.lasso import nesterov_instance
from repro.problems.logreg import random_logreg_instance
from repro.remote import QuotaExceeded, SCHEMA, protocol
from repro.remote.protocol import ProtocolError

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")
CFG = SolverConfig(tol=1e-7, max_iters=4000, tau_adapt=False)
SERVER_ARGS = ["--tol", "1e-7", "--max-iters", "4000", "--no-tau-adapt"]


def _instance(family="lasso", seed=0, **kw):
    if family == "lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed, **kw)
    if family == "group_lasso":
        return nesterov_instance(m=24, n=64, nnz_frac=0.1, c=1.0,
                                 seed=seed, block_size=4)
    return random_logreg_instance(m=24, n=48, nnz_frac=0.15, c=0.5,
                                  seed=seed)


# ------------------------------------------------------------------ #
# 1a. ndarray codec                                                  #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "bool"])
def test_array_roundtrip_bitwise(dtype):
    rng = np.random.default_rng(0)
    a = (rng.standard_normal((3, 5)) * 10).astype(dtype)
    out = protocol.decode_array(protocol.encode_array(a))
    assert out.dtype == a.dtype and out.shape == a.shape
    np.testing.assert_array_equal(out, a)


def test_array_roundtrip_survives_json():
    a = np.linspace(-1, 1, 7, dtype=np.float64)
    wire = json.loads(protocol.dumps({"a": protocol.encode_array(a)}))
    np.testing.assert_array_equal(protocol.decode_array(wire["a"]), a)


def test_array_none_passthrough_and_garbage_rejected():
    assert protocol.encode_array(None) is None
    assert protocol.decode_array(None) is None
    with pytest.raises(ProtocolError, match="not an encoded ndarray"):
        protocol.decode_array({"dtype": "float32"})


# ------------------------------------------------------------------ #
# 1b. Problem + spec codecs                                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("family", ["lasso", "group_lasso", "logreg"])
def test_problem_roundtrip(family):
    p = _instance(family)
    q = protocol.decode_problem(
        json.loads(protocol.dumps(protocol.encode_problem(p))))
    assert q.family == p.family
    assert q.n == p.n and q.block_size == p.block_size
    assert q.g_kind == p.g_kind
    assert float(q.g_weight) == float(p.g_weight)
    for k in p.data:
        if k in json.loads(
                protocol.dumps(protocol.encode_problem(p)))["data"]:
            np.testing.assert_array_equal(np.asarray(q.data[k]),
                                          np.asarray(p.data[k], np.float32))


def _roundtrip_spec(spec):
    item = normalize(spec, ticket=0)
    wire = json.loads(protocol.dumps(protocol.encode_item(item)))
    return protocol.decode_spec(wire)


def test_spec_roundtrip_solo():
    x0 = np.zeros(64, np.float32)
    out = _roundtrip_spec(SoloSpec(problem=_instance(), x0=x0))
    assert type(out).__name__ == "SoloSpec"
    np.testing.assert_array_equal(out.x0, x0)


def test_spec_roundtrip_batch():
    out = _roundtrip_spec(BatchSpec(
        problems=[_instance(seed=s) for s in range(3)]))
    assert type(out).__name__ == "BatchSpec" and len(out.problems) == 3


def test_spec_roundtrip_path():
    out = _roundtrip_spec(PathSpec(problem=_instance(), n_points=4,
                                   lam_min_ratio=0.2, screen=True))
    assert type(out).__name__ == "PathSpec"
    assert out.n_points == 4 and out.lam_min_ratio == 0.2 and out.screen


def test_spec_roundtrip_cv_with_validation():
    folds = [_instance(seed=s) for s in range(2)]
    val = [(np.ones((4, 64), np.float32), np.ones(4, np.float32))
           for _ in folds]
    out = _roundtrip_spec(CVSpec(problems=folds, validation=val,
                                 tol_coarse=1e-3, n_points=3))
    assert type(out).__name__ == "CVSpec"
    assert out.tol_coarse == 1e-3 and len(out.validation) == 2
    np.testing.assert_array_equal(out.validation[0][0], val[0][0])


def test_unknown_schema_rejected():
    item = normalize(SoloSpec(problem=_instance()), ticket=0)
    wire = protocol.encode_item(item)
    wire["schema"] = SCHEMA + 1
    with pytest.raises(ProtocolError, match="schema"):
        protocol.decode_spec(wire)
    with pytest.raises(ProtocolError, match="schema"):
        protocol.decode_result({"schema": SCHEMA + 1, "kind": "solo",
                                "result": {}})


# ------------------------------------------------------------------ #
# 1c. Result codec (encode on "server", decode on "client")          #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def inline_client():
    return FlexaClient(backend="inline", solver=CFG)


@pytest.mark.parametrize("kind,make_spec", [
    ("solo", lambda: SoloSpec(problem=_instance())),
    ("batch", lambda: BatchSpec(problems=[_instance(seed=s)
                                          for s in range(2)])),
    ("path", lambda: PathSpec(problem=_instance(), n_points=3)),
])
def test_result_roundtrip(kind, make_spec, inline_client):
    res = inline_client.run(make_spec())
    wire = json.loads(protocol.dumps(protocol.encode_result(kind, res)))
    out = protocol.decode_result(wire, backend="remote")
    if kind == "path":                       # PathResult stamps in meta
        assert out.meta["backend"] == "remote"
    else:
        assert out.backend == "remote"
    np.testing.assert_allclose(np.asarray(out.x), np.asarray(res.x),
                               rtol=0, atol=0)
    assert getattr(out, "raw", None) is None


def test_result_roundtrip_cv(inline_client):
    folds = [_instance(seed=s) for s in range(2)]
    val = [(np.asarray(_instance(seed=9 + s).data["A"]),
            np.asarray(_instance(seed=9 + s).data["b"]))
           for s in range(2)]
    res = inline_client.run(CVSpec(problems=folds, validation=val,
                                   n_points=3))
    wire = json.loads(protocol.dumps(protocol.encode_result("cv", res)))
    out = protocol.decode_result(wire, backend="remote")
    assert out.best_index == res.best_index
    assert out.best_lambda == pytest.approx(res.best_lambda)
    np.testing.assert_array_equal(np.asarray(out.scores),
                                  np.asarray(res.scores))
    np.testing.assert_array_equal(np.asarray(out.x_best),
                                  np.asarray(res.x_best))
    assert len(out.folds) == 2
    if res.ledger is not None:
        assert out.ledger.as_dict() == res.ledger.as_dict()


# ------------------------------------------------------------------ #
# 1d. Telemetry snapshot schema                                      #
# ------------------------------------------------------------------ #
def test_snapshot_schema_frozen_and_json_roundtrips():
    from repro.serve.metrics import SNAPSHOT_SCHEMA, ServeTelemetry
    tele = ServeTelemetry()
    rid = tele.next_request_id()
    tele.record_arrival(rid, "lasso", "continuous")
    tele.record_admit(rid)
    tele.record_completion(rid, iters=10, converged=True)
    tele.record_timeout()
    snap = tele.snapshot()
    assert snap["schema"] == SNAPSHOT_SCHEMA == 1
    again = json.loads(json.dumps(snap))
    assert again == snap
    assert again["health"]["timeouts"] == 1


def test_dashboard_schema_constant_mirrors_metrics():
    """dashboard stays import-light, so it duplicates the constant —
    this pin keeps the two in lockstep."""
    from repro.obs import dashboard
    from repro.serve.metrics import SNAPSHOT_SCHEMA
    assert dashboard.SNAPSHOT_SCHEMA == SNAPSHOT_SCHEMA


def test_dashboard_rejects_unknown_snapshot_schema():
    from repro.obs.dashboard import check_snapshot_schema
    check_snapshot_schema({"requests": 1})          # pre-versioning: ok
    check_snapshot_schema({"schema": 1})
    with pytest.raises(ValueError, match="only\\s+understands schema"):
        check_snapshot_schema({"schema": 99})


# ------------------------------------------------------------------ #
# 2. Live server                                                     #
# ------------------------------------------------------------------ #
def _spawn_server(extra_args=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.remote.server", "--port", "0",
         *SERVER_ARGS, *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True)
    port = None
    for line in proc.stdout:
        if line.startswith("READY port="):
            port = int(line.split("=")[1])
            break
    if port is None:
        err = proc.stderr.read()
        proc.kill()
        raise RuntimeError(f"server failed to start:\n{err}")
    return proc, f"http://127.0.0.1:{port}"


@pytest.fixture(scope="module")
def server():
    proc, url = _spawn_server()
    yield url
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()


def _remote(url, **cfg):
    return FlexaClient(config=ClientConfig(
        backend="remote", remote_url=url, remote_tenant="pytest",
        solver=CFG, **cfg))


def test_remote_requires_url():
    with pytest.raises(ClientError, match="remote_url"):
        FlexaClient(config=ClientConfig(backend="remote"))


def test_remote_rejects_score_callable(server):
    c = _remote(server)
    with pytest.raises(UnsupportedWorkloadError, match="wire"):
        c.submit(CVSpec(problems=[_instance(seed=s) for s in range(2)],
                        score=lambda prob, x, lam: 0.0))


@pytest.mark.parametrize("family", ["lasso", "logreg"])
def test_remote_solo_matches_inline(server, family, inline_client):
    ref = inline_client.run(SoloSpec(problem=_instance(family)))
    got = _remote(server).run(SoloSpec(problem=_instance(family)))
    assert got.backend == "remote" and got.converged
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(ref.x),
                               atol=1e-5)


def test_remote_path_matches_inline(server, inline_client):
    spec = dict(n_points=4, lam_min_ratio=0.2)
    ref = inline_client.run(PathSpec(problem=_instance("group_lasso"),
                                     **spec))
    got = _remote(server).run(PathSpec(problem=_instance("group_lasso"),
                                       **spec))
    np.testing.assert_allclose(got.lambdas, ref.lambdas, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(ref.x),
                               atol=1e-5)


def test_remote_quota_in_flight_typed_rejection():
    """A dedicated 1-slot server: the second concurrent submit raises
    the typed QuotaExceeded, and the rejection is visible in /stats.

    ``tol=-1`` makes the first request run its full (small) iteration
    budget, so it is deterministically still in flight when the second
    submit arrives — no race against a fast solve."""
    proc, url = _spawn_server(["--max-in-flight", "1", "--tol", "-1",
                               "--max-iters", "2000",
                               "--chunk-iters", "4"])
    try:
        c = _remote(url)
        t1 = c.submit(SoloSpec(problem=_instance()))
        with pytest.raises(QuotaExceeded) as ei:
            c.submit(SoloSpec(problem=_instance(seed=1)))
        assert ei.value.reason == "in_flight"
        assert ei.value.tenant == "pytest"
        assert c.result(t1).iters == 2000    # first ticket unharmed
        stats = c._backend.stats()["server"]
        ten = stats["tenants"]["pytest"]
        assert ten["rejected"]["in_flight"] == 1
        assert ten["in_flight"] == 0         # released on completion
        # Slot free again: submission resumes.
        assert c.run(SoloSpec(problem=_instance(seed=2))).iters == 2000
    finally:
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)


def test_remote_past_deadline_times_out(server):
    """deadline_s=0 expires before the first chunk: the server answers
    through the normal eviction path with status="timeout"."""
    item = normalize(SoloSpec(problem=_instance()), ticket=0)
    msg = protocol.encode_item(item)
    msg.update(tenant="pytest", slo="interactive", deadline_s=0.0)
    req = urllib.request.Request(
        f"{server}/v1/submit", data=protocol.dumps(msg), method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        ticket = json.loads(resp.read())["ticket"]
    with urllib.request.urlopen(
            f"{server}/v1/result/{ticket}?wait_ms=20000",
            timeout=60) as resp:
        out = protocol.decode_result(json.loads(resp.read()))
    assert out.status == "timeout"
    assert not out.converged and out.iters == 0


def test_remote_sigterm_drains_gracefully(tmp_path):
    """SIGTERM with work in flight: admitted work completes, telemetry
    is flushed to --telemetry-out, DRAINED is printed, exit code 0."""
    out_file = tmp_path / "final_snapshot.json"
    proc, url = _spawn_server(["--telemetry-out", str(out_file)])
    c = _remote(url)
    t = c.submit(SoloSpec(problem=_instance()))
    proc.send_signal(signal.SIGTERM)
    # Draining, not dead: the in-flight ticket still completes.
    res = c.result(t)
    assert res.converged
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0
    assert "DRAINED" in out
    snap = json.loads(out_file.read_text())
    assert snap["schema"] == SCHEMA
    assert snap["telemetry"]["completed"] == 1
    # Post-drain: new submissions are refused (server gone).
    with pytest.raises(ClientError):
        c.submit(SoloSpec(problem=_instance(seed=3)))


def test_in_process_server_matches_continuous_and_drains(capsys):
    """One process per chip: the service on a thread of the caller
    answers exactly what the in-process continuous backend answers, and
    its drain returns exit code 0 (no signal handlers off the main
    thread)."""
    from repro.config.base import ServeConfig
    from repro.remote.server import InProcessServer

    server = InProcessServer(SERVER_ARGS)
    try:
        got = _remote(server.url).run(SoloSpec(problem=_instance("lasso")))
    finally:
        server.begin_drain()
        code = server.join(timeout_s=60)
    ref = FlexaClient(backend="continuous", solver=CFG,
                      serve=ServeConfig(slab_capacity=8, chunk_iters=16)) \
        .run(SoloSpec(problem=_instance("lasso")))
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))
    assert code == 0 and "DRAINED" in capsys.readouterr().out
