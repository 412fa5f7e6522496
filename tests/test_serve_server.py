"""PlanServer lifecycle: admission backpressure, deadlines, graceful drain,
batch coalescing, the shared tiered cache across workers and restarts, and
HTTP round-trip parity (served artifact ``diff()``-clean against a direct
``Session.solve``).
"""

import threading
import time

import pytest

from repro.api import Policy, Problem, Session
from repro.serve import (
    DeadlineExceeded,
    PlanClient,
    PlanRequestError,
    PlanServer,
    ServerBusy,
    ServerClosed,
)


def _problem(scale: float = 1.0) -> Problem:
    return Problem(w=[1.0, 2.0 * scale], z=[0.1], v_comm=[1.0],
                   v_comp=[3.0 * scale])


_POLICY = Policy(installments=2, backend="batched")


def _blocked_server(**kw):
    """A 1-worker server whose (single) session blocks until released —
    the deterministic way to test queue behaviour."""
    server = PlanServer(workers=1, policy=_POLICY, **kw)
    release = threading.Event()
    entered = threading.Event()
    real = server.sessions[0].solve_bulk

    def blocking(problems, *a, **k):
        entered.set()
        assert release.wait(timeout=60), "test forgot to release the worker"
        return real(problems, *a, **k)

    server.sessions[0].solve_bulk = blocking
    return server, release, entered


# ---------------- solving + parity ----------------


def test_plan_matches_direct_session():
    with PlanServer(workers=2, policy=_POLICY) as server:
        p = _problem()
        art = server.plan(p)
        assert art.ok
        ref = Session(_POLICY).solve(p)
        assert art.diff(ref) == {}


def test_submit_burst_resolves_everything():
    with PlanServer(workers=2, policy=_POLICY, max_batch=8) as server:
        futs = [server.submit(_problem(1.0 + 0.05 * k)) for k in range(16)]
        arts = [f.result(timeout=120) for f in futs]
        assert all(a.ok for a in arts)
        # attribution: each artifact answers its own problem
        for k, a in enumerate(arts):
            assert a.problem.v_comp[0] == pytest.approx(3.0 * (1.0 + 0.05 * k))


def test_mixed_policy_batch_groups_correctly():
    with PlanServer(workers=1, policy=_POLICY, max_batch=16) as server:
        p1 = Policy(installments=1, backend="batched")
        futs = []
        for k in range(6):
            futs.append(server.submit(_problem(1.0 + 0.1 * k),
                                      policy=p1 if k % 2 else None))
        arts = [f.result(timeout=120) for f in futs]
        assert all(a.ok for a in arts)
        for k, a in enumerate(arts):
            assert a.q == ((1,) if k % 2 else (2,))


def test_workers_share_one_cache():
    with PlanServer(workers=2, policy=_POLICY) as server:
        p = _problem()
        first = server.plan(p)
        assert not first.cache_hit
        hits = [server.plan(p) for _ in range(4)]
        assert all(a.cache_hit for a in hits)
        assert all(a.diff(first) == {} for a in hits)


def test_store_backed_server_restart_serves_hits(tmp_path):
    path = str(tmp_path / "plans.sqlite")
    p = _problem()
    with PlanServer(store=path, policy=_POLICY) as first:
        a1 = first.plan(p)
        assert a1.ok and not a1.cache_hit
    with PlanServer(store=path, policy=_POLICY) as second:  # "restart"
        a2 = second.plan(p)
        assert a2.cache_hit
        assert a2.diff(a1) == {}
        assert second.cache.store_hits == 1


def test_worker_threads_keep_float64_device_outputs(monkeypatch):
    """JAX's x64 scope is thread-local: the engine enters it itself, so the
    solve and replay programs a worker thread runs return float64 — a
    silent float32 downcast would change the numerics."""
    import repro.engine.batched_sim as bsim
    import repro.engine.batched_simplex as bsx

    seen = []

    def spy(real, name):
        def wrapped(*args):
            out = real(*args)
            outs = out if isinstance(out, tuple) else (out,)  # solve: packed
            seen.append((name, threading.current_thread().name,
                         {str(o.dtype) for o in outs if o.dtype.kind == "f"}))
            return out
        return wrapped

    monkeypatch.setattr(bsx, "_solve_batch", spy(bsx._solve_batch, "solve"))
    monkeypatch.setattr(bsim, "_sim_batch", spy(bsim._sim_batch, "replay"))
    with PlanServer(workers=1, policy=_POLICY) as server:
        assert server.plan(_problem(1.7)).ok
    assert {name for name, _, _ in seen} == {"solve", "replay"}
    for name, thread, dtypes in seen:
        assert thread.startswith("plan-worker"), (name, thread)
        assert dtypes == {"float64"}, (name, dtypes)


def test_sharding_needs_an_engine_backend():
    """devices/n_shards reach the worker's engine handle, and a backend
    that cannot shard refuses them instead of dropping them."""
    with pytest.raises(ValueError, match="cannot shard"):
        PlanServer(workers=1, policy=Policy(installments=2), n_shards=2)
    strict = Policy(installments=2, backend="batched", fallback=False)
    with PlanServer(workers=2, policy=strict, n_shards=2) as server:
        for s in server.sessions:
            h = s.backend("batched", fallback=False)
            assert h.n_shards == 2 and h.devices is None
        assert server.plan(_problem(1.1)).ok


# ---------------- admission: backpressure + deadlines ----------------


def test_backpressure_rejects_when_queue_full():
    server, release, entered = _blocked_server(queue_limit=2)
    try:
        first = server.submit(_problem())  # occupies the worker
        assert entered.wait(timeout=60)
        q1 = server.submit(_problem(1.1))  # fills the queue...
        q2 = server.submit(_problem(1.2))
        with pytest.raises(ServerBusy, match="queue full"):
            server.submit(_problem(1.3))  # ...and the bound holds
        release.set()
        for f in (first, q1, q2):
            assert f.result(timeout=120).ok  # nothing admitted was lost
    finally:
        release.set()
        server.close()


def test_deadline_expired_in_queue_never_solves():
    server, release, entered = _blocked_server(queue_limit=8)
    try:
        first = server.submit(_problem())
        assert entered.wait(timeout=60)
        doomed = server.submit(_problem(1.1), deadline_s=0.05)
        alive = server.submit(_problem(1.2), deadline_s=600)
        time.sleep(0.2)  # let the doomed job's deadline lapse while queued
        release.set()
        assert first.result(timeout=120).ok
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=120)
        assert alive.result(timeout=120).ok
    finally:
        release.set()
        server.close()


# ---------------- drain semantics ----------------


def test_close_drains_admitted_work():
    server, release, entered = _blocked_server(queue_limit=8)
    futs = [server.submit(_problem(1.0 + 0.1 * k)) for k in range(4)]
    assert entered.wait(timeout=60)
    closer = threading.Thread(target=server.close)
    closer.start()
    assert server.draining
    with pytest.raises(ServerClosed):
        server.submit(_problem())  # no new work while draining
    release.set()
    closer.join(timeout=120)
    assert not closer.is_alive()
    assert all(f.result(timeout=1).ok for f in futs)  # every admitted job ran


def test_close_without_drain_fails_pending_futures():
    server, release, entered = _blocked_server(queue_limit=8)
    running = server.submit(_problem())
    assert entered.wait(timeout=60)
    queued = server.submit(_problem(1.1))
    release.set()
    server.close(drain=False)
    assert running.result(timeout=120).ok  # in-flight work still lands
    with pytest.raises(ServerClosed):
        queued.result(timeout=1)


def test_close_is_idempotent_and_healthz_reports_draining():
    server = PlanServer(workers=1, policy=_POLICY)
    assert server.healthz()["status"] == "ok"
    server.close()
    server.close()  # second close is a no-op, not an error
    assert server.healthz()["status"] == "draining"
    with pytest.raises(ServerClosed):
        server.plan(_problem())


# ---------------- the HTTP front door ----------------


def test_http_round_trip_parity_and_observability():
    with PlanServer(workers=1, policy=_POLICY, port=0) as server:
        assert server.port and server.port > 0
        client = PlanClient(f"http://localhost:{server.port}")

        h = client.healthz()
        assert h["status"] == "ok" and h["workers"] == 1

        p = _problem(1.3)
        art = client.plan(p)
        assert art.ok and art.problem == p
        ref = Session(_POLICY).solve(p)
        assert art.diff(ref) == {}  # the wire round trip loses nothing

        text = client.metrics_text()
        assert "repro_serve_requests_total" in text
        assert "repro_serve_admitted_total" in text


def test_http_error_mapping():
    import json
    import urllib.request

    with PlanServer(workers=1, policy=_POLICY, port=0) as server:
        base = f"http://localhost:{server.port}"
        client = PlanClient(base)
        # bad request: unparseable problem -> 400 PlanRequestError
        req = urllib.request.Request(
            base + "/v1/plan", data=json.dumps({"problem": {"w": "x"}}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(Exception):
            urllib.request.urlopen(req, timeout=30)
        with pytest.raises(PlanRequestError) as ei:
            client._post("/v1/plan", {"problem": {"nonsense": 1}})
        assert ei.value.status == 400
        # unknown endpoint -> 404
        with pytest.raises(PlanRequestError) as ei:
            client._post("/v1/other", {})
        assert ei.value.status == 404
